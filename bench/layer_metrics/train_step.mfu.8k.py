"""Whole training step's share of the chip's bf16 peak, by the hybrid
model's costs module, in the 8k-token hybrid cell; moves
train_tokens_per_s."""
from bench.readers import train_step_mfu as read  # noqa: F401
