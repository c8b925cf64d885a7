"""Whole serving tick's share of the bf16 peak, valid tokens only; moves itl_p95_ms."""
from bench.readers import serve_step_mfu as read  # noqa: F401
