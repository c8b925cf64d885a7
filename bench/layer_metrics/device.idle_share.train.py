"""Device idle share of the traced training steps; moves train_tokens_per_s."""
from bench.readers import idle_share as read  # noqa: F401
