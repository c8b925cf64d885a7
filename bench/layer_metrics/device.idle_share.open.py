"""Device idle share of the traced ticks; moves itl_p95_ms."""
from bench.readers import idle_share as read  # noqa: F401
