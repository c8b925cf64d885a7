"""90th percentile of admission minus due time; moves ttft_p90_ms."""
from bench.readers import queue_wait_ms_p90 as read  # noqa: F401
