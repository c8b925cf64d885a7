"""Host time per engine tick outside the device wait; moves itl_p95_ms."""
from bench.readers import host_ms_per_tick as read  # noqa: F401
