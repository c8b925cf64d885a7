"""Device self ms a traced engine tick of ops with no scope tag: what the
scopes miss; moves itl_p95_ms.
"""
from bench.scopes import TICK_SPAN, scope_reader

read = scope_reader("unscoped", TICK_SPAN)
