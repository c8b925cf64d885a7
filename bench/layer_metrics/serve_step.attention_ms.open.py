"""Device self ms a traced engine tick under ``attention``: the q, k, v
and output projections, rotary embedding, the KV page writes and the
paged-attention kernel; moves itl_p95_ms.
"""
from bench.scopes import TICK_SPAN, scope_reader

read = scope_reader("attention", TICK_SPAN)
