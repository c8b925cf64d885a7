"""Device self ms a traced engine tick under ``layers``: the block scan's
slicing and stacking, the layer norms and residual adds; moves
itl_p95_ms.
"""
from bench.scopes import TICK_SPAN, scope_reader

read = scope_reader("layers", TICK_SPAN)
