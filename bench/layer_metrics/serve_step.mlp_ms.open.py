"""Device self ms a traced engine tick under ``mlp``: the GELU MLP's two
projections and their biases; moves itl_p95_ms.
"""
from bench.scopes import TICK_SPAN, scope_reader

read = scope_reader("mlp", TICK_SPAN)
