"""Device self ms a traced step under ``moe_route`` in the 8k-token hybrid
cell: the expert-only blocks' router, top-k, sort, dispatch gather and
weighted combine; moves train_tokens_per_s.
"""
from bench.scopes import scope_reader

read = scope_reader("moe_route")
