"""Device self ms a traced step under ``moe`` in the 8k-token hybrid cell: the
expert-only blocks' grouped expert matmuls (forward, backward and
recomputed), the expert weights' layout copies and the shared expert; moves
train_tokens_per_s.
"""
from bench.scopes import scope_reader

read = scope_reader("moe")
