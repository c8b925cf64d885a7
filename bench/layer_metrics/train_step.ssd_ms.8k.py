"""Device self ms a traced step under ``ssd`` in the 8k-token hybrid cell: the
grouped Mamba-2 mixers, forward, backward and recomputed; moves
train_tokens_per_s.
"""
from bench.scopes import scope_reader

read = scope_reader("ssd")
