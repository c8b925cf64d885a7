"""Device self ms a traced step under ``attention`` in the 8k-token hybrid
cell: the attention block with its splash kernels (counted by name where
their lowering drops the tag, bench/kernel_time.py); moves
train_tokens_per_s.
"""
from bench.kernel_time import scope_reader

read = scope_reader("attention")
