"""Device self ms a traced step under ``unscoped`` in the 8k-token hybrid cell:
ops with no scope tag, but for the splash kernels: what the scopes miss;
moves train_tokens_per_s.
"""
from bench.kernel_time import scope_reader

read = scope_reader("unscoped")
