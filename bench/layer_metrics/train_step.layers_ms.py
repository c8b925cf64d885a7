"""Device self ms a traced step under ``layers``: the block scan's
stacking, copies and dynamic-update-slices, the norms and residual adds;
moves train_tokens_per_s.
"""
from bench.scopes import scope_reader

read = scope_reader("layers")
