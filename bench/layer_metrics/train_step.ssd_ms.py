"""Device self ms a traced step under ``ssd``: the SSD mixer, forward,
backward and recomputed; moves train_tokens_per_s.
"""
from bench.scopes import scope_reader

read = scope_reader("ssd")
