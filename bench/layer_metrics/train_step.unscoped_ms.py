"""Device self ms a traced step of ops with no scope tag: what the scopes
miss; moves train_tokens_per_s.
"""
from bench.scopes import scope_reader

read = scope_reader("unscoped")
