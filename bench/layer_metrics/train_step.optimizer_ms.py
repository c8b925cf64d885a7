"""Device self ms a traced step under ``optimizer``: clip, AdamW update and
its application; moves train_tokens_per_s.
"""
from bench.scopes import scope_reader

read = scope_reader("optimizer")
