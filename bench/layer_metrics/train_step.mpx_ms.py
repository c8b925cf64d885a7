"""Device self ms a traced step under ``mpx``: casts, loss scaling,
unscale, finite check, skip-step select; moves train_tokens_per_s.
"""
from bench.scopes import scope_reader

read = scope_reader("mpx")
