"""Host ms a traced step waits in the program's ``data wait`` spans; moves
train_tokens_per_s.
"""
from bench.scopes import data_wait_ms as read  # noqa: F401
