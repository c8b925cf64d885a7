"""Device self ms a traced step under ``unembed_loss``: final norm, tied
logits and the cross-entropy; moves train_tokens_per_s.
"""
from bench.scopes import scope_reader

read = scope_reader("unembed_loss")
