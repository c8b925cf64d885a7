"""Serving operations of a dense GQA transformer, per engine tick."""
from __future__ import annotations

import numpy as np


def linear_params(m: dict) -> int:
    """Weights of one block's matmuls: q, k, v, o and the MLP."""
    d, h, k = m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd = m["head_dim"] or d // h
    mlp = {"gelu": 2, "swiglu": 3, "geglu": 3}[m["mlp"]]
    return d * h * hd * 2 + d * k * hd * 2 + mlp * d * m["d_ff"]


def context_sum(start, valid) -> int:
    """Sum over the valid query tokens of their causal context length
    (a token at position p attends to p + 1 keys)."""
    start = np.asarray(start, np.int64)
    valid = np.asarray(valid, np.int64)
    return int(np.sum(valid * start + valid * (valid + 1) // 2))


def attention_flops(m: dict, start, valid) -> int:
    """QK^T and PV of the valid queries, every layer."""
    hd = m["head_dim"] or m["d_model"] // m["n_heads"]
    return 4 * m["n_heads"] * hd * context_sum(start, valid) * m["n_layers"]


def tick_flops(m: dict, start, valid) -> int:
    """One ``(slots, chunk)`` tick: the valid tokens through every
    block, their attention, and one unembedded position per live slot.
    The padding columns of the chunk are not counted."""
    valid = np.asarray(valid, np.int64)
    tokens = int(valid.sum())
    live = int((valid > 0).sum())
    return (2 * m["n_layers"] * linear_params(m) * tokens
            + attention_flops(m, start, valid)
            + 2 * m["d_model"] * m["vocab_size"] * live)
