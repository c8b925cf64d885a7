"""The paged-attention kernel's required operations and bytes.

One call per attention layer attends each slot's chunk of queries to
its cached context.  What it must do:

- operations: QK^T and PV of the valid queries over their causal
  context (padding queries and idle slots need none);
- bytes: for each (slot, kv head), the K and V pages up to the slot's
  length ``start + valid``, page by page, plus reading q and writing
  the output block of every slot (the kernel moves the whole block).
"""
from __future__ import annotations

import numpy as np

from bench.costs.dense_lm import attention_flops


def call_bytes(m: dict, start, valid, *, page_size: int, chunk: int,
               kv_itemsize: int = 2, q_itemsize: int = 2) -> int:
    """Bytes of one layer's kernel call."""
    hd = m["head_dim"] or m["d_model"] // m["n_heads"]
    start = np.asarray(start, np.int64)
    valid = np.asarray(valid, np.int64)
    pages = np.where(valid > 0, -(-(start + valid) // page_size), 0)
    kv = int(pages.sum()) * page_size * hd * kv_itemsize * 2 * m["n_kv_heads"]
    qo = 2 * len(valid) * chunk * m["n_heads"] * hd * q_itemsize
    return kv + qo


def tick_cost(m: dict, start, valid, *, page_size: int, chunk: int) -> tuple:
    """(operations, bytes) of one tick's kernel calls, every layer."""
    ops = attention_flops(m, start, valid)
    nbytes = m["n_layers"] * call_bytes(m, start, valid,
                                        page_size=page_size, chunk=chunk)
    return ops, nbytes


def roofline_seconds(ops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: compute- or bandwidth-bound."""
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
