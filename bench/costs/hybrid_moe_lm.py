"""Training operations of a hybrid Mamba-2 / expert / GQA language model
(Nemotron-H), and the work of its splash-attention kernel calls.

Per token and block, forward, with no recomputation:

- Mamba-2 (``ssd``): the projections z, x, B, C, dt in and the output
  projection; the chunked SSD's C.B^T once per group and scores . x per
  head over the causal part of a chunk ((Q + 1) / 2 earlier positions on
  average), the incoming state and the state update (H P N each);
- attention (``attn``): q, k, v, o and QK^T and PV over the causal part
  of the row ((S + 1) / 2 keys on average);
- experts (``moe``): the router over every routed expert, the shared
  expert, and the routed experts' expected work here, ``top_k`` experts a
  token of which ``held / n_routed`` are held;
- the unembed over the vocabulary held.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def _kinds(m: dict) -> list:
    reps = -(-m["n_layers"] // len(m["pattern"]))
    return (list(m["pattern"]) * reps)[:m["n_layers"]]


def _held(m: dict) -> int:
    first, count = m.get("moe_held", (0, 0))
    return count or m["moe_experts"] - first


def ssd_flops_per_token(m: dict, seq: int) -> float:
    d, n, g = m["d_model"], m["ssm_state"], m["ssm_groups"]
    hp = m["ssm_n_heads"] * m["ssm_headdim"]
    proj = 2 * d * (2 * hp + 2 * g * n + m["ssm_n_heads"]) + 2 * hp * d
    causal = (min(m["ssm_chunk"], seq) + 1) / 2
    return proj + 2 * (causal * g * n + causal * hp + 2 * hp * n)


def attention_flops_per_token(m: dict, seq: int) -> float:
    d, h, k, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    proj = 2 * d * (2 * h * hd + 2 * k * hd)
    return proj + 4 * h * hd * (seq + 1) / 2


def expert_flops_per_token(m: dict) -> float:
    d = m["d_model"]
    routed = m["moe_top_k"] * _held(m) / m["moe_experts"]
    return (2 * d * m["moe_experts"] + 4 * d * m["moe_shared_d_ff"]
            + routed * 4 * d * m["d_ff"])


def forward_flops_per_token(m: dict, seq: int) -> float:
    per = {"ssd": ssd_flops_per_token(m, seq),
           "attn": attention_flops_per_token(m, seq),
           "moe": expert_flops_per_token(m)}
    return (sum(per[k] for k in _kinds(m))
            + 2 * m["vocab_size"] * m["d_model"])            # unembed


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward and backward (twice the forward); recomputation of the
    remat'd blocks is not counted."""
    return 3 * forward_flops_per_token(m, seq)


# --------------------------------------------------------------------------
# kernels: (operations, bytes) of the calls one training step makes
# --------------------------------------------------------------------------

def _forward_runs(m: dict) -> int:
    """A remat'd block runs its forward again in the backward pass."""
    return 1 if m.get("remat", "full") == "none" else 2


def splash_calls(m: dict, batch: int, seq: int) -> list:
    """(operations, bytes) of each splash-attention kernel call of a
    step: per attention block the forward (QK^T, PV) once a forward run,
    dq (QK^T again, dO V^T, dS K) and dkv (QK^T, dO V^T, P^T dO, dS^T Q),
    each matmul over the causal half; bytes: the q, k, v, o, dO and
    gradient rows each call reads or writes once, bf16."""
    h, k, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    matmul = 2 * batch * h * hd * seq * (seq + 1) / 2
    q_rows = batch * seq * h * hd * 2
    kv_rows = batch * seq * k * hd * 2
    fwd = (2 * matmul, 2 * q_rows + 2 * kv_rows)          # q,k,v in; o out
    dq = (3 * matmul, 3 * q_rows + 2 * kv_rows)           # q,dO,k,v; dq
    dkv = (4 * matmul, 2 * q_rows + 4 * kv_rows)          # q,dO,k,v; dk,dv
    layers = _kinds(m).count("attn")
    return layers * (_forward_runs(m) * [fwd] + [dq, dkv])


def roofline_seconds(calls, peaks: dict) -> float:
    """Least time of the calls at the chip's peaks, each bound by compute
    or by HBM bandwidth."""
    return sum(max(ops / peaks["bf16_flops_per_s"],
                   nbytes / peaks["hbm_bytes_per_s"]) for ops, nbytes in calls)


def cell(config: str, traffic: str) -> tuple:
    """(model entry, traffic) of the files named, for a reader that has
    only the trace."""
    bench = os.path.dirname(HERE)
    with open(os.path.join(bench, "configs", config + ".json")) as f:
        m = json.load(f)["model"]
    with open(os.path.join(bench, "traffic", traffic + ".json")) as f:
        return m, json.load(f)

