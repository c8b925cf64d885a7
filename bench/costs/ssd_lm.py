"""Training operations of a Mamba-2 (SSD) language model."""
from __future__ import annotations


def projection_params(m: dict) -> int:
    """Weights of one block's matmuls: z, x, B, C, dt in; out."""
    d, di = m["d_model"], m["d_model"] * m["ssm_expand"]
    heads = di // m["ssm_headdim"]
    return d * 2 * di + d * 2 * m["ssm_state"] + d * heads + di * d


def ssd_flops_per_token(m: dict, seq: int) -> float:
    """The chunked SSD mixer's matmuls per token and block, chunk
    Q = min(ssm_chunk, seq): C.B^T and scores . x over the causal part
    of the chunk ((Q + 1) / 2 earlier positions on average, so N and
    H P each), the incoming state (H P N) and the state update (H P N)."""
    causal = (min(m["ssm_chunk"], seq) + 1) / 2
    hp = m["d_model"] * m["ssm_expand"]       # heads x head dim
    return 2 * (causal * m["ssm_state"] + causal * hp
                + 2 * hp * m["ssm_state"])


def forward_flops_per_token(m: dict, seq: int) -> float:
    layers = m["n_layers"] * (2 * projection_params(m)
                              + ssd_flops_per_token(m, seq))
    return layers + 2 * m["vocab_size"] * m["d_model"]      # unembed


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward and backward (twice the forward); recomputation of the
    remat'd blocks is not counted."""
    return 3 * forward_flops_per_token(m, seq)
