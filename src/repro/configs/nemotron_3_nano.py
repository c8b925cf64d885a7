"""nemotron-3-nano-30b-a3b — hybrid Mamba-2 / MoE / GQA, 31.6B-A3.2B.
[hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, model_type nemotron_h]

52 blocks, each ``x + mixer(RMSNorm(x))`` with one mixer: 23 Mamba-2
(``M``: 64 heads x head dim 64, so d_inner 4096, 8 B/C groups, state 128,
conv 4, chunk 128), 23 expert-only (``E``: 128 routed experts of width
1856, top 6 by the DeepSeek-V3 sigmoid router with routed scaling 2.5,
squared-ReLU, one shared expert of width 3712) and 6 GQA attention
(``*``: 32 query heads, 2 KV heads, head dim 128, no biases, no RoPE),
in the published ``hybrid_override_pattern``.  d_model 2688, vocab
131072, untied embeddings, norm eps 1e-5.  Trained, not served:
expert-only blocks have no decode path.
"""
from repro.configs.base import ModelConfig

#: the published hybrid_override_pattern, one letter a block
HYBRID_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
_KIND = {"M": "ssd", "E": "moe", "*": "attn"}


def kinds(pattern: str) -> tuple[str, ...]:
    """Layer kinds of a ``hybrid_override_pattern`` string."""
    return tuple(_KIND[c] for c in pattern)


CONFIG = ModelConfig(
    name="nemotron-3-nano-30b-a3b", family="hybrid",
    n_layers=52, d_model=2688, n_heads=32, n_kv_heads=2, head_dim=128,
    d_ff=1856, vocab_size=131072,
    pattern=kinds(HYBRID_PATTERN), mlp="none", norm="rmsnorm", norm_eps=1e-5,
    moe_experts=128, moe_top_k=6, moe_shared_d_ff=3712,
    moe_routed_scale=2.5,
    ssm_state=128, ssm_headdim=64, ssm_n_heads=64, ssm_groups=8,
    ssm_chunk=128, conv_width=4, rope_theta=0.0, tie_embeddings=False,
)


def smoke() -> ModelConfig:
    return ModelConfig(
        name="nemotron-3-nano-smoke", family="hybrid",
        n_layers=7, d_model=48, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=32, vocab_size=256,
        pattern=kinds("MEMEM*E"), mlp="none", norm="rmsnorm", norm_eps=1e-5,
        moe_experts=16, moe_top_k=4, moe_held=(4, 4), moe_shared_d_ff=64,
        moe_routed_scale=2.5,
        ssm_state=16, ssm_headdim=8, ssm_n_heads=8, ssm_groups=2,
        ssm_chunk=8, conv_width=4, rope_theta=0.0, tie_embeddings=False,
        remat="none",
    )
