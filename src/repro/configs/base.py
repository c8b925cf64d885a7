"""Model / run configuration dataclasses.

One :class:`ModelConfig` drives every assigned architecture through the
unified transformer in :mod:`repro.models.transformer` via a repeating
layer ``pattern`` (see DESIGN.md §4).  :class:`RunConfig` adds the
training-time knobs (precision policy, loss scaling, optimizer, sharding
overrides, remat, grad accumulation).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense|moe|hybrid|ssm|audio|vlm|vision
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                 # 0 -> d_model // n_heads
    # cycled: attn|local_attn|rglru|ssd|moe
    pattern: Tuple[str, ...] = ("attn",)
    window: int = 0                   # sliding window for local_attn
    mlp: str = "swiglu"               # swiglu|geglu|gelu|none
    mlp_bias: bool = False
    moe_experts: int = 0
    moe_top_k: int = 2
    capacity_factor: float = 1.25
    # expert-only blocks (pattern kind "moe"): the DeepSeek-V3 router
    # (sigmoid scores, top-k, weights renormalised and scaled) over
    # moe_experts routed experts of width d_ff, plus one shared expert
    moe_shared_d_ff: int = 0
    moe_held: Tuple[int, int] = (0, 0)    # (first, count) held; count 0: all
    moe_routed_scale: float = 1.0
    qkv_bias: bool = False
    out_bias: bool = False
    attn_softcap: float = 0.0
    final_softcap: float = 0.0
    rope_theta: float = 10000.0       # 0 -> no RoPE (hubert: stub frontend owns positions)
    norm: str = "rmsnorm"             # rmsnorm|layernorm
    norm_eps: Optional[float] = None  # None: rmsnorm 1e-6, layernorm 1e-5
    post_norm: bool = False           # gemma2: post-block norms
    causal: bool = True               # False: encoder-only (hubert)
    tie_embeddings: bool = True
    emb_scale: bool = False           # gemma: embeddings * sqrt(d_model)
    # ssm (mamba2)
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    ssm_n_heads: int = 0              # 0 -> ssm_expand * d_model / ssm_headdim
    ssm_groups: int = 1               # B/C groups; head h reads h // (H/G)
    # rglru (recurrentgemma)
    d_rnn: int = 0
    conv_width: int = 4
    # modality frontends (STUBS per the brief: input_specs provides embeddings)
    frontend: str = "none"            # none|frames|patches
    frontend_dim: int = 0
    num_patches: int = 0
    # execution
    scan_layers: bool = True
    remat: str = "full"               # full|dots|none
    rules_overrides: Tuple[Tuple[str, Any], ...] = ()
    decode_rules_overrides: Tuple[Tuple[str, Any], ...] = ()

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def d_inner(self) -> int:
        if self.ssm_n_heads:
            return self.ssm_n_heads * self.ssm_headdim
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim

    @property
    def moe_held_range(self) -> Tuple[int, int]:
        """(first, count) of the routed experts an expert-only block holds."""
        first, count = self.moe_held
        return first, count or self.moe_experts - first

    def layer_kinds(self) -> Tuple[str, ...]:
        """The concrete kind of each of the n_layers layers."""
        reps = -(-self.n_layers // len(self.pattern))
        return (self.pattern * reps)[: self.n_layers]

    def supports_decode(self) -> bool:
        """Expert-only ("moe") blocks are trained, not served."""
        return (self.causal and self.family not in ("audio", "vision")
                and "moe" not in self.pattern)

    def sub_quadratic(self) -> bool:
        """True if every layer's decode state is bounded (or constant) —
        the criterion for running the long_500k cell (DESIGN.md §4)."""
        kinds = set(self.layer_kinds())
        if "attn" in kinds:
            # full-attention layers: unbounded KV growth
            return False
        return True


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Training/serving-time knobs, orthogonal to the architecture."""
    policy: str = "params=float32,compute=bfloat16,output=float32"
    loss_scaling: str = "dynamic"     # dynamic|none  (dynamic is the paper)
    init_scale: float = 2.0 ** 15
    scaling_period: int = 2000
    optimizer: str = "adamw"
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip: float = 1.0
    grad_accum: int = 1
    accum_unroll: bool = False        # unroll the microbatch scan (analysis)
    zero1: bool = True                # shard optimizer state over data axis
    master_weights: str = "params"    # params: paper-faithful fp32 params;
                                      # opt: bf16 working weights + fp32
                                      # master inside (data-sharded) opt
                                      # state — Megatron-style distributed
                                      # optimizer (§Perf iteration A-4)
    compress_grads: bool = False      # bf16 cross-DP gradient reduction
    moe_aux_weight: float = 0.01
    seed: int = 0
