"""Model / shape configuration system.

Every assigned architecture is a ``ModelConfig`` in its own module under
``repro.configs``; the registry resolves ``--arch <id>``. Shapes are the four
assigned input-shape cells with applicability rules (DESIGN.md §4).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    num_shared: int = 0
    d_expert: int = 0           # expert FFN width (fine-grained MoE)
    capacity_factor: float = 1.25
    group_size: int = 256       # dispatch group (bounds the one-hot temp)
    first_dense: int = 0        # leading layers with a dense d_ff MLP (hf first_k_dense_replace)
    norm_topk: bool = True      # renormalise the top-k gates to sum to 1 (hf norm_topk_prob)


@dataclass(frozen=True)
class QuantConfig:
    """EXAQ as a first-class feature (paper §3-§4)."""

    softmax_impl: str = "exaq"   # exact | exaq | naive
    bits: int = 2
    clip_rule: str = "paper"     # paper (Table 1) | analytic (Eq. 14 re-derivation)
    sigma_default: float = 2.0   # fallback before calibration (Fig. 6 mid-range)
    use_fused_kernel: bool = False  # fused flash-EXAQ Pallas kernel (via shard_map under a mesh)
    sp_decode: bool = False      # seq-parallel decode: EXAQ integer-count combine over 'model'


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int               # 0 => attention-free
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0            # 0 => d_model // num_heads
    qk_norm: bool = False
    moe: MoEConfig | None = None
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv_width: int = 4
    ssm_chunk: int = 128         # SSD scan chunk; 1 = per-token recurrence
                                 # (paged state serving requires 1, DESIGN.md §13)
    hybrid_period: int = 0       # zamba2: shared attn block every N mamba blocks
    enc_layers: int = 0          # whisper: encoder depth (enc-dec when > 0)
    enc_seq: int = 1500          # whisper: encoder frames (stub frontend)
    frontend: str | None = None  # vlm | audio
    frontend_tokens: int = 256   # vlm: patch embeddings replacing the prefix
    frontend_dim: int = 1024     # stub embedding dim before projection
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    quant: QuantConfig = field(default_factory=QuantConfig)
    remat: str = "full"          # none | full | dots
    pad_vocab_to: int = 1        # pad embed/head vocab dim (TP-divisibility; Megatron-style)
    attn_block_q: int = 512      # q-block size of the streamed attention scan
    attn_scores_bf16: bool = False  # materialize attention scores in bf16 (EXAQ makes this ~free)
    source: str = ""             # provenance note
    expert_parallel: int = 1     # moe: chips that share each expert layer's routed experts
    expert_shard: int = 0        # moe: which of them this program is (holds a contiguous range)

    @property
    def padded_vocab(self) -> int:
        p = max(self.pad_vocab_to, 1)
        return -(-self.vocab_size // p) * p

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    @property
    def held_experts(self) -> tuple[int, int]:
        """[lo, hi): the routed experts this program holds. Expert parallelism
        over ``expert_parallel`` chips gives shard ``s`` the s-th contiguous
        range; the router still scores all ``moe.num_experts``."""
        E, ep, s = self.moe.num_experts, self.expert_parallel, self.expert_shard
        if ep < 1 or E % ep or not 0 <= s < ep:
            raise ValueError(f"{E} experts cannot be shard {s} of {ep}-way expert parallelism")
        n = E // ep
        return s * n, (s + 1) * n

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def enc_dec(self) -> bool:
        return self.enc_layers > 0

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def reduced(self, **overrides) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests."""
        small = dict(
            num_layers=min(self.num_layers, 4 if self.hybrid_period == 0 else 2 * self.hybrid_period),
            d_model=128,
            num_heads=min(self.num_heads, 4) if self.num_heads else 0,
            num_kv_heads=min(self.num_kv_heads, 2) if self.num_kv_heads else 0,
            d_ff=256,
            vocab_size=512,
            head_dim=32 if self.num_heads else 0,
            enc_layers=2 if self.enc_layers else 0,
            enc_seq=16 if self.enc_layers else 1500,
            frontend_tokens=8 if self.frontend == "vlm" else self.frontend_tokens,
            frontend_dim=32 if self.frontend else self.frontend_dim,
            ssm_state=16 if self.ssm_state else 0,
            ssm_head_dim=16 if self.ssm_state else 64,
        )
        if self.moe is not None:
            small["moe"] = MoEConfig(
                num_experts=4,
                top_k=min(self.moe.top_k, 2),
                num_shared=min(self.moe.num_shared, 1),
                d_expert=64,
                capacity_factor=2.0,
                group_size=32,
                first_dense=min(self.moe.first_dense, 1),
                norm_topk=self.moe.norm_topk,
            )
        small.update(overrides)
        return dataclasses.replace(self, **small)

    def with_quant(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, quant=dataclasses.replace(self.quant, **kw))


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Assignment rules: long_500k needs sub-quadratic attention."""
    if shape.name == "long_500k" and cfg.family not in ("ssm", "hybrid"):
        return False, "long_500k skipped: pure full-attention arch (quadratic at 0.5M)"
    return True, ""


_REGISTRY: dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    import repro.configs  # noqa: F401  (populates the registry)

    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_configs() -> list[str]:
    import repro.configs  # noqa: F401

    return sorted(_REGISTRY)
