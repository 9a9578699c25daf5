"""Config system: model + shape + run configs, and the arch registry.

Every assigned architecture registers a ``ModelConfig`` (full size, used only by the
dry-run via ShapeDtypeStruct) and a ``smoke()`` reduction of the same family (used by
CPU tests).  Shapes are the assignment's four LM cells plus DLRM's own shapes.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence

# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 0            # routed experts
    experts_per_token: int = 0    # top-k
    d_expert: int = 0             # per-expert FFN hidden dim
    n_shared_experts: int = 0
    d_shared_expert: int = 0      # FFN hidden dim of the shared expert(s)
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    # 'gather': replicated-token gather/scatter-add + psum (TP-friendly, no a2a)
    # 'a2a'   : explicit all_to_all expert-parallel dispatch (BLS-pipelinable)
    dispatch: str = "gather"


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 64
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk: int = 128              # chunked-scan block length


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | audio | vlm | recsys
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    d_head: int = 0               # 0 -> d_model // n_heads
    # --- attention flavour ---
    rope_theta: float = 10_000.0
    rope_style: str = "neox"      # neox | glm2d (partial/interleaved, chatglm)
    rope_fraction: float = 1.0    # fraction of head dims rotated (chatglm: 0.5)
    qk_norm: bool = False         # qwen3
    qkv_bias: bool = False        # qwen2 / chatglm
    attn_logit_softcap: float = 0.0   # gemma2: 50.0 (0 = off)
    final_logit_softcap: float = 0.0  # gemma2: 30.0
    sliding_window: int = 0       # gemma2 local layers: 4096 (0 = off)
    layer_pattern: str = "global"  # global | local_global (gemma2 alternation)
    post_norms: bool = False      # gemma2 sandwich norms
    norm_plus_one: bool = False   # gemma2 RMSNorm stores w, applies (1+w)
    scale_embeds: bool = False    # gemma2 multiplies embeddings by sqrt(d)
    act: str = "silu"             # silu | gelu | relu2
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    # --- MoE / SSM / hybrid ---
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    shared_attn_every: int = 0    # zamba2: shared attention block cadence
    # --- enc-dec (whisper) ---
    n_encoder_layers: int = 0     # >0 -> encoder-decoder model
    # --- modality frontend stubs ---
    frontend: str = "none"        # none | audio_frames | vision_patches
    d_frontend: int = 0           # raw stub-embedding dim before projection
    n_frontend_tokens: int = 0    # prefix positions fed from the stub
    # --- training ---
    remat: str = "full"           # full | none | dots
    train_accum: int = 1          # gradient-accumulation microbatches
    dtype: str = "bfloat16"

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def is_encdec(self) -> bool:
        return self.n_encoder_layers > 0

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class DLRMConfig:
    """The paper's own model (Naumov et al. reference DLRM)."""

    name: str
    n_dense_features: int = 13
    table_sizes: Sequence[int] = ()
    embed_dim: int = 64                      # s in the paper
    bottom_mlp: Sequence[int] = (512, 256, 64)
    top_mlp: Sequence[int] = (512, 256, 1)
    max_hot: int = 1                         # multi-hot pooling factor (Setting 1: 100)
    arch_interaction_op: str = "dot"         # dot | cat
    dtype: str = "float32"
    # --- fused sparse hot path (DESIGN.md) ---
    sparse_backend: str = "auto"    # ref | pallas | interpret | auto
    # embedding-bag row streaming (DESIGN.md §1): 0 = auto (VMEM-resident
    # table blocks when they fit, DMA streaming of 128-row lane tiles
    # otherwise), > 0 = forced streaming at that block height, -1 = forced
    # resident (fails loudly when the table block cannot fit VMEM)
    row_block: int = 0
    # embedding-bag pooling loop (DESIGN.md §1): 'vector' pools indices in
    # lane-width chunks (whole (chunk, s) row tiles gathered and reduced
    # under a validity mask), 'scalar' keeps the one-row-per-iteration
    # dynamic-slice walk for A/B; 'auto' = vector.  Both are bit-identical
    # to the jnp oracle in f32.
    pool_mode: str = "auto"
    wire_dtype: str = "float32"     # exchange codec: float32 | bfloat16 | int8
    cache_rows: int = 0             # hot-row cache rows per table (0 = off)
    # --- ragged miss-residual exchange (DESIGN.md §6) ---
    # dense:  equal-split butterfly of the full pooled buffer (reference)
    # ragged: cap-padded per-destination buckets of live rows (alltoallv)
    # auto:   ragged iff a cache is active AND the cap beats the dense
    #         buffer (cap * P < B * T); the serving autotuner drives the cap
    exchange: str = "auto"
    ragged_cap: int = 0             # rows per destination bucket (0 = dense-
                                    # equivalent cap, i.e. lossless / auto)
    # --- pipelined exchange (DESIGN.md §7) ---
    # mono: the whole fused (P, slot_bytes) wire buffer moves as ONE
    #       all_to_all per exchange
    # ring: P-1 chunked ppermute rounds over the same buffer, each peer's
    #       chunk defused/decoded/scattered while the next shift flies —
    #       bit-identical output to mono per codec
    # auto: ring when P >= 4 (enough rounds to overlap), mono below
    exchange_pipeline: str = "auto"

    @property
    def n_tables(self) -> int:
        return len(self.table_sizes)

    def replace(self, **kw) -> "DLRMConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Shape cells
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str                     # train | prefill | decode
    seq_len: int
    global_batch: int


TRAIN_4K = ShapeConfig("train_4k", "train", 4_096, 256)
PREFILL_32K = ShapeConfig("prefill_32k", "prefill", 32_768, 32)
DECODE_32K = ShapeConfig("decode_32k", "decode", 32_768, 128)
LONG_500K = ShapeConfig("long_500k", "decode", 524_288, 1)

LM_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)

# DLRM shapes (the paper's own experiments: batch 512, 26 tables, s=64)
DLRM_INFER = ShapeConfig("dlrm_infer", "decode", 1, 512 * 256)  # batch per the paper x 256 chips
DLRM_TRAIN = ShapeConfig("dlrm_train", "train", 1, 512 * 256)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, "ArchSpec"] = {}


@dataclass(frozen=True)
class ArchSpec:
    config: ModelConfig | DLRMConfig
    smoke: Callable[[], ModelConfig | DLRMConfig]
    shapes: Sequence[ShapeConfig] = LM_SHAPES
    # shape names skipped + reason (e.g. long_500k on full-attention archs)
    skips: dict = field(default_factory=dict)


def register(spec: ArchSpec) -> ArchSpec:
    _REGISTRY[spec.config.name] = spec
    return spec


def get_arch(name: str) -> ArchSpec:
    _ensure_loaded()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_archs() -> list[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


_LOADED = False


def _ensure_loaded() -> None:
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    # importing the config modules populates the registry
    from repro.configs import (  # noqa: F401
        chatglm3_6b,
        dlrm_kaggle,
        gemma2_9b,
        granite_moe_3b_a800m,
        llava_next_mistral_7b,
        qwen2_72b,
        qwen2_moe_a2_7b,
        qwen3_14b,
        rwkv6_1_6b,
        whisper_tiny,
        zamba2_2_7b,
    )
