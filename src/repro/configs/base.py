"""Model/run configuration system.

One ``ModelConfig`` describes every assigned architecture; per-arch modules
(``repro/configs/<id>.py``) export ``CONFIG`` (the exact published config) and
``SMOKE_CONFIG`` (a reduced same-family config for CPU smoke tests).

Block types (``ModelConfig.pattern`` entries):
  "attn"        full causal self-attention + MLP  (decoder block)
  "local"       sliding-window causal self-attention + MLP
  "global"      full causal self-attention + MLP (alias used in 5:1 patterns)
  "mamba"       Mamba2 (SSD) block, no MLP
  "shared_attn" attention+MLP block whose params are SHARED across every
                occurrence (Zamba2-style)
  "moe"         full causal self-attention + MoE FFN
  "mla"         multi-head latent attention (DeepSeek-V2 §2.1) + dense MLP
  "mla_moe"     multi-head latent attention + MoE FFN (routed + shared
                experts, DeepSeek-V3 routing)
``lead`` kinds run once, ahead of the scanned pattern (DeepSeek-V3's
leading dense layers).
Encoder-decoder archs use ``enc_layers``/``dec_layers`` instead of pattern.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Dict, Optional, Tuple

__all__ = ["ModelConfig", "ShapeSpec", "SHAPES", "load_config", "ARCH_IDS"]

ARCH_IDS = (
    "zamba2_7b",
    "qwen25_14b",
    "gemma3_27b",
    "smollm_360m",
    "yi_34b",
    "internvl2_26b",
    "grok1_314b",
    "phi35_moe",
    "whisper_large_v3",
    "mamba2_370m",
)


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """One cell of the (arch x shape) grid."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    # identity
    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    # transformer core
    n_layers: int = 0
    d_model: int = 0
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    vocab: int = 0
    act: str = "swiglu"  # swiglu | gelu
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # layer pattern: repeating unit + tail (len(pattern)*n_repeats + len(tail)
    # == n_layers).  None => homogeneous ("attn" or "moe") stack.
    pattern: Optional[Tuple[str, ...]] = None
    n_repeats: int = 0
    tail: Tuple[str, ...] = ()
    sliding_window: int = 0  # window for "local" blocks
    lead: Tuple[str, ...] = ()  # kinds run once ahead of the pattern scan
    # MLA (the "mla"/"mla_moe" kinds): per token one cached latent row of
    # kv_lora_rank + qk_rope_head_dim, shared by every head
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # MoE
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    # softmax | sigmoid (DeepSeek-V3 noaux_tc: a per-expert correction bias
    # for selection only, the chosen scores divided by their sum)
    router_scoring: str = "softmax"
    routed_scaling: float = 1.0  # routed gates' factor after normalising
    expert_ff: int = 0  # routed expert width (0: d_ff)
    shared_ff: int = 0  # shared experts' total width (0: none)
    # expert parallelism: this chip holds experts expert_offset ..
    # expert_offset + experts_held - 1 of the n_experts it routes over and
    # computes their share with no token dropped (0: all, capacity dispatch)
    experts_held: int = 0
    expert_offset: int = 0
    moe_sharding: str = "tp"  # tp | ep  (hillclimb knob)
    decode_param_mode: str = "fsdp"  # fsdp | tp2d (serving weight layout)
    # SSM (mamba2)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    d_conv: int = 4
    # encoder-decoder
    enc_layers: int = 0
    dec_layers: int = 0
    enc_seq_divisor: int = 2  # encoder frames = seq_len // divisor (stub)
    cross_kv_len: int = 1_500  # fixed encoder context for decode shapes
    # modality stub (vlm)
    n_patch_tokens: int = 0
    # serving / paged KV (the paper's technique)
    page_size: int = 64
    bounded_kv_pages: int = 256  # resident page pool for long_500k AWRP mode
    # awrp | lru | lfu | fifo | arc | car (stateless two-segment) |
    # arc_adaptive | car_adaptive (TRUE adaptive: AdaptiveState in the pool)
    kv_policy: str = "awrp"
    force_paged_decode: bool = False  # AWRP-bounded pool for decode_32k too
    # numerics / execution
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    remat: str = "full"  # none | full
    attention_impl: str = "xla"  # xla | pallas_flash
    attention_schedule: str = "rect"  # rect | balanced (§Perf hillclimb)
    tp_feat: bool = True  # False => pure-DP weights (small-model hillclimb)
    seq_parallel: bool = False  # Megatron-style SP on the residual stream
    # training execution
    microbatches: int = 8  # grad-accum chunks of the global batch
    adam_dtype: str = "float32"
    grad_accum_dtype: str = "float32"
    opt_master: bool = True
    grad_compress: bool = False  # error-feedback int8 gradient all-reduce
    # shapes this arch runs (protocol skips noted in DESIGN.md §5)
    run_shapes: Tuple[str, ...] = ("train_4k", "prefill_32k", "decode_32k")
    skip_reasons: Dict[str, str] = dataclasses.field(default_factory=dict)

    # ---- derived -----------------------------------------------------------
    @property
    def layer_pattern(self) -> Tuple[str, ...]:
        if self.pattern is None:
            unit = ("moe",) if self.n_experts else ("attn",)
            return self.lead + unit * (self.n_layers - len(self.lead))
        return self.lead + self.pattern * self.n_repeats + self.tail

    @property
    def qk_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def d_inner(self) -> int:  # mamba2
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def n_params(self) -> int:
        """Analytic parameter count (used for 6ND model-FLOPs in roofline)."""
        d, ff, v = self.d_model, self.d_ff, self.vocab
        emb = v * d * (1 if self.tie_embeddings else 2)
        per_attn = d * self.qk_dim + 2 * d * self.kv_dim + self.qk_dim * d
        per_mlp = 3 * d * ff if self.act == "swiglu" else 2 * d * ff
        per_moe = self.n_experts * per_mlp + d * self.n_experts
        per_mamba = (
            self.d_model * (2 * self.d_inner + 2 * self.ssm_state + self.ssm_heads)
            + self.d_inner * self.d_model  # out_proj
            + self.d_conv * (self.d_inner + 2 * self.ssm_state)  # conv
            + 2 * self.ssm_heads  # A_log, dt_bias
            + self.d_inner  # D
        )
        total = emb
        if self.family == "encdec":
            total += self.enc_layers * (per_attn + per_mlp + 2 * d)
            total += self.dec_layers * (2 * per_attn + per_mlp + 3 * d)
            return total
        shared_attn_counted = False
        for blk in self.layer_pattern:
            if blk in ("attn", "local", "global"):
                total += per_attn + per_mlp + 2 * d
            elif blk == "moe":
                total += per_attn + per_moe + 2 * d
            elif blk == "mamba":
                total += per_mamba + d
            elif blk == "shared_attn":
                if not shared_attn_counted:
                    total += per_attn + per_mlp + 2 * d
                    shared_attn_counted = True
            else:
                raise ValueError(blk)
        return total

    def n_active_params(self) -> int:
        """Active params per token (MoE: only top_k experts count)."""
        if not self.n_experts:
            return self.n_params()
        d, ff = self.d_model, self.d_ff
        per_mlp = 3 * d * ff if self.act == "swiglu" else 2 * d * ff
        inactive = (self.n_experts - self.top_k) * per_mlp
        n_moe_layers = sum(1 for b in self.layer_pattern if b == "moe")
        return self.n_params() - n_moe_layers * inactive


def load_config(arch: str) -> ModelConfig:
    arch = arch.replace("-", "_").replace(".", "")
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.CONFIG


def load_smoke_config(arch: str) -> ModelConfig:
    arch = arch.replace("-", "_").replace(".", "")
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.SMOKE_CONFIG
