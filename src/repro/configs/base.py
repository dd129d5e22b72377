"""Model / shape configuration dataclasses shared by all assigned architectures.

Every architecture in ``repro.configs`` produces a :class:`ModelConfig`.
``ShapeConfig`` describes one of the assigned input-shape cells
(train_4k / prefill_32k / decode_32k / long_500k).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyper-parameters (full, paper-spec values).

    ``family`` is one of: dense | moe | ssm | hybrid | encdec | vlm.
    """

    arch_id: str
    family: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int

    head_dim: int = 0  # 0 -> d_model // n_heads
    mlp_type: str = "swiglu"  # swiglu | gelu
    qkv_bias: bool = False
    attn_window: Optional[int] = None  # sliding-window size; None = full attention
    rope_theta: float = 1e4
    norm_eps: float = 1e-5
    tie_embeddings: bool = False

    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25

    # --- SSM (mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    ssm_conv: int = 4
    ssm_ngroups: int = 1

    # --- hybrid (recurrentgemma / Griffin) ---
    # pattern is applied per super-block; e.g. ("rec", "rec", "attn")
    hybrid_pattern: Tuple[str, ...] = ()
    lru_width: int = 0
    local_window: int = 2048

    # --- hybrid MoE (granite-4.0-h): one mixer kind per layer, each layer
    # followed by a dropless expert layer plus a shared expert ---
    layer_pattern: Tuple[str, ...] = ()  # "mamba" | "attention", one per layer
    experts_held: Tuple[int, ...] = ()  # expert ids this chip holds; () = all
    shared_d_ff: int = 0
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0  # 0 -> 1/sqrt(head_dim)
    logits_scaling: float = 1.0
    use_rope: bool = True  # False: no positional encoding (NoPE)
    param_dtype: str = "float32"

    # --- encoder-decoder (whisper) ---
    n_enc_layers: int = 0
    src_len: int = 0  # encoder source positions (precomputed frame embeds)

    # --- vlm ---
    n_patches: int = 0  # stub frontend: precomputed patch embeddings

    # pad embedding/head vocab so the `model` mesh axis divides it
    # (16 = divisibility-only baseline; 2048 = MXU-aligned, see §Perf)
    vocab_pad_multiple: int = 16

    # citation / provenance tag from the assignment table
    source: str = ""

    # ------------------------------------------------------------------
    @property
    def padded_vocab(self) -> int:
        m = max(self.vocab_pad_multiple, 1)
        return ((self.vocab_size + m - 1) // m) * m

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.resolved_head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.resolved_head_dim

    @property
    def d_inner(self) -> int:
        """SSM inner width."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_headdim

    @property
    def has_ssm_layers(self) -> bool:
        """Whether some layer runs the chunked SSD scan."""
        return self.family == "ssm" or "mamba" in self.layer_pattern

    @property
    def held_experts(self) -> Tuple[int, ...]:
        return self.experts_held or tuple(range(self.n_experts))

    @property
    def is_subquadratic(self) -> bool:
        """True when ``long_500k`` decode is feasible (bounded state)."""
        if self.family in ("ssm", "hybrid"):
            return True
        return self.attn_window is not None

    # ------------------------------------------------------------------
    # Parameter counting — used for MODEL_FLOPS = 6*N*D in the roofline.
    # ------------------------------------------------------------------
    def _attn_params(self) -> int:
        hd = self.resolved_head_dim
        p = self.d_model * (self.q_dim + 2 * self.kv_dim)  # qkv
        p += self.q_dim * self.d_model  # out proj
        if self.qkv_bias:
            p += self.q_dim + 2 * self.kv_dim
        return p

    def _mlp_params(self, d_ff: int) -> int:
        if self.mlp_type == "swiglu":
            return 3 * self.d_model * d_ff
        return 2 * self.d_model * d_ff

    def _moe_layer_params(self) -> Tuple[int, int]:
        """(total, active) params of one MoE FFN layer."""
        per_expert = self._mlp_params(self.d_ff)
        router = self.d_model * self.n_experts
        total = self.n_experts * per_expert + router
        active = self.top_k * per_expert + router
        return total, active

    def _ssm_layer_params(self) -> int:
        di, ds, nh = self.d_inner, self.ssm_state, self.ssm_nheads
        g = self.ssm_ngroups
        in_proj = self.d_model * (2 * di + 2 * g * ds + nh)
        conv = self.ssm_conv * (di + 2 * g * ds)
        out = di * self.d_model + di  # out proj + gate norm
        return in_proj + conv + out + 2 * nh  # + A_log, D

    def _rglru_layer_params(self) -> int:
        w = self.lru_width or self.d_model
        # in/out proj (gated, 2 branches) + conv + lru gates
        return self.d_model * 2 * w + 4 * w + w * self.d_model + 3 * w

    def param_count(self) -> int:
        """Total parameters (embedding included once when tied)."""
        emb = self.vocab_size * self.d_model
        head = 0 if self.tie_embeddings else self.vocab_size * self.d_model
        norms = 2 * self.d_model  # final norm (+ slack)

        if self.family == "ssm":
            body = self.n_layers * (self._ssm_layer_params() + self.d_model)
        elif self.family == "hybrid":
            pat = self.hybrid_pattern or ("rec", "rec", "attn")
            n_super = self.n_layers // len(pat)
            rem = self.n_layers - n_super * len(pat)
            per_super = 0
            for kind in pat:
                blk = self._attn_params() if kind == "attn" else self._rglru_layer_params()
                per_super += blk + self._mlp_params(self.d_ff) + 2 * self.d_model
            body = n_super * per_super
            for kind in (self.hybrid_pattern or ("rec", "rec", "attn"))[:rem]:
                blk = self._attn_params() if kind == "attn" else self._rglru_layer_params()
                body += blk + self._mlp_params(self.d_ff) + 2 * self.d_model
        elif self.family == "hybrid_moe":
            ffn = (len(self.held_experts) * self._mlp_params(self.d_ff)
                   + self.d_model * self.n_experts + self._mlp_params(self.shared_d_ff))
            body = sum((self._ssm_layer_params() if k == "mamba"
                        else self._attn_params()) + ffn + 2 * self.d_model
                       for k in self.layer_pattern)
        elif self.family == "moe":
            moe_total, _ = self._moe_layer_params()
            body = self.n_layers * (self._attn_params() + moe_total + 2 * self.d_model)
        elif self.family == "encdec":
            enc = self.n_enc_layers * (
                self._attn_params() + self._mlp_params(self.d_ff) + 2 * self.d_model
            )
            dec = self.n_layers * (
                2 * self._attn_params() + self._mlp_params(self.d_ff) + 3 * self.d_model
            )
            body = enc + dec + self.src_len * self.d_model  # learned enc pos-emb
        else:  # dense / vlm
            body = self.n_layers * (
                self._attn_params() + self._mlp_params(self.d_ff) + 2 * self.d_model
            )
            if self.family == "vlm":
                body += self.d_model * self.d_model  # patch-embed projection stub
        return emb + head + norms + body

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k experts only)."""
        if self.family != "moe":
            return self.param_count()
        moe_total, moe_active = self._moe_layer_params()
        return self.param_count() - self.n_layers * (moe_total - moe_active)


# ---------------------------------------------------------------------------
# Input-shape cells
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode

    @property
    def tokens_per_step(self) -> int:
        if self.kind == "decode":
            return self.global_batch  # one new token per sequence
        return self.seq_len * self.global_batch


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def get_shape(name: str) -> ShapeConfig:
    return SHAPES[name]


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether an (arch x shape) cell runs, and why not when skipped."""
    if shape.name == "long_500k" and not cfg.is_subquadratic:
        return False, "full quadratic attention: 500k decode infeasible (documented skip)"
    return True, ""
