"""Shared model components: config schema, norms, RoPE, initializers.

One :class:`ArchConfig` covers all ten assigned architecture families; the
family field selects the code path in ``models.lm``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                     # dense | moe | encdec | vlm | hybrid | ssm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    # attention
    attn_type: str = "gqa"          # gqa | mla | none
    rope_theta: float = 1e4
    sliding_window: Optional[int] = None   # hymba SWA width
    global_every: int = 0           # every k-th layer is full attention (0=all)

    # MLA (minicpm3)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_rope_dim: int = 0
    qk_nope_dim: int = 0
    v_head_dim: int = 0

    # MoE
    n_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25

    # SSM (rwkv6 / hymba-mamba)
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 0

    # enc-dec (seamless)
    n_enc_layers: int = 0

    # MLA decode: absorbed (inference-optimal) vs naive expand — §Perf knob
    mla_absorb: bool = False

    # Fully unroll the layer scan (dry-run cost probes only: HloCostAnalysis
    # counts while-loop bodies once, so probes unroll to get true totals)
    unroll_layers: bool = False

    # ---- §Perf hillclimb knobs (baseline = paper-faithful naive values) ----
    # chunked flash-style attention: online softmax over key blocks, never
    # materializes the [S,S] score matrix (memory-term optimization)
    attn_impl: str = "naive"        # naive | chunked
    attn_kblock: int = 1024
    attn_qblock: int = 2048
    # mixed precision: bf16 activations + bf16 weight use (f32 master params)
    activations_bf16: bool = False
    # explicit sharding constraints inside the MoE dispatch (keeps expert
    # weights stationary; tokens move via all-to-all instead of weight
    # all-gathers — collective-term optimization)
    moe_shard_constraints: bool = False
    # attention activation sharding: "none" (GSPMD decides) or "auto"
    # (shard heads over model when divisible, else sequence-parallel q —
    # fixes full-head replication for archs whose head counts don't divide TP)
    attn_act_shard: str = "none"
    # keep attention scores in bf16 end-to-end (decode memory-term knob)
    attn_scores_bf16: bool = False
    # remat policy for the layer scan: full | dots | none
    remat_policy: str = "full"
    # MoE dispatch: "global" (single token stream; paper-faithful baseline —
    # global cumsum serializes and GSPMD replicates the chain) or "grouped"
    # (per-batch-row queues, fully shardable — see moe.moe_ffn_grouped)
    moe_dispatch: str = "global"

    # modality frontend stub (audio frames / vision patches)
    frontend: Optional[str] = None  # audio | vision
    frontend_tokens: int = 0        # image tokens per example (vlm)

    # training knobs
    optimizer: str = "adamw"        # adamw | adafactor (large MoE)
    remat: bool = True
    param_dtype: Any = jnp.float32
    activ_dtype: Any = jnp.bfloat16
    tie_embeddings: bool = False

    # long-context capability: sub-quadratic path exists for this arch
    @property
    def supports_long_context(self) -> bool:
        return self.family in ("ssm", "hybrid")

    @property
    def has_decoder(self) -> bool:
        return True  # all assigned archs have a decode path (enc-dec included)

    def n_params(self) -> int:
        """Approximate parameter count (embeddings + blocks), for rooflines."""
        d, ff, V, L = self.d_model, self.d_ff, self.vocab, self.n_layers
        emb = V * d * (1 if self.tie_embeddings else 2)
        if self.attn_type == "mla":
            attn = (
                d * self.q_lora_rank
                + self.q_lora_rank * self.n_heads * (self.qk_nope_dim + self.qk_rope_dim)
                + d * (self.kv_lora_rank + self.qk_rope_dim)
                + self.kv_lora_rank * self.n_heads * (self.qk_nope_dim + self.v_head_dim)
                + self.n_heads * self.v_head_dim * d
            )
        elif self.attn_type == "gqa":
            attn = d * self.head_dim * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * self.head_dim * d
        else:
            attn = 0
        if self.family == "ssm":  # rwkv6: time-mix + channel-mix
            h = self.ssm_heads * self.ssm_head_dim
            attn = 4 * d * h + h * d  # r,k,v,g,out (w is low-rank, small)
            ffn = 2 * d * ff  # channel mix has 2 mats + small r
        elif self.n_experts:
            ffn = 3 * d * self.moe_d_ff * self.n_experts + d * self.n_experts
        else:
            ffn = 3 * d * ff
        if self.family == "hybrid":
            h = self.ssm_heads * self.ssm_head_dim
            attn += 3 * d * h  # mamba in/out/gate projections (approx)
        blocks = L * (attn + ffn)
        if self.family == "encdec":
            enc_attn = d * self.head_dim * (self.n_heads + 2 * self.n_kv_heads) + d * d
            blocks += self.n_enc_layers * (enc_attn + 3 * d * ff) + L * (2 * d * d)
        return emb + blocks

    def n_active_params(self) -> int:
        """Active params per token (MoE: only routed experts count)."""
        if not self.n_experts:
            return self.n_params()
        full = self.n_params()
        expert_p = self.n_layers * 3 * self.d_model * self.moe_d_ff * self.n_experts
        active_e = expert_p * self.experts_per_token / self.n_experts
        return int(full - expert_p + active_e)


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale).astype(dt)


def rope_angles(positions: jax.Array, dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions [...]; returns (cos, sin) of shape [..., dim/2]."""
    freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [..., S, H, dh]; cos/sin [S, dh/2] (broadcast over batch/heads)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c = cos[..., None, :]
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def dense_init(key: jax.Array, shape: Tuple[int, ...], in_dim: int,
               dtype=jnp.float32) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(in_dim)).astype(dtype)


def split_keys(key: jax.Array, n: int):
    return list(jax.random.split(key, n))


def cross_entropy_loss(logits: jax.Array, targets: jax.Array,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token CE; logits [..., V] f32, targets int32 [...]."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()
