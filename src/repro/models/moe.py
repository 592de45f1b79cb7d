"""Mixture-of-Experts FFN with top-k routing and capacity-based dispatch.

Dispatch is the sort/scatter formulation (MegaBlocks/MaxText-style "dropping"
MoE): tokens are scattered into per-expert buffers of size
``capacity = ceil(T·K/E · capacity_factor)``; overflow tokens lose that
expert's contribution (standard at scale). Expert compute is a batched
einsum over the [E, cap, d] buffer, so compiled FLOPs scale with *active*
experts (what the roofline wants), and under pjit the scatter/gather is where
GSPMD inserts the expert-parallel all-to-alls.

A dense all-experts reference (``moe_dense_ref``) is kept for smoke tests:
with ample capacity the two must agree exactly.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import get_abstract_mesh

from repro.models.common import ArchConfig, dense_init


def moe_init(key, cfg: ArchConfig) -> Dict[str, jax.Array]:
    d, e, ff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 4)
    dt = cfg.param_dtype
    return {
        "router": dense_init(ks[0], (d, e), d, dt),
        "w1": dense_init(ks[1], (e, d, ff), d, dt),   # gate proj
        "w3": dense_init(ks[2], (e, d, ff), d, dt),   # up proj
        "w2": dense_init(ks[3], (e, ff, d), ff, dt),  # down proj
    }


def _route(p, x2d: jax.Array, cfg: ArchConfig):
    """x2d [T, d] -> (weights [T, K], experts [T, K])."""
    logits = (x2d @ p["router"]).astype(jnp.float32)       # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.experts_per_token)   # [T, K]
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)    # renormalize
    return w, idx, probs


def moe_capacity(n_tokens: int, cfg: ArchConfig) -> int:
    cap = math.ceil(
        n_tokens * cfg.experts_per_token * cfg.capacity_factor / cfg.n_experts
    )
    return max(8, cap)


def _moe_spec(cfg: ArchConfig):
    """Dispatch-buffer spec for [E, cap, *]: experts over 'model' when E
    divides it (qwen: 128/16); otherwise shard the token-capacity dim over
    'data' so expert weights stay put and tokens move (grok: E=8 < 16)."""
    from jax.sharding import PartitionSpec as _P

    am = get_abstract_mesh()
    if not am.empty and "model" in am.axis_names:
        if cfg.n_experts % am.shape["model"] == 0:
            return _P("model", None, None)
    if not am.empty and "data" in am.axis_names:
        return _P(None, "data", None)
    return _P(None, None, None)


def moe_ffn_grouped(p, x: jax.Array, cfg: ArchConfig) -> Tuple[jax.Array, jax.Array]:
    """Grouped dispatch: per-batch-row expert queues (MaxText-style).

    The global-cumsum dispatch (``moe_ffn``) has a sequential dependency
    across the whole token stream, which defeats GSPMD: the scatter chain —
    and with it the expert einsums — replicates on every device (measured:
    qwen3 train compute 180× MODEL_FLOPS). Grouped dispatch computes queue
    positions *within each batch row* (cumsum over an unsharded axis), so
    the whole pipeline stays batch-sharded and expert compute parallelizes.
    Capacity is enforced per (row, expert) — the standard locality
    trade-off; with capacity_factor≈1.25 drop rates are comparable.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    capg = max(1, math.ceil(s * k * cfg.capacity_factor / e))
    w, idx, probs = _route(p, x.reshape(-1, d), cfg)
    w = w.reshape(b, s, k)
    idx = idx.reshape(b, s, k)

    flat_e = idx.reshape(b, s * k)                          # [B, S*K]
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)     # [B, S*K, E]
    pos = jnp.cumsum(onehot, axis=1) - onehot               # queue slot per row
    slot = jnp.take_along_axis(pos, flat_e[..., None], axis=2)[..., 0]
    keep = slot < capg
    dest = jnp.where(keep, flat_e * capg + slot, e * capg)  # [B, S*K]

    tok_of = jnp.repeat(jnp.arange(s), k)                   # [S*K] within row
    x_rep = x[:, tok_of, :]                                 # [B, S*K, d]
    buf = jnp.zeros((b, e * capg + 1, d), x.dtype)
    buf = jax.vmap(lambda bf, ds, xr: bf.at[ds].set(xr))(buf, dest, x_rep)
    xin = buf[:, : e * capg].reshape(b, e, capg, d)
    xin = checkpoint_name(xin, "moe_xin")
    if cfg.moe_shard_constraints:
        xin = jax.lax.with_sharding_constraint(xin, _moe_spec_grouped(cfg))

    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xin, p["w1"])) * jnp.einsum(
        "becd,edf->becf", xin, p["w3"]
    )
    out_e = jnp.einsum("becf,efd->becd", h, p["w2"])        # [B, E, capg, d]
    out_e = checkpoint_name(out_e, "moe_out")

    flat_out = jnp.concatenate(
        [out_e.reshape(b, e * capg, d), jnp.zeros((b, 1, d), x.dtype)], axis=1
    )
    gathered = jax.vmap(lambda fo, ds: fo[ds])(flat_out, dest)  # [B, S*K, d]
    y_tok = gathered * (w.reshape(b, s * k)[..., None] * keep[..., None]).astype(x.dtype)
    y = jax.ops.segment_sum(
        y_tok.reshape(b * s * k, d),
        (jnp.arange(b)[:, None] * s + tok_of[None, :]).reshape(-1),
        num_segments=b * s,
    ).reshape(b, s, d)

    frac_tokens = jnp.mean(jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(2),
                           axis=(0, 1)) / k
    aux = e * jnp.sum(frac_tokens * probs.mean(0))
    return y, aux


def _moe_spec_grouped(cfg: ArchConfig):
    """[B, E, capg, d] dispatch spec: rows over data, experts over model."""
    from jax.sharding import PartitionSpec as _P

    am = get_abstract_mesh()
    if am.empty:
        return _P(None, None, None, None)
    dp = tuple(a for a in ("pod", "data") if a in am.axis_names)
    b_ax = (dp if len(dp) > 1 else dp[0]) if dp else None
    e_ax = ("model" if "model" in am.axis_names
            and cfg.n_experts % am.shape["model"] == 0 else None)
    return _P(b_ax, e_ax, None, None)


def moe_ffn(p, x: jax.Array, cfg: ArchConfig) -> Tuple[jax.Array, jax.Array]:
    """x [B, S, d] -> (y [B, S, d], aux_loss scalar)."""
    if cfg.moe_dispatch == "grouped":
        return moe_ffn_grouped(p, x, cfg)
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.experts_per_token
    cap = moe_capacity(t, cfg)
    x2 = x.reshape(t, d)
    w, idx, probs = _route(p, x2, cfg)

    # position of each (token, k) in its expert's queue
    flat_e = idx.reshape(-1)                               # [T*K]
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)    # [T*K, E]
    pos = jnp.cumsum(onehot, axis=0) - onehot              # queue slot
    slot = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = slot < cap
    dest = jnp.where(keep, flat_e * cap + slot, e * cap)   # overflow -> scratch

    buf = jnp.zeros((e * cap + 1, d), x.dtype)
    tok_of = jnp.repeat(jnp.arange(t), k)                  # [T*K]
    buf = buf.at[dest].set(x2[tok_of])
    xin = buf[: e * cap].reshape(e, cap, d)
    # name the dispatch buffers so remat_policy="moe" can SAVE them instead
    # of recomputing the whole scatter chain in the backward pass
    xin = checkpoint_name(xin, "moe_xin")

    if cfg.moe_shard_constraints:
        xin = jax.lax.with_sharding_constraint(xin, _moe_spec(cfg))

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, p["w1"])) * jnp.einsum(
        "ecd,edf->ecf", xin, p["w3"]
    )
    out_e = jnp.einsum("ecf,efd->ecd", h, p["w2"])         # [E, cap, d]
    out_e = checkpoint_name(out_e, "moe_out")
    if cfg.moe_shard_constraints:
        h = jax.lax.with_sharding_constraint(h, _moe_spec(cfg))
        out_e = jax.lax.with_sharding_constraint(out_e, _moe_spec(cfg))

    flat_out = jnp.concatenate(
        [out_e.reshape(e * cap, d), jnp.zeros((1, d), x.dtype)]
    )
    y_tok = flat_out[dest] * (w.reshape(-1)[:, None] * keep[:, None]).astype(x.dtype)
    y = jax.ops.segment_sum(y_tok, tok_of, num_segments=t)

    # load-balancing auxiliary loss (Switch-style)
    frac_tokens = jnp.mean(
        (jax.nn.one_hot(idx, e, dtype=jnp.float32)).sum(1), axis=0
    ) / k
    frac_probs = probs.mean(0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return y.reshape(b, s, d), aux


def moe_dense_ref(p, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """All-experts dense reference (smoke-test oracle; O(E) compute)."""
    b, s, d = x.shape
    x2 = x.reshape(-1, d)
    w, idx, _ = _route(p, x2, cfg)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", x2, p["w1"])) * jnp.einsum(
        "td,edf->tef", x2, p["w3"]
    )
    y_all = jnp.einsum("tef,efd->ted", h, p["w2"])         # [T, E, d]
    gates = jnp.zeros((x2.shape[0], cfg.n_experts), x.dtype)
    gates = jax.vmap(lambda g, i, v: g.at[i].set(v))(gates, idx, w.astype(x.dtype))
    return jnp.einsum("ted,te->td", y_all, gates).reshape(b, s, d)
