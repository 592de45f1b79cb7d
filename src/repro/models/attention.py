"""Attention variants: GQA (llama-family), MLA (MiniCPM3), sliding window.

Two execution modes per variant:
* full  — training / prefill over [B, S] with causal (+ optional window) mask
* decode — one query token against a KV cache of length S_max

MLA keeps the *compressed* cache (c_kv + rotary key), as the architecture
intends; decode supports both the naive expand-per-step form and the
"absorbed" form (projection matrices folded into the query / output) — the
absorbed form is the §Perf optimization for decode_32k.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import get_abstract_mesh

from repro.models.common import (ArchConfig, apply_rope, dense_init,
                                 rms_norm, rope_angles)


# ---------------------------------------------------------------------------
# masking
# ---------------------------------------------------------------------------

def causal_window_mask(s_q: int, s_k: int, q_offset, window) -> jax.Array:
    """[s_q, s_k] bool; window (traced int32) 0 => plain causal."""
    qpos = jnp.arange(s_q)[:, None] + q_offset
    kpos = jnp.arange(s_k)[None, :]
    mask = kpos <= qpos
    win = jnp.asarray(window, jnp.int32)
    windowed = mask & (qpos - kpos < jnp.maximum(win, 1))
    return jnp.where(win > 0, windowed, mask)


def _sdpa(q, k, v, mask, *, scores_bf16: bool = False) -> jax.Array:
    """q [B,Sq,H,dh], k [B,Sk,Hkv,dh], v [B,Sk,Hkv,dv]; GQA head grouping."""
    b, sq, h, dh = q.shape
    hkv, dv = k.shape[2], v.shape[3]
    g = h // hkv
    q = q.reshape(b, sq, hkv, g, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k)
    if not scores_bf16:
        scores = scores.astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(dh, scores.dtype))
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, sq, h, dv)


def _attn_act_specs(cfg: ArchConfig, b, s, h, hkv):
    """(q_spec, kv_spec, out_spec) under attn_act_shard="auto", else Nones.

    Heads shard over 'model' when they divide it; otherwise the query SEQ
    dim shards over 'model' (sequence-parallel attention: k/v replicate —
    they are Hkv·dh wide, tiny — and each shard computes its q-rows against
    all keys). Fixes full-head replication for 25-head/5-kv archs on TP=16.
    """
    if cfg.attn_act_shard != "auto":
        return None, None, None
    am = get_abstract_mesh()
    if am.empty or "model" not in am.axis_names:
        return None, None, None
    from jax.sharding import PartitionSpec as _P

    msz = am.shape["model"]
    dp = tuple(a for a in ("pod", "data") if a in am.axis_names)
    dsz = 1
    for a in dp:
        dsz *= am.shape[a]
    b_ax = (dp if len(dp) > 1 else dp[0]) if (dp and b % dsz == 0 and b >= dsz) else None
    if h % msz == 0 and h >= msz:
        q_spec = _P(b_ax, None, "model", None)
        kv_spec = _P(b_ax, None, "model", None) if (hkv % msz == 0 and hkv >= msz) else _P(b_ax, None, None, None)
        return q_spec, kv_spec, q_spec
    if s % msz == 0 and s >= msz and s > 1:
        return (_P(b_ax, "model", None, None), _P(b_ax, None, None, None),
                _P(b_ax, "model", None, None))
    return None, None, None


def _maybe_constrain(x, spec):
    return x if spec is None else jax.lax.with_sharding_constraint(x, spec)


def _chunked_sdpa(q, k, v, *, q_offset, window, kblock: int, qblock: int,
                  causal: bool = True, full_unroll: bool = False) -> jax.Array:
    """Flash-style attention: online softmax over key blocks.

    Never materializes the [Sq, Sk] score matrix — peak intermediate is one
    [qblock, kblock] tile per head group. Key blocks are taken with
    ``dynamic_slice`` from the ORIGINAL k/v layout (an earlier scan-xs
    formulation copy-transposed the whole cache per call — refuted §Perf
    iteration C-it1, kept as a lesson in EXPERIMENTS.md). Same FLOPs as
    naive; bit-compatible up to fp reassociation. q [B,Sq,H,dh].

    ``full_unroll`` unrolls the key-block scan (dry-run cost probes only —
    HloCostAnalysis counts rolled loop bodies once).
    """
    b, sq, h, dh = q.shape
    sk, hkv, dv = k.shape[1], k.shape[2], v.shape[3]
    g = h // hkv
    kblock = min(kblock, sk)
    qblock = min(qblock, sq)
    n_k = (sk + kblock - 1) // kblock
    pad_k = n_k * kblock - sk
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    win = jnp.asarray(window, jnp.int32)
    scale = jax.lax.rsqrt(jnp.float32(dh))

    outs = []
    for q0 in range(0, sq, qblock):
        qb = q.reshape(b, sq, hkv, g, dh)[:, q0 : q0 + qblock]
        qbs = qb.shape[1]
        qpos = (jnp.arange(qbs) + q0 + q_offset)[:, None]

        def kstep(carry, k0):
            m, l, acc = carry
            kb = jax.lax.dynamic_slice_in_dim(k, k0, kblock, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, k0, kblock, axis=1)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb).astype(jnp.float32) * scale
            kpos = (k0 + jnp.arange(kblock))[None, :]
            if causal:
                mask = (kpos <= qpos) & (kpos < sk)
                mask = jnp.where(
                    win > 0, mask & (qpos - kpos < jnp.maximum(win, 1)), mask
                )
            else:
                mask = jnp.broadcast_to(kpos < sk, (qpos.shape[0], kblock))
            s = jnp.where(mask[None, None, None], s, -1e30)
            m2 = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m2[..., None])
            corr = jnp.exp(m - m2)
            l2 = l * corr + p.sum(-1)
            acc2 = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(vb.dtype), vb
            ).astype(jnp.float32)
            return (m2, l2, acc2), None

        m0 = jnp.full((b, hkv, g, qbs), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, qbs), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, qbs, dv), jnp.float32)
        k0s = jnp.arange(n_k) * kblock
        (m, l, acc), _ = jax.lax.scan(
            kstep, (m0, l0, a0), k0s, unroll=n_k if full_unroll else 1
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        outs.append(out.astype(v.dtype))
    out = jnp.concatenate(outs, axis=3) if len(outs) > 1 else outs[0]
    # [B, hkv, g, Sq, dv] -> [B, Sq, H, dv]
    return jnp.moveaxis(out, 3, 1).reshape(b, sq, h, dv)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def gqa_init(key, cfg: ArchConfig) -> Dict[str, jax.Array]:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    dt = cfg.param_dtype
    return {
        "wq": dense_init(ks[0], (d, h * dh), d, dt),
        "wk": dense_init(ks[1], (d, hkv * dh), d, dt),
        "wv": dense_init(ks[2], (d, hkv * dh), d, dt),
        "wo": dense_init(ks[3], (h * dh, d), h * dh, dt),
    }


def gqa_full(p, x: jax.Array, cfg: ArchConfig, *, window=0, q_offset=0) -> jax.Array:
    b, s, d = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(b, s, h, dh)
    k = (x @ p["wk"]).reshape(b, s, hkv, dh)
    v = (x @ p["wv"]).reshape(b, s, hkv, dh)
    cos, sin = rope_angles(jnp.arange(s) + q_offset, dh, cfg.rope_theta)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    q_spec, kv_spec, out_spec = _attn_act_specs(cfg, b, s, h, hkv)
    q = _maybe_constrain(q, q_spec)
    k = _maybe_constrain(k, kv_spec)
    v = _maybe_constrain(v, kv_spec)
    if cfg.attn_impl == "chunked":
        out = _chunked_sdpa(q, k, v, q_offset=q_offset, window=window,
                            kblock=cfg.attn_kblock, qblock=cfg.attn_qblock,
                            full_unroll=cfg.unroll_layers)
    else:
        mask = causal_window_mask(s, s, q_offset, window)
        out = _sdpa(q, k, v, mask, scores_bf16=cfg.attn_scores_bf16)
    out = _maybe_constrain(out, out_spec)
    return out.reshape(b, s, h * dh) @ p["wo"], (k, v)


def gqa_decode(p, x: jax.Array, cache_k, cache_v, pos, cfg: ArchConfig,
               *, window=0) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x [B,1,d]; cache_k/v [B,S,Hkv,dh]; pos int32 [] write position."""
    b, _, d = x.shape
    s_max = cache_k.shape[1]
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(b, 1, h, dh)
    k = (x @ p["wk"]).reshape(b, 1, hkv, dh)
    v = (x @ p["wv"]).reshape(b, 1, hkv, dh)
    cos, sin = rope_angles(pos[None], dh, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    cache_k = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype), (0, pos, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype), (0, pos, 0, 0))
    if cfg.attn_impl == "chunked":
        # flash-decode: online softmax over cache blocks — one pass over the
        # cache, no [B,H,1,S] f32 score buffer round-trips (§Perf cell C)
        out = _chunked_sdpa(q, cache_k, cache_v, q_offset=pos, window=window,
                            kblock=cfg.attn_kblock, qblock=1,
                            full_unroll=cfg.unroll_layers)
    else:
        kpos = jnp.arange(s_max)
        win = jnp.asarray(window, jnp.int32)
        mask = kpos <= pos
        mask = jnp.where(win > 0, mask & (pos - kpos < jnp.maximum(win, 1)), mask)
        out = _sdpa(q, cache_k, cache_v, mask[None, :],
                    scores_bf16=cfg.attn_scores_bf16)
    return out.reshape(b, 1, h * dh) @ p["wo"], cache_k, cache_v


# ---------------------------------------------------------------------------
# MLA (MiniCPM3 / DeepSeek-style multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ArchConfig) -> Dict[str, jax.Array]:
    d = cfg.d_model
    h = cfg.n_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope_d, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    dt = cfg.param_dtype
    return {
        "wdq": dense_init(ks[0], (d, qr), d, dt),
        "q_norm": jnp.ones((qr,), dt),
        "wuq": dense_init(ks[1], (qr, h * (nope + rope_d)), qr, dt),
        "wdkv": dense_init(ks[2], (d, kvr), d, dt),
        "kv_norm": jnp.ones((kvr,), dt),
        "wkr": dense_init(ks[3], (d, rope_d), d, dt),
        "wukv": dense_init(ks[4], (kvr, h * (nope + vd)), kvr, dt),
        "wo": dense_init(ks[5], (h * vd, d), h * vd, dt),
    }


def _mla_q(p, x, cfg):
    b, s, _ = x.shape
    h, nope, rope_d = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    cq = rms_norm(x @ p["wdq"], p["q_norm"])
    q = (cq @ p["wuq"]).reshape(b, s, h, nope + rope_d)
    return q[..., :nope], q[..., nope:]


def mla_full(p, x: jax.Array, cfg: ArchConfig, *, q_offset=0):
    b, s, _ = x.shape
    h = cfg.n_heads
    nope, rope_d, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(p, x, cfg)
    ckv = rms_norm(x @ p["wdkv"], p["kv_norm"])                  # [B,S,kvr]
    kr = (x @ p["wkr"])[:, :, None, :]                           # [B,S,1,rope]
    cos, sin = rope_angles(jnp.arange(s) + q_offset, rope_d, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    kr = apply_rope(kr, cos, sin)
    kv = (ckv @ p["wukv"]).reshape(b, s, h, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(kr, (b, s, h, rope_d))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    q_spec, kv_spec, out_spec = _attn_act_specs(cfg, b, s, h, h)
    q = _maybe_constrain(q, q_spec)
    k = _maybe_constrain(k, kv_spec)
    v = _maybe_constrain(v, kv_spec)
    if cfg.attn_impl == "chunked":
        out = _chunked_sdpa(q, k, v, q_offset=q_offset, window=0,
                            kblock=cfg.attn_kblock, qblock=cfg.attn_qblock,
                            full_unroll=cfg.unroll_layers)
    else:
        mask = causal_window_mask(s, s, q_offset, 0)
        out = _sdpa(q, k, v, mask, scores_bf16=cfg.attn_scores_bf16)
    out = _maybe_constrain(out, out_spec)
    return out.reshape(b, s, h * vd) @ p["wo"], (ckv, kr[:, :, 0, :])


def mla_decode(p, x, cache_ckv, cache_kr, pos, cfg: ArchConfig, *, absorb: bool = True):
    """Compressed-cache decode. absorb=True folds W_ukv into q/out (the
    inference-optimal form); absorb=False expands keys/values per step
    (naive baseline kept for §Perf before/after)."""
    b, _, _ = x.shape
    h = cfg.n_heads
    nope, rope_d, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    s_max = cache_ckv.shape[1]
    q_nope, q_rope = _mla_q(p, x, cfg)                    # [B,1,H,*]
    cos, sin = rope_angles(pos[None], rope_d, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    ckv_t = rms_norm(x @ p["wdkv"], p["kv_norm"])         # [B,1,kvr]
    kr_t = apply_rope((x @ p["wkr"])[:, :, None, :], cos, sin)[:, :, 0, :]
    cache_ckv = jax.lax.dynamic_update_slice(cache_ckv, ckv_t.astype(cache_ckv.dtype), (0, pos, 0))
    cache_kr = jax.lax.dynamic_update_slice(cache_kr, kr_t.astype(cache_kr.dtype), (0, pos, 0))
    kpos = jnp.arange(s_max)
    mask = kpos <= pos                                    # [S]
    wukv = p["wukv"].reshape(kvr, h, nope + vd)
    wk = wukv[..., :nope]                                 # [kvr,H,nope]
    wv = wukv[..., nope:]                                 # [kvr,H,vd]
    scale = jnp.sqrt(jnp.float32(nope + rope_d))
    if absorb:
        # score_h(s) = <q_nope_h W_k_h, ckv_s> + <q_rope_h, kr_s>
        q_eff = jnp.einsum("bqhn,chn->bqhc", q_nope, wk)  # [B,1,H,kvr]
        s_c = jnp.einsum("bqhc,bsc->bhqs", q_eff, cache_ckv)
        s_r = jnp.einsum("bqhr,bsr->bhqs", q_rope, cache_kr)
        scores = (s_c + s_r).astype(jnp.float32) / scale
        scores = jnp.where(mask[None, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(cache_ckv.dtype)
        ctx = jnp.einsum("bhqs,bsc->bqhc", probs, cache_ckv)     # [B,1,H,kvr]
        out = jnp.einsum("bqhc,chv->bqhv", ctx, wv)              # [B,1,H,vd]
    else:
        kv = jnp.einsum("bsc,chn->bshn", cache_ckv, wukv.reshape(kvr, h, nope + vd))
        k_nope, v = kv[..., :nope], kv[..., nope:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(cache_kr[:, :, None, :], k_nope.shape[:3] + (rope_d,))],
            axis=-1,
        )
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        scores = jnp.einsum("bqhd,bshd->bhqs", q, k).astype(jnp.float32) / scale
        scores = jnp.where(mask[None, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhqs,bshv->bqhv", probs, v)
    return out.reshape(b, 1, h * vd) @ p["wo"], cache_ckv, cache_kr
