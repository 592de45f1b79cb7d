"""Unified LM: parameter init, train forward, prefill, decode — all families.

Layers are stacked on a leading L axis and driven by ``lax.scan`` (small HLO,
per-layer FSDP all-gathers under GSPMD). Families:

  dense | moe | vlm   decoder-only attention (GQA or MLA) + SwiGLU/MoE FFN
  ssm                 RWKV6 blocks (time-mix + channel-mix)
  hybrid              Hymba: parallel GQA + SSD heads per layer, SwiGLU FFN,
                      sliding-window attention except a few global layers
  encdec              Seamless: bidirectional encoder over frame embeddings +
                      causal decoder with cross-attention

Modality frontends are STUBS per the assignment: VLM/audio inputs arrive as
precomputed patch/frame embeddings (see ``launch.specs.input_specs``).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import get_abstract_mesh

from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.common import (ArchConfig, cross_entropy_loss, dense_init,
                                 rms_norm)

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _ffn_init(key, cfg: ArchConfig) -> Params:
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = cfg.param_dtype
    return {
        "w1": dense_init(ks[0], (d, ff), d, dt),
        "w3": dense_init(ks[1], (d, ff), d, dt),
        "w2": dense_init(ks[2], (ff, d), ff, dt),
    }


def _ffn(p, x):
    return (jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])) @ p["w2"]


def _layer_init(key, cfg: ArchConfig) -> Params:
    dt = cfg.param_dtype
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    layer: Params = {"ln1": jnp.ones((d,), dt), "ln2": jnp.ones((d,), dt)}
    if cfg.family == "ssm":
        layer["tm"] = ssm_lib.rwkv_time_mix_init(ks[0], cfg)
        layer["cm"] = ssm_lib.rwkv_channel_mix_init(ks[1], cfg)
        return layer
    if cfg.attn_type == "mla":
        layer["attn"] = attn_lib.mla_init(ks[0], cfg)
    else:
        layer["attn"] = attn_lib.gqa_init(ks[0], cfg)
    if cfg.family == "hybrid":
        layer["ssd"] = ssm_lib.ssd_init(ks[1], cfg)
    if cfg.n_experts:
        layer["ffn"] = moe_lib.moe_init(ks[2], cfg)
    else:
        layer["ffn"] = _ffn_init(ks[2], cfg)
    return layer


def _enc_layer_init(key, cfg: ArchConfig) -> Params:
    dt = cfg.param_dtype
    d = cfg.d_model
    ks = jax.random.split(key, 2)
    return {
        "ln1": jnp.ones((d,), dt),
        "ln2": jnp.ones((d,), dt),
        "attn": attn_lib.gqa_init(ks[0], cfg),
        "ffn": _ffn_init(ks[1], cfg),
    }


def _stack_layers(key, cfg: ArchConfig, n: int, init_fn) -> Params:
    keys = jax.random.split(key, n)
    layers = [init_fn(k, cfg) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def layer_windows(cfg: ArchConfig) -> jnp.ndarray:
    """Per-layer attention window (0 = full/global). Hymba keeps 3 global."""
    if cfg.sliding_window is None:
        return jnp.zeros((cfg.n_layers,), jnp.int32)
    w = jnp.full((cfg.n_layers,), cfg.sliding_window, jnp.int32)
    for g in (0, cfg.n_layers // 2, cfg.n_layers - 1):
        w = w.at[g].set(0)
    return w


def init_params(cfg: ArchConfig, key: jax.Array) -> Params:
    ks = jax.random.split(key, 6)
    dt = cfg.param_dtype
    params: Params = {
        "embed": dense_init(ks[0], (cfg.vocab, cfg.d_model), cfg.d_model, dt),
        "final_norm": jnp.ones((cfg.d_model,), dt),
        "layers": _stack_layers(ks[1], cfg, cfg.n_layers, _layer_init),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[2], (cfg.d_model, cfg.vocab), cfg.d_model, dt)
    if cfg.family == "encdec":
        params["enc_layers"] = _stack_layers(ks[3], cfg, cfg.n_enc_layers, _enc_layer_init)
        params["enc_norm"] = jnp.ones((cfg.d_model,), dt)
        params["cross_layers"] = _stack_layers(ks[4], cfg, cfg.n_layers, _cross_init)
    return params


def _cross_init(key, cfg: ArchConfig) -> Params:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 5)
    dt = cfg.param_dtype
    return {
        "ln": jnp.ones((d,), dt),
        "wq": dense_init(ks[0], (d, h * dh), d, dt),
        "wk": dense_init(ks[1], (d, hkv * dh), d, dt),
        "wv": dense_init(ks[2], (d, hkv * dh), d, dt),
        "wo": dense_init(ks[3], (h * dh, d), h * dh, dt),
    }


def param_shapes(cfg: ArchConfig, key=None) -> Params:
    """ShapeDtypeStruct pytree — no allocation (dry-run input)."""
    k = jax.random.PRNGKey(0) if key is None else key
    return jax.eval_shape(lambda kk: init_params(cfg, kk), k)


# ---------------------------------------------------------------------------
# layer bodies (full-sequence mode: train / prefill)
# ---------------------------------------------------------------------------



def _remat(cfg: ArchConfig, body):
    """Layer-scan remat policy: full (save only inputs), dots (save matmul
    outputs — avoids recomputing scatter/dispatch chains in backward, trades
    memory for bytes), none."""
    if not cfg.remat or cfg.remat_policy == "none":
        return body
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    if cfg.remat_policy == "moe":
        # save the named MoE dispatch buffers (forward scatter chain is not
        # recomputed in backward); everything else rematerializes
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "moe_xin", "moe_out"
            ),
        )
    return jax.checkpoint(body)


def _shard_act(x: jax.Array) -> jax.Array:
    """Constrain activation batch dim to the data-parallel mesh axes.

    GSPMD propagation can drop to full replication through the SSM chunk
    scans (observed on hymba prefill: every device computed the whole global
    batch). Explicit per-layer constraints pin the batch dim — standard
    production practice (cf. MaxText). No-op outside a mesh context or when
    the batch dim does not divide."""
    am = get_abstract_mesh()
    if am.empty:
        return x
    dp = tuple(a for a in ("pod", "data") if a in am.axis_names)
    if not dp:
        return x
    size = 1
    for a in dp:
        size *= am.shape[a]
    if x.ndim == 0 or x.shape[0] % size != 0 or x.shape[0] < size:
        return x
    from jax.sharding import PartitionSpec as _P

    return jax.lax.with_sharding_constraint(
        x, _P(dp if len(dp) > 1 else dp[0], *([None] * (x.ndim - 1)))
    )


def _cast_layer(cfg: ArchConfig, lp):
    """Mixed precision: use bf16 copies of the layer weights in compute
    (f32 master params stay in the optimizer) when activations_bf16."""
    if not cfg.activations_bf16:
        return lp
    return jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a, lp
    )


def _attn_block_full(cfg, lp, x, window, q_offset):
    h = rms_norm(x, lp["ln1"])
    if cfg.attn_type == "mla":
        out, kv = attn_lib.mla_full(lp["attn"], h, cfg, q_offset=q_offset)
    else:
        out, kv = attn_lib.gqa_full(lp["attn"], h, cfg, window=window, q_offset=q_offset)
    if cfg.family == "hybrid":
        sstate = jnp.zeros(
            (x.shape[0], cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim), jnp.float32
        )
        ssd_out, sstate = ssm_lib.ssd_mix(lp["ssd"], h, sstate, cfg, mode="chunked")
        out = 0.5 * (out + ssd_out)
        kv = kv + (sstate,)
    return x + out, kv


def _ffn_block(cfg, lp, x):
    h = rms_norm(x, lp["ln2"])
    if cfg.n_experts:
        out, aux = moe_lib.moe_ffn(lp["ffn"], h, cfg)
    else:
        out, aux = _ffn(lp["ffn"], h), jnp.float32(0)
    return x + out, aux


def _rwkv_block_full(cfg, lp, x, mode="chunked"):
    b = x.shape[0]
    h = rms_norm(x, lp["ln1"])
    tm_x0 = jnp.zeros((b, cfg.d_model), x.dtype)
    tm_s0 = jnp.zeros((b, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_head_dim), jnp.float32)
    out, tm_x, tm_s = ssm_lib.rwkv_time_mix(lp["tm"], h, tm_x0, tm_s0, cfg, mode=mode)
    x = x + out
    h2 = rms_norm(x, lp["ln2"])
    cm_x0 = jnp.zeros((b, cfg.d_model), x.dtype)
    out2, cm_x = ssm_lib.rwkv_channel_mix(lp["cm"], h2, cm_x0)
    return x + out2, (tm_x, tm_s, cm_x)


# ---------------------------------------------------------------------------
# full forward (train / prefill)
# ---------------------------------------------------------------------------

def _decoder_stack(cfg: ArchConfig, params: Params, x: jax.Array, *,
                   q_offset: int = 0, collect_cache: bool = False,
                   enc_out: Optional[jax.Array] = None):
    """Scan the decoder layers over a full sequence.

    Returns (hidden [B,S,d], per-layer cache pytree or None, aux loss)."""
    windows = layer_windows(cfg)
    use_cross = cfg.family == "encdec"

    def body(carry, xs):
        x, aux = carry
        x = _shard_act(x)
        if use_cross:
            lp, w, cp = xs
            cp = _cast_layer(cfg, cp)
        else:
            (lp, w), cp = xs, None
        lp = _cast_layer(cfg, lp)
        if cfg.family == "ssm":
            x, cache = _rwkv_block_full(cfg, lp, x)
            a = jnp.float32(0)  # channel-mix IS the ffn for rwkv
        else:
            x, cache = _attn_block_full(cfg, lp, x, w, q_offset)
            if use_cross:
                x, ck, cv = _cross_attn(cfg, cp, x, enc_out)
                cache = cache + (ck, cv)
            x, a = _ffn_block(cfg, lp, x)
        out_cache = cache if collect_cache else None
        return (x, aux + a), out_cache

    body_fn = _remat(cfg, body)
    xs = (params["layers"], windows)
    if use_cross:
        xs = (params["layers"], windows, params["cross_layers"])
    unroll = cfg.n_layers if cfg.unroll_layers else 1
    (x, aux), caches = jax.lax.scan(body_fn, (x, jnp.float32(0)), xs, unroll=unroll)
    return x, caches, aux


def _cross_attn(cfg, cp, x, enc_out, cached_kv=None):
    b, s, d = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    hq = rms_norm(x, cp["ln"])
    q = (hq @ cp["wq"]).reshape(b, s, h, dh)
    if cached_kv is None:
        se = enc_out.shape[1]
        k = (enc_out @ cp["wk"]).reshape(b, se, hkv, dh)
        v = (enc_out @ cp["wv"]).reshape(b, se, hkv, dh)
    else:
        k, v = cached_kv
    if cfg.attn_impl == "chunked":
        out = attn_lib._chunked_sdpa(q, k, v, q_offset=0, window=0,
                                     kblock=cfg.attn_kblock,
                                     qblock=cfg.attn_qblock, causal=False,
                                     full_unroll=cfg.unroll_layers)
    else:
        mask = jnp.ones((s, k.shape[1]), bool)
        out = attn_lib._sdpa(q, k, v, mask)
    return x + out.reshape(b, s, h * dh) @ cp["wo"], k, v


def _encoder_stack(cfg: ArchConfig, params: Params, src: jax.Array) -> jax.Array:
    """Bidirectional encoder over frame embeddings (stub frontend)."""

    def body(x, lp):
        x = _shard_act(x)
        lp = _cast_layer(cfg, lp)
        h = rms_norm(x, lp["ln1"])
        b, s, d = h.shape
        hh, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        q = (h @ lp["attn"]["wq"]).reshape(b, s, hh, dh)
        k = (h @ lp["attn"]["wk"]).reshape(b, s, hkv, dh)
        v = (h @ lp["attn"]["wv"]).reshape(b, s, hkv, dh)
        from repro.models.common import apply_rope, rope_angles

        cos, sin = rope_angles(jnp.arange(s), dh, cfg.rope_theta)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        out = attn_lib._sdpa(q, k, v, jnp.ones((s, s), bool))
        x = x + out.reshape(b, s, hh * dh) @ lp["attn"]["wo"]
        h2 = rms_norm(x, lp["ln2"])
        return x + _ffn(lp["ffn"], h2), None

    body_fn = _remat(cfg, body)
    unroll = cfg.n_enc_layers if cfg.unroll_layers else 1
    x, _ = jax.lax.scan(body_fn, src, params["enc_layers"], unroll=unroll)
    return rms_norm(x, params["enc_norm"])


def _logits(cfg: ArchConfig, params: Params, x: jax.Array) -> jax.Array:
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (x @ head).astype(jnp.float32)


def _maybe_bf16(cfg: ArchConfig, x: jax.Array) -> jax.Array:
    return x.astype(cfg.activ_dtype) if cfg.activations_bf16 else x


def forward_train(cfg: ArchConfig, params: Params, batch: Dict[str, jax.Array]):
    """Full training forward. Returns (logits [B,S,V], aux_loss)."""
    emb = params["embed"]
    if cfg.family == "encdec":
        enc_out = _encoder_stack(cfg, params, _maybe_bf16(cfg, batch["src_embeds"].astype(emb.dtype)))
        x = _maybe_bf16(cfg, emb[batch["tokens"]])
        x, _, aux = _decoder_stack(cfg, params, x, enc_out=enc_out)
    elif cfg.family == "vlm":
        tok = emb[batch["tokens"]]
        x = jnp.concatenate([batch["patch_embeds"].astype(emb.dtype), tok], axis=1)
        x = _maybe_bf16(cfg, x)
        x, _, aux = _decoder_stack(cfg, params, x)
        x = x[:, batch["patch_embeds"].shape[1] :]  # only text positions score
    else:
        x = _maybe_bf16(cfg, emb[batch["tokens"]])
        x, _, aux = _decoder_stack(cfg, params, x)
    x = rms_norm(x, params["final_norm"])
    return _logits(cfg, params, x), aux


def loss_fn(cfg: ArchConfig, params: Params, batch: Dict[str, jax.Array]):
    logits, aux = forward_train(cfg, params, batch)
    mask = batch.get("loss_mask")
    ce = cross_entropy_loss(logits, batch["targets"], mask)
    loss = ce + 0.01 * aux
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# prefill / decode (serving)
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               src_len: int = 0) -> Dict[str, jax.Array]:
    """Allocate an empty cache pytree for ``decode_step``."""
    L, b = cfg.n_layers, batch
    dt = cfg.activ_dtype
    cache: Dict[str, jax.Array] = {}
    if cfg.family == "ssm":
        cache["tm_x"] = jnp.zeros((L, b, cfg.d_model), dt)
        cache["tm_s"] = jnp.zeros(
            (L, b, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_head_dim), jnp.float32
        )
        cache["cm_x"] = jnp.zeros((L, b, cfg.d_model), dt)
        return cache
    if cfg.attn_type == "mla":
        cache["ckv"] = jnp.zeros((L, b, max_len, cfg.kv_lora_rank), dt)
        cache["kr"] = jnp.zeros((L, b, max_len, cfg.qk_rope_dim), dt)
    else:
        cache["k"] = jnp.zeros((L, b, max_len, cfg.n_kv_heads, cfg.head_dim), dt)
        cache["v"] = jnp.zeros((L, b, max_len, cfg.n_kv_heads, cfg.head_dim), dt)
    if cfg.family == "hybrid":
        cache["ssd_s"] = jnp.zeros(
            (L, b, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim), jnp.float32
        )
    if cfg.family == "encdec":
        cache["cross_k"] = jnp.zeros((L, b, src_len, cfg.n_kv_heads, cfg.head_dim), dt)
        cache["cross_v"] = jnp.zeros((L, b, src_len, cfg.n_kv_heads, cfg.head_dim), dt)
    return cache


def prefill(cfg: ArchConfig, params: Params, batch: Dict[str, jax.Array],
            max_len: int):
    """Process the prompt; returns (last-position logits, filled cache)."""
    emb = params["embed"]
    enc_out = None
    if cfg.family == "encdec":
        enc_out = _encoder_stack(cfg, params, batch["src_embeds"].astype(emb.dtype))
        x = emb[batch["tokens"]]
    elif cfg.family == "vlm":
        tok = emb[batch["tokens"]]
        x = jnp.concatenate([batch["patch_embeds"].astype(emb.dtype), tok], axis=1)
    else:
        x = emb[batch["tokens"]]
    x = _maybe_bf16(cfg, x)
    b, s, _ = x.shape
    x, caches, _ = _decoder_stack(cfg, params, x, collect_cache=True, enc_out=enc_out)
    x = rms_norm(x, params["final_norm"])
    logits = _logits(cfg, params, x[:, -1:])
    cache = init_cache(cfg, b, max_len, src_len=0 if enc_out is None else enc_out.shape[1])
    if cfg.family == "ssm":
        tm_x, tm_s, cm_x = caches
        cache.update(tm_x=tm_x.astype(cache["tm_x"].dtype), tm_s=tm_s,
                     cm_x=cm_x.astype(cache["cm_x"].dtype))
    else:
        k, v = caches[0], caches[1]
        if cfg.attn_type == "mla":
            cache["ckv"] = _place(cache["ckv"], k)
            cache["kr"] = _place(cache["kr"], v)
        else:
            cache["k"] = _place(cache["k"], k)
            cache["v"] = _place(cache["v"], v)
        extra = 2
        if cfg.family == "hybrid":
            cache["ssd_s"] = caches[extra]
            extra += 1
        if cfg.family == "encdec":
            cache["cross_k"] = caches[extra].astype(cache["cross_k"].dtype)
            cache["cross_v"] = caches[extra + 1].astype(cache["cross_v"].dtype)
    return logits, cache


def _place(buf, val):
    """Write [L,B,S,...] prefill values into the [L,B,max,...] cache."""
    return jax.lax.dynamic_update_slice(
        buf, val.astype(buf.dtype), (0,) * buf.ndim
    )


def decode_step(cfg: ArchConfig, params: Params, cache: Dict[str, jax.Array],
                tokens: jax.Array, pos: jax.Array):
    """One token for every sequence. tokens [B] int32; pos scalar int32.

    Returns (logits [B, V], updated cache)."""
    emb = params["embed"]
    x = _maybe_bf16(cfg, emb[tokens][:, None, :])          # [B,1,d]
    windows = layer_windows(cfg)
    use_cross = cfg.family == "encdec"

    def body(x, xs):
        x = _shard_act(x)
        if cfg.family == "ssm":
            lp, w, tm_x, tm_s, cm_x = xs
            lp = _cast_layer(cfg, lp)
            h = rms_norm(x, lp["ln1"])
            out, tm_x, tm_s = ssm_lib.rwkv_time_mix(
                lp["tm"], h, tm_x.astype(h.dtype), tm_s, cfg, mode="recurrent"
            )
            x = x + out
            h2 = rms_norm(x, lp["ln2"])
            out2, cm_x = ssm_lib.rwkv_channel_mix(lp["cm"], h2, cm_x.astype(h2.dtype))
            x = x + out2
            x, _ = _ffn_block_noop(cfg, lp, x)
            return x, (tm_x, tm_s, cm_x)
        if use_cross:
            lp, w, cp, ck, cv, xk, xv = xs
            cp = _cast_layer(cfg, cp)
        elif cfg.family == "hybrid":
            lp, w, ck, cv, ss = xs
        else:
            lp, w, ck, cv = xs
        lp = _cast_layer(cfg, lp)
        h = rms_norm(x, lp["ln1"])
        if cfg.attn_type == "mla":
            out, ck, cv = attn_lib.mla_decode(
                lp["attn"], h, ck, cv, pos, cfg, absorb=cfg.mla_absorb
            )
        else:
            out, ck, cv = attn_lib.gqa_decode(lp["attn"], h, ck, cv, pos, cfg, window=w)
        if cfg.family == "hybrid":
            sout, ss = ssm_lib.ssd_mix(lp["ssd"], h, ss, cfg, mode="recurrent")
            out = 0.5 * (out + sout)
        x = x + out
        if use_cross:
            x, _, _ = _cross_attn(cfg, cp, x, None, cached_kv=(xk, xv))
        x, _ = _ffn_block(cfg, lp, x)
        new_cache = (ck, cv)
        if cfg.family == "hybrid":
            new_cache = (ck, cv, ss)
        return x, new_cache

    unroll = cfg.n_layers if cfg.unroll_layers else 1
    if cfg.family == "ssm":
        xs = (params["layers"], windows, cache["tm_x"], cache["tm_s"], cache["cm_x"])
        x, (tm_x, tm_s, cm_x) = jax.lax.scan(body, x, xs, unroll=unroll)
        cache = dict(cache, tm_x=tm_x, tm_s=tm_s, cm_x=cm_x)
    elif cfg.attn_type == "mla":
        xs = (params["layers"], windows, cache["ckv"], cache["kr"])
        x, (ckv, kr) = jax.lax.scan(body, x, xs, unroll=unroll)
        cache = dict(cache, ckv=ckv, kr=kr)
    elif use_cross:
        xs = (params["layers"], windows, params["cross_layers"],
              cache["k"], cache["v"], cache["cross_k"], cache["cross_v"])
        x, (k, v) = jax.lax.scan(body, x, xs, unroll=unroll)
        cache = dict(cache, k=k, v=v)
    elif cfg.family == "hybrid":
        xs = (params["layers"], windows, cache["k"], cache["v"], cache["ssd_s"])
        x, (k, v, ss) = jax.lax.scan(body, x, xs, unroll=unroll)
        cache = dict(cache, k=k, v=v, ssd_s=ss)
    else:
        xs = (params["layers"], windows, cache["k"], cache["v"])
        x, (k, v) = jax.lax.scan(body, x, xs, unroll=unroll)
        cache = dict(cache, k=k, v=v)
    x = rms_norm(x, params["final_norm"])
    return _logits(cfg, params, x)[:, 0], cache


def _ffn_block_noop(cfg, lp, x):
    """RWKV has no separate FFN block (channel-mix plays that role)."""
    return x, jnp.float32(0)
