"""Attention: GQA (+qk-norm, RoPE/M-RoPE, padding-aware) and DeepSeek MLA.

KV caches are explicit pytrees so serve_step can donate them.  Head counts may
be padded for TP divisibility (extra heads are zero-weighted → exact function
preservation, DESIGN.md §5).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..core import dof
from ..core.plan import plan_view
from ..core.qconfig import QuantConfig
from ..kernels.decode_attention import (decode_attention, decode_tiles_ok,
                                        paged_decode_attention,
                                        paged_decode_tiles_ok)
from ..serve.kv_cache import quantize_kv
from .config import ModelConfig
from .layers import apply_mrope, apply_rope, rmsnorm, init_rmsnorm

Params = dict[str, Any]


def decode_route(cfg: ModelConfig, max_len: int, use_pallas: bool,
                 bk: int = 128, page_size: int | None = None) -> bool:
    """Whether the vector-pos decode path routes through a Pallas
    flash-decode kernel for a serving cache of depth ``max_len``: with
    ``page_size`` (the paged int8 cache) the paged kernel, by
    ``paged_decode_tiles_ok``; without, the monolithic kernel, by
    ``decode_tiles_ok``.

    The single source of truth for kernel routing: :func:`attention` applies
    it at trace time and ``serve.engine.Engine.stats()`` reports it as
    per-layer route counters — they cannot disagree.  MLA layers never route
    (the latent-space decode is a different kernel, future work)."""
    if not use_pallas or cfg.mla is not None:
        return False
    if page_size is not None:
        return paged_decode_tiles_ok(page_size, cfg.n_kv_heads_padded,
                                     cfg.head_dim)
    return decode_tiles_ok(max_len, bk)


# --------------------------------------------------------------------------
# GQA
# --------------------------------------------------------------------------

def init_attention(key: jax.Array, cfg: ModelConfig,
                   qcfg: QuantConfig | None) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    H, Hkv = cfg.n_heads_padded, cfg.n_kv_heads_padded
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": dof.init_qlinear(ks[0], d, H * hd, qcfg, bias=cfg.bias,
                               name="wq"),
        "wk": dof.init_qlinear(ks[1], d, Hkv * hd, qcfg, bias=cfg.bias,
                               name="wk"),
        "wv": dof.init_qlinear(ks[2], d, Hkv * hd, qcfg, bias=cfg.bias,
                               name="wv"),
        "wo": dof.init_qlinear(ks[3], H * hd, d, qcfg, bias=False, name="wo"),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd)
        p["k_norm"] = init_rmsnorm(hd)
    if qcfg is not None:
        p["in_stream"] = dof.init_stream(d)        # shared by q,k,v (fan-out)
        p["out_stream"] = dof.init_stream(H * hd)
    return p


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, n_layers: int,
                  dtype=jnp.bfloat16) -> Params:
    Hkv, hd = cfg.n_kv_heads_padded, cfg.head_dim
    shape = (n_layers, batch, max_len, Hkv, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((), jnp.int32)}


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
          q_offset: jax.Array | int, kv_len: jax.Array | None = None) -> jax.Array:
    """q: [B,Sq,H,hd]; k,v: [B,Skv,Hkv,hd] (GQA grouping inside). f32 softmax.

    ``q_offset``/``kv_len`` may be per-slot vectors [B] (continuous-batching
    serving: every slot is at its own sequence offset); the mask then becomes
    [B,Sq,Skv] and each batch row attends only its own valid prefix."""
    B, Sq, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Sq, Hkv, G, hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                        preferred_element_type=jnp.float32)
    logits = logits * (hd ** -0.5)
    off = jnp.asarray(q_offset)
    pos_k = jnp.arange(Skv)
    if off.ndim:                                 # per-slot offsets [B]
        pos_q = off[:, None] + jnp.arange(Sq)[None, :]          # [B,Sq]
        mask = jnp.ones((B, Sq, Skv), bool)
        if causal:
            mask = mask & (pos_q[:, :, None] >= pos_k[None, None, :])
        if kv_len is not None:
            mask = mask & (pos_k[None, None, :]
                           < jnp.asarray(kv_len)[:, None, None])
        logits = jnp.where(mask[:, None, None], logits, -1e30)
    else:
        pos_q = off + jnp.arange(Sq)
        mask = jnp.ones((Sq, Skv), bool)
        if causal:
            mask = mask & (pos_q[:, None] >= pos_k[None, :])
        if kv_len is not None:                   # cached decode: valid prefix
            mask = mask & (pos_k[None, :] < kv_len)
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(B, Sq, H, hd)


def _paged_sdpa(q: jax.Array, k8: jax.Array, v8: jax.Array,
                lengths: jax.Array, k_scale: jax.Array,
                v_scale: jax.Array) -> jax.Array:
    """Masked-XLA decode attention over gathered int8 KV pages.

    q: [S,1,H,hd] float; k8/v8: [S,T,Hkv,hd] int8; lengths: [S];
    k_scale/v_scale: [S,Hkv].  Dequantization is **fused by construction**:
    the K scale (and the softmax 1/sqrt(hd)) folds into the tiny q operand
    before the dot and the V scale multiplies the tiny [S,Hkv,G,hd] context
    after it, so the int8 cache feeds each einsum through a bare convert —
    no float tensor at cache extent is ever materialized.
    """
    S, _, H, hd = q.shape
    T, Hkv = k8.shape[1], k8.shape[2]
    G = H // Hkv
    qg = q[:, 0].reshape(S, Hkv, G, hd)
    qs = qg * (hd ** -0.5 * k_scale)[:, :, None, None]
    logits = jnp.einsum("skgh,stkh->skgt", qs, k8.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
    mask = jnp.arange(T)[None, :] < lengths[:, None]             # [S,T]
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("skgt,stkh->skgh", probs, v8.astype(jnp.float32))
    ctx = ctx * v_scale[:, :, None, None]
    return ctx.reshape(S, 1, H, hd)


def _paged_decode(q: jax.Array, k: jax.Array, v: jax.Array, cache: Params,
                  cfg: ModelConfig, use_pallas: bool,
                  interpret: bool | None) -> tuple[jax.Array, Params]:
    """One decode step over the paged int8 KV cache (serve, Sq == 1).

    Cache leaves, all layers' and ``layer``, the index of this one: the
    int8 page pools ``k``/``v`` ``[L, n_pages+1, P, Hkv, hd]`` (last page
    is the write-sink "trash" page); the install-time MMSE scales
    ``k_scale``/``v_scale`` ``[L, S, Hkv]``; the page table ``pt``
    ``[S, max_pages]`` and ``pos`` ``[S]``.  The new token is quantized
    with the slot's frozen scales and scattered into (page, row) of the
    stack in place; retired slots' pt rows all point at the trash page, so
    the unconditional every-slot write never aliases a reused page, and
    such a slot reads just that one page.
    """
    pos, pt, layer = cache["pos"], cache["pt"], cache["layer"]
    pool_k, pool_v = cache["k"], cache["v"]
    ks, vs = cache["k_scale"][layer], cache["v_scale"][layer]
    S, n_pg = pt.shape
    trash = pool_k.shape[1] - 1
    P, Hkv, hd = pool_k.shape[2], pool_k.shape[3], pool_k.shape[4]
    H = q.shape[2]
    pg = pt[jnp.arange(S), jnp.minimum(pos // P, n_pg - 1)]
    row = pos % P
    pool_k = pool_k.at[layer, pg, row].set(quantize_kv(k[:, 0], ks))
    pool_v = pool_v.at[layer, pg, row].set(quantize_kv(v[:, 0], vs))
    # a retired slot's pos keeps counting: its length is the trash page's
    lengths = jnp.where(pt[:, 0] == trash, 1, pos + 1)
    if decode_route(cfg, n_pg * P, use_pallas, page_size=P):
        # the kernel reads each live page of the pool in place
        qd = q[:, 0].reshape(S, Hkv, H // Hkv, hd)
        od = paged_decode_attention(qd, pool_k, pool_v, lengths, pt,
                                    cache["k_scale"], cache["v_scale"],
                                    layer=layer, interpret=interpret)
        out = od.reshape(S, 1, H, hd)
    else:
        # gather each slot's pages into a transient [S,T,Hkv,hd] int8 view;
        # rows past the slot's length (incl. trash-page garbage) are masked
        k8 = pool_k[layer][pt].reshape(S, n_pg * P, Hkv, hd)
        v8 = pool_v[layer][pt].reshape(S, n_pg * P, Hkv, hd)
        out = _paged_sdpa(q, k8, v8, lengths, ks, vs)
    new_cache = {**cache, "k": pool_k, "v": pool_v, "pos": pos + 1}
    return out, new_cache


def attention(x: jax.Array, p: Params, cfg: ModelConfig,
              qcfg: QuantConfig | None, positions: jax.Array,
              cache: Params | None = None, taps: dict | None = None,
              prefix: str = "", plan=None, use_pallas: bool = False,
              interpret: bool | None = None) -> tuple[jax.Array, Params | None]:
    """Returns (out, updated layer cache).  cache leaves: k/v [B, Smax, Hkv, hd].

    ``plan``: QuantPlan/PlanView scoped to this module's path
    (``layers.attn``, ``dec_layers.attn``, …) — per-projection fake-quant
    bits come from the resolved plan so training and export share one grid.

    ``use_pallas``: route the vector-pos decode step (continuous-batching
    serving: per-slot offsets, Sq == 1) through a flash-decode kernel
    (kernels/decode_attention.py: the paged kernel for the paged int8 cache,
    the slot-masked one for the monolithic cache), gated by
    :func:`decode_route`; the masked-XLA `_paged_sdpa` / `_sdpa` stay the
    oracles and the fallbacks.  All other modes (train, prefill, scalar-pos
    decode) are unaffected.
    """
    B, Sq, _ = x.shape
    hd = cfg.head_dim
    H, Hkv = cfg.n_heads_padded, cfg.n_kv_heads_padded
    pv = plan_view(plan)
    ins = p.get("in_stream")
    q = dof.qlinear(x, p["wq"], qcfg, stream=ins,
                    bits=pv.bits("wq")).reshape(B, Sq, H, hd)
    k = dof.qlinear(x, p["wk"], qcfg, stream=ins,
                    bits=pv.bits("wk")).reshape(B, Sq, Hkv, hd)
    v = dof.qlinear(x, p["wv"], qcfg, stream=ins,
                    bits=pv.bits("wv")).reshape(B, Sq, Hkv, hd)
    if cfg.qk_norm:
        q, k = rmsnorm(q, p["q_norm"]), rmsnorm(k, p["k_norm"])
    if cfg.mrope_sections:
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if cache is None:
        out = _sdpa(q, k, v, causal=True, q_offset=0)
        new_cache = None
    elif "pt" in cache:
        # paged int8 KV (serve decode: Sq == 1, per-slot vector pos)
        out, new_cache = _paged_decode(q, k, v, cache, cfg, use_pallas,
                                       interpret)
        out = out.astype(x.dtype)
    else:
        pos = cache["pos"]
        if getattr(pos, "ndim", 0) == 1:
            # per-slot offsets (continuous-batching serve): each slot writes
            # its new K/V at its own length and masks its own valid prefix
            def upd(c, u, p):
                return jax.lax.dynamic_update_slice(
                    c, u.astype(c.dtype), (p, 0, 0))
            ck = jax.vmap(upd)(cache["k"], k, pos)
            cv = jax.vmap(upd)(cache["v"], v, pos)
        else:
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0))
        if (Sq == 1 and getattr(pos, "ndim", 0) == 1
                and decode_route(cfg, ck.shape[1], use_pallas)):
            # slot-masked flash-decode: per-slot valid prefix is pos + 1
            # (the token just written above), dead KV blocks skipped
            qd = q[:, 0].reshape(B, Hkv, H // Hkv, hd)
            od = decode_attention(qd, ck, cv, pos + 1, interpret=interpret)
            out = od.reshape(B, 1, H, hd).astype(x.dtype)
        else:
            out = _sdpa(q, ck, cv, causal=Sq > 1, q_offset=pos,
                        kv_len=pos + Sq)
        new_cache = {"k": ck, "v": cv, "pos": pos + Sq}
    out = out.reshape(B, Sq, H * hd)
    if taps is not None:
        from .transformer import _tap
        _tap(taps, prefix + ".pre_o", out)
    out = dof.qlinear(out, p["wo"], qcfg, stream=p.get("out_stream"),
                      bits=pv.bits("wo"))
    return out, new_cache


# --------------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank compressed KV, optional absorbed decode
# --------------------------------------------------------------------------

def init_mla(key: jax.Array, cfg: ModelConfig,
             qcfg: QuantConfig | None) -> Params:
    m, d, H = cfg.mla, cfg.d_model, cfg.n_heads_padded
    ks = jax.random.split(key, 6)
    p: Params = {
        "q_down": dof.init_qlinear(ks[0], d, m.q_lora, qcfg, name="q_down"),
        "q_up": dof.init_qlinear(ks[1], m.q_lora, H * (m.d_nope + m.d_rope),
                                 qcfg, name="q_up"),
        "kv_down": dof.init_qlinear(ks[2], d, m.kv_lora + m.d_rope, qcfg,
                                    name="kv_down"),
        "k_up": dof.init_qlinear(ks[3], m.kv_lora, H * m.d_nope, qcfg,
                                 name="k_up"),
        "v_up": dof.init_qlinear(ks[4], m.kv_lora, H * m.d_v, qcfg,
                                 name="v_up"),
        "wo": dof.init_qlinear(ks[5], H * m.d_v, d, qcfg, name="wo"),
        "q_norm": init_rmsnorm(m.q_lora),
        "kv_norm": init_rmsnorm(m.kv_lora),
    }
    if qcfg is not None:
        p["in_stream"] = dof.init_stream(d)       # shared q_down/kv_down
        p["q_stream"] = dof.init_stream(m.q_lora)
        p["kv_stream"] = dof.init_stream(m.kv_lora)  # shared k_up/v_up
        p["out_stream"] = dof.init_stream(H * m.d_v)
    return p


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, n_layers: int,
                   dtype=jnp.bfloat16) -> Params:
    m = cfg.mla
    return {"ckv": jnp.zeros((n_layers, batch, max_len, m.kv_lora), dtype),
            "kr": jnp.zeros((n_layers, batch, max_len, m.d_rope), dtype),
            "pos": jnp.zeros((), jnp.int32)}


def mla_attention(x: jax.Array, p: Params, cfg: ModelConfig,
                  qcfg: QuantConfig | None, positions: jax.Array,
                  cache: Params | None = None,
                  plan=None) -> tuple[jax.Array, Params | None]:
    """MLA forward; ``plan`` as in :func:`attention` (scoped to
    ``layers.attn``), covering the absorbed-decode effective weights too."""
    m = cfg.mla
    B, Sq, _ = x.shape
    H = cfg.n_heads_padded
    pv = plan_view(plan)
    ins = p.get("in_stream")
    ql = rmsnorm(dof.qlinear(x, p["q_down"], qcfg, stream=ins,
                             bits=pv.bits("q_down")), p["q_norm"])
    q = dof.qlinear(ql, p["q_up"], qcfg, stream=p.get("q_stream"),
                    bits=pv.bits("q_up"))
    q = q.reshape(B, Sq, H, m.d_nope + m.d_rope)
    q_nope, q_rope = q[..., : m.d_nope], q[..., m.d_nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv = dof.qlinear(x, p["kv_down"], qcfg, stream=ins,
                     bits=pv.bits("kv_down"))
    ckv, kr = kv[..., : m.kv_lora], kv[..., m.kv_lora:]
    ckv = rmsnorm(ckv, p["kv_norm"])
    kr = apply_rope(kr[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]

    if cache is not None:
        pos = cache["pos"]
        if getattr(pos, "ndim", 0) == 1:         # per-slot offsets (serving)
            def upd(c, u, p):
                return jax.lax.dynamic_update_slice(
                    c, u.astype(c.dtype), (p, 0))
            ckv_all = jax.vmap(upd)(cache["ckv"], ckv, pos)
            kr_all = jax.vmap(upd)(cache["kr"], kr, pos)
        else:
            ckv_all = jax.lax.dynamic_update_slice(
                cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, pos, 0))
            kr_all = jax.lax.dynamic_update_slice(
                cache["kr"], kr.astype(cache["kr"].dtype), (0, pos, 0))
        new_cache = {"ckv": ckv_all, "kr": kr_all, "pos": pos + Sq}
        kv_len = pos + Sq
        q_offset = pos
    else:
        ckv_all, kr_all, new_cache, kv_len, q_offset = ckv, kr, None, None, 0

    scale = (m.d_nope + m.d_rope) ** -0.5
    Skv = ckv_all.shape[1]
    if cfg.mla_absorb:
        # ---- absorbed decode (beyond-paper §Perf opt): attention runs in the
        # compressed latent space; k_up/v_up folded into q / output path.
        k_up_w = dof.effective_weight(p["k_up"], qcfg,
                                      None if qcfg is None else p["kv_stream"]["log_sa"],
                                      compute_dtype=x.dtype,
                                      bits=pv.bits("k_up"))
        k_up_w = k_up_w.reshape(m.kv_lora, H, m.d_nope)
        q_c = jnp.einsum("bqhn,chn->bqhc", q_nope, k_up_w)       # [B,Sq,H,kv_lora]
        logits = (jnp.einsum("bqhc,bsc->bhqs", q_c, ckv_all,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhr,bsr->bhqs", q_rope, kr_all,
                               preferred_element_type=jnp.float32)) * scale
    else:
        k_nope = dof.qlinear(ckv_all, p["k_up"], qcfg, stream=p.get("kv_stream"),
                             bits=pv.bits("k_up")).reshape(B, Skv, H, m.d_nope)
        logits = (jnp.einsum("bqhn,bshn->bhqs", q_nope, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhr,bsr->bhqs", q_rope, kr_all,
                               preferred_element_type=jnp.float32)) * scale

    off = jnp.asarray(q_offset if q_offset is not None else 0)
    pos_k = jnp.arange(Skv)
    if off.ndim:                                 # per-slot offsets [B]
        pos_q = off[:, None] + jnp.arange(Sq)[None, :]          # [B,Sq]
        mask = pos_q[:, :, None] >= pos_k[None, None, :]
        if kv_len is not None:
            mask = mask & (pos_k[None, None, :]
                           < jnp.asarray(kv_len)[:, None, None])
        logits = jnp.where(mask[:, None], logits, -1e30)
    else:
        pos_q = off + jnp.arange(Sq)
        mask = pos_q[:, None] >= pos_k[None, :]
        if kv_len is not None:
            mask = mask & (pos_k[None, :] < kv_len)
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)

    if cfg.mla_absorb:
        ctx_c = jnp.einsum("bhqs,bsc->bqhc", probs, ckv_all)     # latent context
        v_up_w = dof.effective_weight(p["v_up"], qcfg,
                                      None if qcfg is None else p["kv_stream"]["log_sa"],
                                      compute_dtype=x.dtype,
                                      bits=pv.bits("v_up"))
        v_up_w = v_up_w.reshape(m.kv_lora, H, m.d_v)
        ctx = jnp.einsum("bqhc,chv->bqhv", ctx_c, v_up_w)
    else:
        v = dof.qlinear(ckv_all, p["v_up"], qcfg, stream=p.get("kv_stream"),
                        bits=pv.bits("v_up")).reshape(B, Skv, H, m.d_v)
        ctx = jnp.einsum("bhqs,bshv->bqhv", probs, v)
    ctx = ctx.reshape(B, Sq, H * m.d_v)
    out = dof.qlinear(ctx, p["wo"], qcfg, stream=p.get("out_stream"),
                      bits=pv.bits("wo"))
    return out, new_cache
