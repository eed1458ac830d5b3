"""Generic quantization-aware model assembly for the whole architecture pool.

One ``init_model``/``forward`` pair covers: dense GQA LMs, MoE (+MLA), pure
SSM (Mamba2), hybrid (Zamba2: Mamba backbone + ONE shared attention block
invoked every ``attn_every`` layers), encoder-decoder (Seamless backbone,
audio frontend stubbed to precomputed frame embeddings) and VLM backbones
(Qwen2-VL: patch embeddings stubbed, M-RoPE positions).

Teacher (qcfg=None) and student (qcfg set) run the *same* code, so the QFT
distillation pair is structurally aligned by construction.

Layers are ``lax.scan``-ed over vmap-stacked params when cfg.scan_layers
(production: O(1) compile in depth); smoke/benchmark runs may set
scan_layers=False to enable per-layer activation taps (calibration, bias
correction, CLE init).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ..core import dof
from ..core.plan import plan_view
from ..core.qconfig import QuantConfig
from .attention import (attention, init_attention, init_kv_cache, init_mla,
                        init_mla_cache, mla_attention)
from .config import ModelConfig
from .layers import embed_lookup, init_embed, init_mlp, init_rmsnorm, mlp, rmsnorm
from .moe import init_moe, moe_block
from .ssm import init_ssm, init_ssm_cache, ssm_block

Params = dict[str, Any]


# --------------------------------------------------------------------------
# Layer init / forward per family
# --------------------------------------------------------------------------

def _attn_block(x, lp, cfg, qcfg, positions, cache, taps, prefix, plan=None,
                use_pallas=False, interpret=None):
    """One attention+MLP layer; ``plan`` is a PlanView scoped to the layer's
    container path (``layers``, ``shared_attn``, …) and narrows to the
    ``attn``/``mlp`` subtrees here.  ``use_pallas``/``interpret`` are the
    decode kernel-routing knobs (models/attention.py vector-pos path)."""
    pv = plan_view(plan)
    x = constrain_act(x)
    h = rmsnorm(x, lp["norm1"])
    _tap(taps, prefix + ".attn_in", h)
    if cfg.mla is not None:
        a, new_cache = mla_attention(h, lp["attn"], cfg, qcfg, positions,
                                     cache, plan=pv.child("attn"))
    else:
        a, new_cache = attention(h, lp["attn"], cfg, qcfg, positions, cache,
                                 taps=taps, prefix=prefix + ".attn",
                                 plan=pv.child("attn"), use_pallas=use_pallas,
                                 interpret=interpret)
    _tap(taps, prefix + ".attn_out", a)
    x = x + a
    h = rmsnorm(x, lp["norm2"])
    _tap(taps, prefix + ".mlp_in", h)
    if cfg.moe is not None:
        m = moe_block(h, lp["mlp"], cfg, qcfg,
                      mode=_RUNTIME.get("moe_mode", "sorted"),
                      expert_fn=_RUNTIME.get("moe_expert_fn"),
                      moe_fn=_RUNTIME.get("moe_fn"),
                      plan=pv.child("mlp"))
    else:
        m = mlp(h, lp["mlp"], qcfg, cfg.mlp, taps=taps, prefix=prefix + ".mlp",
                plan=pv.child("mlp"))
    _tap(taps, prefix + ".mlp_out", m)
    return constrain_act(x + m), new_cache


def _ssm_layer(x, lp, cfg, qcfg, cache, taps, prefix, plan=None):
    pv = plan_view(plan)
    x = constrain_act(x)
    h = rmsnorm(x, lp["norm1"])
    _tap(taps, prefix + ".ssm_in", h)
    y, new_cache = ssm_block(h, lp["ssm"], cfg, qcfg, cache,
                             taps=taps, prefix=prefix + ".ssm",
                             plan=pv.child("ssm"))
    _tap(taps, prefix + ".ssm_out", y)
    return constrain_act(x + y), new_cache


def _init_attn_layer(key, cfg: ModelConfig, qcfg) -> Params:
    ks = jax.random.split(key, 2)
    lp: Params = {"norm1": init_rmsnorm(cfg.d_model),
                  "norm2": init_rmsnorm(cfg.d_model)}
    lp["attn"] = (init_mla(ks[0], cfg, qcfg) if cfg.mla is not None
                  else init_attention(ks[0], cfg, qcfg))
    lp["mlp"] = (init_moe(ks[1], cfg, qcfg) if cfg.moe is not None
                 else init_mlp(ks[1], cfg.d_model, cfg.d_ff, qcfg, cfg.mlp,
                               bias=False))
    return lp


def _init_ssm_layer(key, cfg: ModelConfig, qcfg) -> Params:
    return {"norm1": init_rmsnorm(cfg.d_model),
            "ssm": init_ssm(key, cfg, qcfg)}


# --------------------------------------------------------------------------
# Tap collection (scan_layers=False only)
# --------------------------------------------------------------------------

_RUNTIME: dict[str, Any] = {}


def set_runtime(**kw) -> None:
    """Process-level runtime knobs (moe_mode / moe_expert_fn / act_spec)."""
    _RUNTIME.update(kw)


def constrain_act(x: jax.Array) -> jax.Array:
    """Pin the residual-stream sharding (batch over DP axes, feature open).

    Without this, GSPMD may resolve the scan carry to *replicated*, blowing
    activation collectives up by the DP degree (observed 16× on the first
    dry-run — see EXPERIMENTS.md §Dry-run).  Set via
    ``set_runtime(act_spec=("data",))`` (or ("pod","data")); requires an
    ambient mesh (jax.set_mesh) at trace time.
    """
    dp = _RUNTIME.get("act_spec")
    if dp is None:
        return x
    from jax.sharding import PartitionSpec as P
    spec = P(dp, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)


def _tap(taps: dict | None, name: str, x: jax.Array) -> None:
    if taps is None:
        return
    xf = x.astype(jnp.float32).reshape(-1, x.shape[-1])
    taps[name] = {"min": jnp.min(xf, 0), "max": jnp.max(xf, 0),
                  "mean": jnp.mean(xf, 0)}


# --------------------------------------------------------------------------
# Model init
# --------------------------------------------------------------------------

def init_model(key: jax.Array, cfg: ModelConfig,
               qcfg: QuantConfig | None) -> Params:
    keys = jax.random.split(key, 8)
    V, d = cfg.vocab_padded, cfg.d_model
    params: Params = {"final_norm": init_rmsnorm(d)}
    if cfg.family != "encdec":
        params["embed"] = init_embed(keys[0], V, d, qcfg)
    if not cfg.tie_embeddings:
        params["lm_head"] = dof.init_qlinear(
            keys[1], d, V, qcfg, name="lm_head",
            w_bits=None if qcfg is None else qcfg.embed_bits)
    if qcfg is not None:
        params["head_stream"] = dof.init_stream(d)

    def stack(init_fn, n, key):
        return jax.vmap(lambda k: init_fn(k, cfg, qcfg))(jax.random.split(key, n))

    fam = cfg.family
    if fam in ("dense", "moe", "mla_moe", "vlm"):
        params["layers"] = stack(_init_attn_layer, cfg.n_layers, keys[2])
    elif fam == "ssm":
        params["layers"] = stack(_init_ssm_layer, cfg.n_layers, keys[2])
    elif fam == "hybrid":
        k = cfg.attn_every
        G, r = cfg.n_layers // k, cfg.n_layers % k
        body = stack(_init_ssm_layer, G * k, keys[2])
        params["layers"] = jax.tree.map(
            lambda a: a.reshape((G, k) + a.shape[1:]), body)
        if r:
            params["tail"] = stack(_init_ssm_layer, r, keys[3])
        params["shared_attn"] = _init_attn_layer(keys[4],
                                                 _dense_view(cfg), qcfg)
    elif fam == "encdec":
        params["embed"] = init_embed(keys[0], V, d, qcfg)   # decoder tokens
        params["frame_proj"] = dof.init_qlinear(keys[5], d, d, qcfg,
                                                name="frame_proj")
        params["enc_layers"] = stack(_init_enc_layer, cfg.enc_layers, keys[2])
        params["dec_layers"] = stack(_init_dec_layer, cfg.n_layers, keys[3])
        params["enc_final_norm"] = init_rmsnorm(d)
    else:
        raise ValueError(fam)
    return params


def _dense_view(cfg: ModelConfig) -> ModelConfig:
    """Hybrid's shared attention block behaves like a dense layer."""
    return dataclasses.replace(cfg, moe=None, mla=None)


def _init_enc_layer(key, cfg: ModelConfig, qcfg) -> Params:
    return _init_attn_layer(key, _dense_view(cfg), qcfg)


def _init_dec_layer(key, cfg: ModelConfig, qcfg) -> Params:
    ks = jax.random.split(key, 2)
    lp = _init_attn_layer(ks[0], _dense_view(cfg), qcfg)
    lp["norm_x"] = init_rmsnorm(cfg.d_model)
    lp["cross"] = init_attention(ks[1], _dense_view(cfg), qcfg)
    return lp


# --------------------------------------------------------------------------
# Cache init
# --------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, enc_len: int | None = None) -> Params:
    """``enc_len``: encdec decode-only caches prebuild the cross-KV slots
    (a decode step then never needs encoder frames)."""
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        return init_kv_cache(cfg, batch, max_len, cfg.n_layers, dtype)
    if fam == "mla_moe":
        return init_mla_cache(cfg, batch, max_len, cfg.n_layers, dtype)
    if fam == "ssm":
        return init_ssm_cache(cfg, batch, cfg.n_layers)
    if fam == "hybrid":
        k = cfg.attn_every
        G, r = cfg.n_layers // k, cfg.n_layers % k
        c: Params = {"mamba": init_ssm_cache(cfg, batch, G * k)}
        c["mamba"] = jax.tree.map(
            lambda a: a.reshape((G, k) + a.shape[1:]), c["mamba"])
        if r:
            c["tail"] = init_ssm_cache(cfg, batch, r)
        c["attn"] = init_kv_cache(cfg, batch, max_len, G, dtype)
        return c
    if fam == "encdec":
        c = init_kv_cache(cfg, batch, max_len, cfg.n_layers, dtype)
        cross = None
        if enc_len is not None:
            Hkv, hd = cfg.n_kv_heads_padded, cfg.head_dim
            cross = {"k": jnp.zeros((cfg.n_layers, batch, enc_len, Hkv, hd),
                                    dtype),
                     "v": jnp.zeros((cfg.n_layers, batch, enc_len, Hkv, hd),
                                    dtype)}
        return {"self": c, "cross": cross}   # cross filled at prefill
    raise ValueError(fam)


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def _maybe_remat(fn, cfg: ModelConfig):
    if not cfg.remat or cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "save_dots":
        # keep matmul/psum outputs; recompute only elementwise (cuts the
        # remat-replayed TP collectives — §Perf)
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


def _scan_layers(x, layers, cfg, qcfg, positions, cache_kv, body):
    """Generic scan helper. cache_kv: pytree stacked on L (or None)."""
    wrapped = _maybe_remat(body, cfg)

    if not cfg.scan_layers:
        n = jax.tree.leaves(layers)[0].shape[0]
        new_slices = []
        for i in range(n):
            lp = jax.tree.map(lambda a: a[i], layers)
            cs = None if cache_kv is None else jax.tree.map(lambda a: a[i], cache_kv)
            x, ns = body(x, lp, cs, i)
            new_slices.append(ns)
        new_cache = (None if cache_kv is None else
                     jax.tree.map(lambda *s: jnp.stack(s), *new_slices))
        return x, new_cache

    def scan_body(carry, xs):
        lp, cs = xs
        y, ns = wrapped(carry, lp, cs, None)
        return y, ns

    x, new_cache = jax.lax.scan(scan_body, x,
                                (layers, cache_kv))
    return x, new_cache


def forward(params: Params, cfg: ModelConfig, qcfg: QuantConfig | None,
            batch: dict[str, jax.Array], cache: Params | None = None,
            collect_taps: bool = False,
            compute_dtype=jnp.bfloat16, plan=None, use_pallas: bool = False,
            interpret: bool | None = None) -> dict[str, Any]:
    """Returns {hidden, logits, cache, taps}.

    modes are implicit: cache=None → full-sequence (train / no-cache eval);
    cache given and S>1 → prefill; cache given and S==1 → decode.

    ``use_pallas``/``interpret`` route the per-slot decode attention through
    the flash-decode kernel (serving engines thread them from the
    DeployPlan); static at trace time, so they key the jit cache like any
    other Python argument.

    ``plan`` (a resolved :class:`core.plan.QuantPlan`) makes the fake-quant
    forward plan-aware: every qlinear quantizes at its plan bits — the same
    path-qualified lookup export/serving do — so finetuning happens on
    exactly the grid the artifact ships on (the train≡export invariant; see
    DESIGN.md).  Lookups resolve at trace time (static Python ints), so jit
    caching, scan layer-stacking and the fast tier are unaffected.  Without
    a plan the role-ladder defaults apply (backbone at ``qcfg.w_bits``,
    lm_head at ``embed_bits``, routers at ``router_bits``) — the correct
    grid whenever the plan assigns no non-default bits.
    """
    taps: dict | None = {} if collect_taps else None
    pv = plan_view(plan)
    fam = cfg.family
    if fam == "encdec":
        return _forward_encdec(params, cfg, qcfg, batch, cache, taps,
                               compute_dtype, pv)

    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_lookup(tokens, params["embed"], qcfg, compute_dtype)
    if fam == "vlm" and "patch_embeds" in batch:
        x = jnp.concatenate(
            [batch["patch_embeds"].astype(compute_dtype), x], axis=1)
        S = x.shape[1]
    if cache is not None and "pos" in cache:
        base = cache["pos"]
    elif (cache is not None and isinstance(cache.get("attn"), dict)
          and "pos" in cache["attn"]):
        base = cache["attn"]["pos"]              # hybrid: shared-attn cache
    else:
        base = 0
    # per-slot serving caches carry a [B] position vector — one offset per
    # slot — instead of the scalar the static train/prefill paths use
    off = base[:, None] if getattr(base, "ndim", 0) == 1 else base
    if "positions" in batch:
        positions = batch["positions"]
    elif cfg.mrope_sections:
        pos1 = off + jnp.arange(S)[None, :]      # [1,S] or [B,S]
        positions = jnp.broadcast_to(pos1[:, None, :], (B, 3, S))
    else:
        positions = jnp.broadcast_to(off + jnp.arange(S)[None, :], (B, S))

    new_cache = None
    if fam in ("dense", "moe", "mla_moe", "vlm"):
        # "pos" (and the paged-KV page table "pt") are shared across layers:
        # excluded from the per-layer scan tree, re-injected into every
        # layer's cache view, threaded through unchanged.  The paged cache's
        # [L, ...] stacks stay whole too, each layer handed them with its
        # index (a per-layer slice of a stack is a copy of it every step):
        # the int8 pools "k"/"v", which each layer writes in place, ride the
        # layer loop as carry; the scales only ride along
        pt = None if cache is None else cache.get("pt")
        shared = ("pos", "pt") if pt is None else (
            "pos", "pt", "k", "v", "k_scale", "v_scale")
        ck = None if cache is None else {k: cache[k] for k in cache
                                         if k not in shared}
        pos = None if cache is None else cache["pos"]
        pools = None
        if pt is not None:
            pools = (cache["k"], cache["v"])
            ck["layer"] = jnp.arange(cfg.n_layers, dtype=jnp.int32)

        def body(carry, lp, cs, i):
            h, pools = carry
            c = None if cs is None else {**cs, "pos": pos}
            if pt is not None:
                c.update(pt=pt, k=pools[0], v=pools[1],
                         k_scale=cache["k_scale"], v_scale=cache["v_scale"])
            h, nc = _attn_block(h, lp, cfg, qcfg, positions, c, taps,
                                f"L{i}" if i is not None else "L",
                                plan=pv.child("layers"),
                                use_pallas=use_pallas, interpret=interpret)
            if nc is not None:
                if pt is not None:
                    pools = (nc["k"], nc["v"])
                nc = {k: v for k, v in nc.items()
                      if k not in shared and k != "layer"}
            return (h, pools), nc

        (x, pools), nk = _scan_layers((x, pools), params["layers"], cfg,
                                      qcfg, positions, ck, body)
        if cache is not None:
            new_cache = {**nk, "pos": cache["pos"] + S}
            if pt is not None:
                new_cache.update(pt=pt, k=pools[0], v=pools[1],
                                 k_scale=cache["k_scale"],
                                 v_scale=cache["v_scale"])

    elif fam == "ssm":
        def body(h, lp, cs, i):
            return _ssm_layer(h, lp, cfg, qcfg, cs, taps,
                              f"L{i}" if i is not None else "L",
                              plan=pv.child("layers"))
        x, nk = _scan_layers(x, params["layers"], cfg, qcfg, positions, cache, body)
        new_cache = nk

    elif fam == "hybrid":
        x, new_cache = _forward_hybrid(params, cfg, qcfg, x, positions,
                                       cache, taps, pv,
                                       use_pallas=use_pallas,
                                       interpret=interpret)

    h = rmsnorm(x, params["final_norm"])
    if cfg.tie_embeddings:
        w = params["embed"]["w"].astype(h.dtype)
        logits = h @ w.T
    else:
        logits = dof.qlinear(h, params["lm_head"], qcfg,
                             stream=params.get("head_stream"),
                             bits=None if qcfg is None
                             else pv.bits("lm_head", qcfg.embed_bits))
    return {"hidden": h, "logits": logits, "cache": new_cache, "taps": taps}


def _forward_hybrid(params, cfg, qcfg, x, positions, cache, taps, pv,
                    use_pallas=False, interpret=None):
    k = cfg.attn_every
    G, r = cfg.n_layers // k, cfg.n_layers % k
    shared = params["shared_attn"]
    dcfg = _dense_view(cfg)
    attn_pos = None if cache is None else cache["attn"]["pos"]

    def group_body(h, gp, cs, gi):
        mcs = None if cs is None else cs[0]
        nm_slices = []
        for j in range(k):
            lp = jax.tree.map(lambda a: a[j], gp)
            mc = None if mcs is None else jax.tree.map(lambda a: a[j], mcs)
            h, nm = _ssm_layer(h, lp, cfg, qcfg, mc, taps, f"G.m{j}",
                               plan=pv.child("layers"))
            nm_slices.append(nm)
        ac = None if cs is None else {**cs[1], "pos": attn_pos}
        h, na = _attn_block(h, shared, dcfg, qcfg, positions, ac, taps,
                            "G.attn", plan=pv.child("shared_attn"),
                            use_pallas=use_pallas, interpret=interpret)
        nm_stack = (None if mcs is None else
                    jax.tree.map(lambda *s: jnp.stack(s), *nm_slices))
        if na is not None:
            na = {kk: v for kk, v in na.items() if kk != "pos"}
        return h, (nm_stack, na)

    wrapped = _maybe_remat(group_body, cfg)
    if cfg.scan_layers:
        cs_stack = None
        if cache is not None:
            ac = {kk: cache["attn"][kk] for kk in cache["attn"] if kk != "pos"}
            cs_stack = (cache["mamba"], ac)

        def scan_body(carry, xs):
            gp, cs = xs
            return wrapped(carry, gp, cs, None)

        x, (nm, na) = jax.lax.scan(scan_body, x,
                                   (params["layers"], cs_stack))
    else:
        ng = jax.tree.leaves(params["layers"])[0].shape[0]
        nms, nas = [], []
        for gi in range(ng):
            gp = jax.tree.map(lambda a: a[gi], params["layers"])
            cs = None
            if cache is not None:
                cs = (jax.tree.map(lambda a: a[gi], cache["mamba"]),
                      jax.tree.map(lambda a: a[gi],
                                   {kk: cache["attn"][kk]
                                    for kk in cache["attn"] if kk != "pos"}))
            x, (nm, na) = group_body(x, gp, cs, gi)
            nms.append(nm); nas.append(na)
        nm = (None if cache is None else jax.tree.map(lambda *s: jnp.stack(s), *nms))
        na = (None if cache is None else jax.tree.map(lambda *s: jnp.stack(s), *nas))

    new_cache = None
    S = x.shape[1]
    if r:
        def tail_body(h, lp, cs, i):
            return _ssm_layer(h, lp, cfg, qcfg, cs, taps, f"T{i}",
                              plan=pv.child("tail"))
        x, nt = _scan_layers(x, params["tail"], cfg, qcfg, positions,
                             None if cache is None else cache["tail"], tail_body)
    if cache is not None:
        new_cache = {"mamba": nm, "tail": (nt if r else None),
                     "attn": {**na, "pos": cache["attn"]["pos"] + S}}
        if not r:
            new_cache.pop("tail")
    return x, new_cache


def _forward_encdec(params, cfg, qcfg, batch, cache, taps, compute_dtype, pv):
    d = cfg.d_model
    dcfg = _dense_view(cfg)
    enc_out = None
    new_cache: Params = {}
    epv, dpv = pv.child("enc_layers"), pv.child("dec_layers")

    if cache is None or cache.get("cross") is None:
        frames = batch["frames"].astype(compute_dtype)
        e = dof.qlinear(frames, params["frame_proj"], qcfg,
                        bits=pv.bits("frame_proj"))
        Se = e.shape[1]
        epos = jnp.broadcast_to(jnp.arange(Se)[None], (e.shape[0], Se))

        def enc_body(h, lp, cs, i):
            h2 = rmsnorm(h, lp["norm1"])
            a, _ = attention(h2, lp["attn"], dcfg, qcfg, epos, None,
                             plan=epv.child("attn"))
            h = h + a
            h2 = rmsnorm(h, lp["norm2"])
            return h + mlp(h2, lp["mlp"], qcfg, cfg.mlp,
                           plan=epv.child("mlp")), None

        e, _ = _scan_layers(e, params["enc_layers"], cfg, qcfg, epos, None,
                            enc_body)
        enc_out = rmsnorm(e, params["enc_final_norm"])

    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_lookup(tokens, params["embed"], qcfg, compute_dtype)
    base = cache["self"]["pos"] if cache is not None else 0
    positions = jnp.broadcast_to(base + jnp.arange(S)[None, :], (B, S))

    # cross K/V: computed once from encoder output, cached thereafter
    if cache is not None and cache.get("cross") is not None:
        cross_kv = cache["cross"]
    else:
        cross_kv = None  # computed per layer below (and stacked if caching)

    ck = None
    pos = None
    if cache is not None:
        ck = {k: cache["self"][k] for k in cache["self"] if k != "pos"}
        pos = cache["self"]["pos"]
        if cross_kv is not None:
            ck = (ck, cross_kv)
        else:
            ck = (ck, None)

    cpv = dpv.child("cross")

    def dec_body(h, lp, cs, i):
        scs = None if cs is None else ({**cs[0], "pos": pos})
        h2 = rmsnorm(h, lp["norm1"])
        a, ns = attention(h2, lp["attn"], dcfg, qcfg, positions, scs,
                          plan=dpv.child("attn"))
        h = h + a
        # cross attention
        h2 = rmsnorm(h, lp["norm_x"])
        cp = lp["cross"]
        ins = cp.get("in_stream")
        Bq, Sq = h2.shape[0], h2.shape[1]
        hd, H, Hkv = cfg.head_dim, cfg.n_heads_padded, cfg.n_kv_heads_padded
        q = dof.qlinear(h2, cp["wq"], qcfg, stream=ins,
                        bits=cpv.bits("wq")).reshape(Bq, Sq, H, hd)
        if cs is not None and cs[1] is not None:
            ckx, cvx = cs[1]["k"], cs[1]["v"]
        else:
            ckx = dof.qlinear(enc_out, cp["wk"], qcfg, stream=ins,
                              bits=cpv.bits("wk")) \
                .reshape(Bq, -1, Hkv, hd)
            cvx = dof.qlinear(enc_out, cp["wv"], qcfg, stream=ins,
                              bits=cpv.bits("wv")) \
                .reshape(Bq, -1, Hkv, hd)
        from .attention import _sdpa
        a = _sdpa(q, ckx, cvx, causal=False, q_offset=0)
        a = dof.qlinear(a.reshape(Bq, Sq, H * hd), cp["wo"], qcfg,
                        stream=cp.get("out_stream"), bits=cpv.bits("wo"))
        h = h + a
        h2 = rmsnorm(h, lp["norm2"])
        h = h + mlp(h2, lp["mlp"], qcfg, cfg.mlp, plan=dpv.child("mlp"))
        if ns is not None:
            ns = {k: v for k, v in ns.items() if k != "pos"}
            return h, (ns, {"k": ckx, "v": cvx})
        return h, None

    x, nk = _scan_layers(x, params["dec_layers"], cfg, qcfg, positions, ck,
                         dec_body)
    h = rmsnorm(x, params["final_norm"])
    logits = dof.qlinear(h, params["lm_head"], qcfg,
                         stream=params.get("head_stream"),
                         bits=None if qcfg is None
                         else pv.bits("lm_head", qcfg.embed_bits))
    out_cache = None
    if cache is not None:
        out_cache = {"self": {**nk[0], "pos": cache["self"]["pos"] + S},
                     "cross": nk[1]}
    return {"hidden": h, "logits": logits, "cache": out_cache, "taps": taps,
            "enc_out": enc_out}
