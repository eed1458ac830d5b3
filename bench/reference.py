"""Plain reference of the benchmarked models, written from the published
descriptions and the QFT paper.  It imports nothing of the program.

- The weights of every cell come from :func:`init_weights`: seeded, made on
  the device in one jitted call, float32.  The program is handed the same
  tree, so both sides start from one set of weights that neither made.
- The model is a Qwen3-style dense decoder: RMSNorm (eps 1e-6),
  GQA attention with optional per-head q/k RMSNorm, rotate-half RoPE over
  the whole head, SwiGLU MLP, final RMSNorm, untied or tied lm_head.
- Quantization follows the QFT paper (arXiv:2212.02634), deployment-
  oriented W4A8: each linear's kernel scale is ``S_wL[m] * S_wR`` with
  ``S_wL = 1 / S_a`` of its input stream and a scalar ``S_wR``; weights on
  the signed grid ``+-(2^(b-1)-1)``; activations unsigned 8-bit with a
  zero-point; every ``round`` is a straight-through estimator; the loss is
  the normalized L2 between student and teacher final hidden states; Adam
  with the paper's cosine-with-reloads schedule.
- :func:`init_student` is the paper's pre-QFT step: max-min activation
  calibration per tensor, then PPQ (Algorithm 1) MMSE weight scales.

Everything runs in float32 with ``highest`` matmul precision.  With
``lowp=True`` every matmul operand is first rounded to float8 e4m3 under a
per-tensor scale: the control, one precision step below the programs'
bfloat16, which the comparison has to reject.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
NORM_EPS = 1e-6
FP8_MAX = 448.0


def dims(model: dict) -> dict:
    """Sizes the reference needs, from a configuration file's ``model``."""
    return {"L": model["num_hidden_layers"], "d": model["hidden_size"],
            "H": model["num_attention_heads"],
            "Hkv": model["num_key_value_heads"], "hd": model["head_dim"],
            "ff": model["intermediate_size"], "V": model["vocab_size"],
            "theta": float(model["rope_theta"]),
            "qk_norm": bool(model.get("qk_norm", False)),
            "tied": bool(model.get("tie_word_embeddings", False))}


# ---------------------------------------------------------------- weights

def init_weights(c: dict, key) -> dict:
    """Float32 weights from the PRNG ``key``, laid out as the program's
    parameter tree (layer-stacked leading axis).  Kernels ~ N(0, 1/fan_in),
    embedding ~ N(0, 0.02^2), norm gains 1 + N(0, 0.1^2)."""
    L, d, H, Hkv, hd, ff, V = (c[k] for k in ("L", "d", "H", "Hkv", "hd",
                                              "ff", "V"))
    ks = iter(jax.random.split(key, 16))

    def lin(fan_in, fan_out):
        return {"w": jax.random.normal(next(ks), (L, fan_in, fan_out), F32)
                * fan_in ** -0.5}

    def gain(*shape):
        return {"g": 1.0 + 0.1 * jax.random.normal(next(ks), shape, F32)}

    attn = {"wq": lin(d, H * hd), "wk": lin(d, Hkv * hd),
            "wv": lin(d, Hkv * hd), "wo": lin(H * hd, d)}
    if c["qk_norm"]:
        attn["q_norm"], attn["k_norm"] = gain(L, hd), gain(L, hd)
    p = {"embed": {"w": jax.random.normal(next(ks), (V, d), F32) * 0.02},
         "final_norm": gain(d),
         "layers": {"norm1": gain(L, d), "norm2": gain(L, d), "attn": attn,
                    "mlp": {"gate": lin(d, ff), "up": lin(d, ff),
                            "down": lin(ff, d)}}}
    if not c["tied"]:
        p["lm_head"] = {"w": jax.random.normal(next(ks), (d, V), F32)
                        * d ** -0.5}
    return p


def seed_key(seed: int):
    return jax.random.PRNGKey(seed % (2 ** 31))


def make_weights(c: dict, seed: int) -> dict:
    """The seed's weights in one jitted call.  The key is an argument, not
    a constant of the program, so every seed finds it in the compile
    cache."""
    return jax.jit(lambda k: init_weights(c, k))(seed_key(seed))


# ------------------------------------------------------------ primitives

def _fp8(a):
    """Round to float8 e4m3 under a per-tensor scale (the control); the
    rounding passes gradients straight through."""
    s = FP8_MAX / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    r = (a * s).astype(jnp.float8_e4m3fn).astype(F32) / s
    return a + jax.lax.stop_gradient(r - a)


def _mm(eq, a, b, lowp):
    if lowp:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision="highest",
                      preferred_element_type=F32)


def rmsnorm(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + NORM_EPS) * g


def rope(x, pos, theta):
    """x [S, h, hd], pos [S]: rotate-half RoPE over the whole head."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def ste_round(x):
    return x + jax.lax.stop_gradient(jnp.round(x) - x)


def fq_weight(w, scale, bits):
    qmax = 2.0 ** (bits - 1) - 1
    return jnp.clip(ste_round(w / scale), -qmax, qmax) * scale


def fq_act(x, stream, bits=8):
    s = jnp.exp(stream["log_sa"])
    zp = ste_round(stream["zp"])
    q = jnp.clip(ste_round(x / s) + zp, 0.0, 2.0 ** bits - 1)
    return (q - zp) * s


def ppq(w, bits, axes=None, iters=10):
    """QFT Algorithm 1 (PPQ): s <- <q, w> / <q, q>, from s0 = max|w|/qmax."""
    axes = tuple(range(w.ndim)) if axes is None else axes
    qmax = 2.0 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(w), axes, keepdims=True) / qmax, 1e-12)
    for _ in range(iters):
        q = jnp.clip(jnp.round(w / s), -qmax, qmax)
        num = jnp.sum(q * w, axes, keepdims=True)
        den = jnp.maximum(jnp.sum(q * q, axes, keepdims=True), 1e-12)
        s = jnp.where(num / den > 1e-12, num / den, s)
    return s


# ------------------------------------------------------------------ model

_STREAM = {"wq": "in_stream", "wk": "in_stream", "wv": "in_stream",
           "wo": "out_stream", "gate": "in_stream", "up": "in_stream",
           "down": "act_stream"}


def _linear(x, mod, name, q, lowp):
    """x @ W for one linear of ``mod``; ``q`` (QFT bits) fake-quantizes
    the input stream and the kernel (S_w = S_wL[m] * S_wR)."""
    w = mod[name]["w"]
    if q is not None:
        stream = mod[_STREAM[name]]
        x = fq_act(x, stream)
        scale = jnp.exp(-stream["log_sa"])[:, None] * jnp.exp(
            mod[name]["log_swr"])
        w = fq_weight(w, scale, q["w_bits"])
    return _mm("sd,df->sf", x, w, lowp)


def kv_int8(x, plen):
    """The serving cache's int8 K or V ([S, Hkv, hd]): one PPQ scale per
    KV head, fitted over the prompt's rows (``plen``, traced), which every
    later row is quantized with too."""
    keep = (jnp.arange(x.shape[0]) < plen)[:, None, None]
    s = ppq(jnp.where(keep, x, 0.0), 8, axes=(0, 2))
    return jnp.clip(jnp.round(x / s), -127.0, 127.0) * s


def _attention(qh, kh, vh, c, lowp, q_block, kv_plen=None):
    """Causal GQA softmax attention, f32, in blocks of query rows.  With
    ``kv_plen`` it reads the cache as served: queries of the prompt see
    its K and V as computed, later queries see the int8 cache."""
    S, H, hd = qh.shape
    G = H // c["Hkv"]
    kvs = [(kh, vh)]
    if kv_plen is not None:
        kvs.append((kv_int8(kh, kv_plen), kv_int8(vh, kv_plen)))
    kvs = [(jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1))
           for k, v in kvs]
    outs = []
    for q0 in range(0, S, q_block):
        qb = qh[q0:q0 + q_block]
        qpos = q0 + jnp.arange(qb.shape[0])
        causal = qpos[None, :, None] >= jnp.arange(S)[None, None, :]
        per = []
        for k, v in kvs:
            sc = jnp.where(causal, _mm("qhd,khd->hqk", qb, k, lowp)
                           * hd ** -0.5, -jnp.inf)
            per.append(_mm("hqk,khd->qhd", jax.nn.softmax(sc, -1), v, lowp))
        out = per[0]
        if kv_plen is not None:
            out = jnp.where((qpos < kv_plen)[:, None, None], per[0], per[1])
        outs.append(out)
    return jnp.concatenate(outs, 0)


def _layer(x, lp, pos, c, q, lowp, taps, q_block, kv_plen=None):
    hd, H, Hkv = c["hd"], c["H"], c["Hkv"]
    a, m = lp["attn"], lp["mlp"]
    tap = (lambda t: None) if taps is None else taps.append
    h = rmsnorm(x, lp["norm1"]["g"])
    tap(h)
    qh = _linear(h, a, "wq", q, lowp).reshape(-1, H, hd)
    kh = _linear(h, a, "wk", q, lowp).reshape(-1, Hkv, hd)
    vh = _linear(h, a, "wv", q, lowp).reshape(-1, Hkv, hd)
    if c["qk_norm"]:
        qh, kh = rmsnorm(qh, a["q_norm"]["g"]), rmsnorm(kh, a["k_norm"]["g"])
    qh, kh = rope(qh, pos, c["theta"]), rope(kh, pos, c["theta"])
    ctx = _attention(qh, kh, vh, c, lowp, q_block,
                     kv_plen).reshape(-1, H * hd)
    tap(ctx)
    x = x + _linear(ctx, a, "wo", q, lowp)
    h = rmsnorm(x, lp["norm2"]["g"])
    tap(h)
    act = jax.nn.silu(_linear(h, m, "gate", q, lowp)) * _linear(
        h, m, "up", q, lowp)
    tap(act)
    return x + _linear(act, m, "down", q, lowp)


def embed(params, tokens, q):
    w = params["embed"]["w"]
    if q is not None:
        w = fq_weight(w, jnp.exp(params["embed"]["log_s"]), q["embed_bits"])
    return w[tokens]


def hidden(params, tokens, c, q=None, lowp=False, taps=None, remat=False,
           q_block=2048, kv_plen=None):
    """Final-norm hidden states [S, d] of one sequence ``tokens`` [S];
    ``taps``, a list, collects each layer's four stream inputs;
    ``kv_plen``: attention reads the serving cache (``_attention``)."""
    x = embed(params, tokens, q)
    pos = jnp.arange(tokens.shape[0])
    for i in range(c["L"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        fn = lambda x, lp: _layer(x, lp, pos, c, q, lowp, taps, q_block,
                                  kv_plen)
        x = jax.checkpoint(fn)(x, lp) if remat else fn(x, lp)
    return rmsnorm(x, params["final_norm"]["g"])


def logits(params, h, c, lowp=False):
    w = params["embed"]["w"].T if c["tied"] else params["lm_head"]["w"]
    return _mm("sd,dv->sv", h, w, lowp)


# ------------------------------------------------- QFT: init, loss, Adam

def _stream_from_range(lo, hi, width, bits=8):
    lo = jnp.minimum(lo, 0.0)
    hi = jnp.maximum(hi, lo + 1e-6)
    scale = (hi - lo) / (2.0 ** bits - 1)
    return {"log_sa": jnp.full((width,), jnp.log(scale), F32),
            "zp": jnp.full((width,), jnp.round(-lo / scale), F32)}


def init_student(teacher, c, q, calib):
    """The paper's pre-QFT step on ``teacher``: per-tensor max-min
    calibration of the four activation streams of each layer over the
    ``calib`` sequences [n, S], then PPQ scales for every kernel (tied to
    its input stream) and per-row PPQ for the embedding.  Returns the
    student tree (the program's layout)."""
    L, d = c["L"], c["d"]
    widths = (d, c["H"] * c["hd"], d, c["ff"])
    names = (("attn", "in_stream"), ("attn", "out_stream"),
             ("mlp", "in_stream"), ("mlp", "act_stream"))
    lo = [[jnp.inf] * 4 for _ in range(L)]
    hi = [[-jnp.inf] * 4 for _ in range(L)]
    for row in calib:
        taps = []
        hidden(teacher, row, c, taps=taps, q_block=512)
        for i in range(L):
            for j in range(4):
                t = taps[4 * i + j]
                lo[i][j] = jnp.minimum(lo[i][j], jnp.min(t))
                hi[i][j] = jnp.maximum(hi[i][j], jnp.max(t))
    layers = jax.tree.map(lambda a: a, teacher["layers"])
    for j, (mod, sname) in enumerate(names):
        per = [_stream_from_range(lo[i][j], hi[i][j], widths[j])
               for i in range(L)]
        layers[mod][sname] = jax.tree.map(lambda *a: jnp.stack(a), *per)
    for mod, lin in (("attn", "wq"), ("attn", "wk"), ("attn", "wv"),
                     ("attn", "wo"), ("mlp", "gate"), ("mlp", "up"),
                     ("mlp", "down")):
        node = layers[mod]
        sa = node[_STREAM[lin]]["log_sa"]
        w = node[lin]["w"]
        s = jnp.stack([ppq(w[i] * jnp.exp(sa[i])[:, None], q["w_bits"])
                       .reshape(()) for i in range(L)])
        node[lin] = {"w": w, "log_swr": jnp.log(jnp.maximum(s, 1e-12))}
    ew = teacher["embed"]["w"]
    student = {"embed": {"w": ew, "log_s": jnp.log(jnp.maximum(
                   ppq(ew, q["embed_bits"], axes=(1,)), 1e-12))},
               "final_norm": teacher["final_norm"], "layers": layers,
               "head_stream": {"log_sa": jnp.full((d,), math.log(1 / 16), F32),
                               "zp": jnp.zeros((d,), F32)}}
    if not c["tied"]:
        hs = student["head_stream"]["log_sa"]
        s = ppq(teacher["lm_head"]["w"] * jnp.exp(hs)[:, None],
                q["embed_bits"]).reshape(())
        student["lm_head"] = {"w": teacher["lm_head"]["w"],
                              "log_swr": jnp.log(jnp.maximum(s, 1e-12))}
    return student


def qft_loss(student, teacher, tokens, c, q, lowp=False):
    """Normalized backbone L2, mean over the tokens of one sequence."""
    h_t = jax.lax.stop_gradient(hidden(teacher, tokens, c, lowp=lowp,
                                       remat=True))
    h_s = hidden(student, tokens, c, q=q, lowp=lowp, remat=True)
    err = jnp.sum((h_s - h_t) ** 2, -1)
    return jnp.mean(err / (jnp.sum(h_t ** 2, -1) + 1e-6))


def cosine_reload_lr(step, base_lr, steps_per_cycle, n_cycles=3,
                     reload=0.5):
    cycle = min(step // steps_per_cycle, n_cycles - 1)
    t = min(max((step - cycle * steps_per_cycle) / steps_per_cycle, 0.0), 1)
    return 0.5 * base_lr * reload ** cycle * (1 + math.cos(math.pi * t))


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                      for x in jax.tree.leaves(tree)])


def make_qft_reference(c, q, opt, lowp=False):
    """Jitted pieces of one reference QFT step: the loss and gradient of one
    sequence, and the Adam update.  ``opt``: base_lr, steps_per_cycle,
    b1, b2, eps."""
    grad_row = jax.jit(jax.value_and_grad(
        lambda s, t, tok: qft_loss(s, t, tok, c, q, lowp)))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adam(params, m, v, g, step, lr):
        b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        upd = lambda p, m, v: p - lr * (m / (1 - b1 ** step)) / (
            jnp.sqrt(v / (1 - b2 ** step)) + eps)
        return jax.tree.map(upd, params, m, v), m, v

    def run(student, teacher, batches):
        """Steps over ``batches`` (each [B, S]); returns the per-step losses,
        each leaf's norm of the first step's gradient, and the final
        parameters."""
        m = jax.tree.map(jnp.zeros_like, student)
        v = jax.tree.map(jnp.zeros_like, student)
        losses, first = [], None
        for i, batch in enumerate(batches, start=1):
            loss, grad = 0.0, None
            for row in batch:              # the batch mean, one row at a time
                l, g = grad_row(student, teacher, row)
                loss = loss + l / len(batch)
                g = jax.tree.map(lambda a: a / len(batch), g)
                grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
            losses.append(float(loss))
            if first is None:
                first = leaf_norms(grad)
            lr = cosine_reload_lr(i, opt["base_lr"], opt["steps_per_cycle"])
            student, m, v = adam(student, m, v, grad, i, lr)
        return losses, first, student

    return run


# ---------------------------------------------------------------- serving

def quantize_for_serving(teacher, c, q):
    """Weights as the configuration serves them: every kernel on its
    signed ``w_bits`` grid under a scalar PPQ scale, lm_head at
    ``embed_bits``, embedding rows at ``embed_bits`` (per-row PPQ).
    Activations stay unquantized."""
    def fq(w, bits, axes=None):
        qmax = 2.0 ** (bits - 1) - 1
        s = ppq(w, bits, axes)
        return jnp.clip(jnp.round(w / s), -qmax, qmax) * s

    layers = jax.tree.map(lambda a: a, teacher["layers"])
    for mod, lin in (("attn", "wq"), ("attn", "wk"), ("attn", "wv"),
                     ("attn", "wo"), ("mlp", "gate"), ("mlp", "up"),
                     ("mlp", "down")):
        w = layers[mod][lin]["w"]
        layers[mod][lin] = {"w": jnp.stack([fq(w[i], q["w_bits"])
                                            for i in range(c["L"])])}
    out = {"embed": {"w": fq(teacher["embed"]["w"], q["embed_bits"],
                             axes=(1,))},
           "final_norm": teacher["final_norm"], "layers": layers}
    if not c["tied"]:
        out["lm_head"] = {"w": fq(teacher["lm_head"]["w"], q["embed_bits"])}
    return out


def make_served_gap(c, lowp=False, q_block=1024):
    """gap(params, seq, plen, served) -> [len(served)]: for the served
    tokens of one request, the reference's best logit minus its logit of
    the served token.  ``seq`` is the prompt and the served tokens but the
    last, right-padded (causal attention never reads the padding); the
    first ``plen`` are the prompt, whose K and V fit the int8 cache's
    scales.  With ``lowp`` the gap is read for the token that the fp8
    forward puts first instead (the control).  One compiled program per
    padded length: the prompt length is traced and ``served`` is padded on
    the host to the length of ``seq``."""
    def fwd(params, seq, plen, low):
        h = hidden(params, seq, c, lowp=low, q_block=q_block, kv_plen=plen)
        return logits(params, h, c, lowp=low)

    @jax.jit
    def gaps(params, seq, plen, served):
        T = seq.shape[0]
        rows = jnp.minimum(plen - 1 + jnp.arange(T), T - 1)
        ref = fwd(params, seq, plen, False)[rows]
        pick = (jnp.argmax(fwd(params, seq, plen, True)[rows], -1) if lowp
                else served)
        chosen = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        return jnp.max(ref, -1) - chosen

    def gap(params, seq, plen, served):
        n = len(served)
        padded = np.zeros(seq.shape[0], np.int32)
        padded[:n] = served
        return np.asarray(gaps(params, seq, jnp.int32(plen), padded))[:n]

    return gap
