"""Shared fixtures for the paper's figure and table reproduction.

- a trained tiny CNN teacher on a synthetic separable classification task
  (the paper's own experimental setting at CPU scale; accuracy is exact);
- a tiny LM teacher + calibration stream (degradation measured as
  normalized-L2 distillation loss / top-1 next-token agreement with the FP
  teacher — see DESIGN.md §9.3).
Fixtures are cached under paper/results/.
"""
from __future__ import annotations

import functools
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.paper_cnn import CONFIG as CNN_CFG
from repro.data.calib import CalibConfig, CalibDataset
from repro.models import ModelConfig, forward, init_model
from repro.models.cnn import forward_cnn, init_cnn

RESULTS = pathlib.Path(__file__).resolve().parent / "results"
RESULTS.mkdir(parents=True, exist_ok=True)

FAST = os.environ.get("REPRO_BENCH_FAST", "1") == "1"


# ---------------------------------------------------------------- CNN fixture

def synth_images(key, n, cfg=CNN_CFG):
    """Separable, CNN-learnable task: each class is a smooth low-frequency
    spatial template (survives stride/pooling), images = template + noise."""
    kx, kn = jax.random.split(key, 2)
    kb = jax.random.PRNGKey(777)           # class templates FIXED across calls
    hw = cfg.img_hw
    grid = jnp.arange(hw) / hw
    modes = jnp.stack([jnp.cos(jnp.pi * f * grid) for f in (0, 1, 2)])  # [3,hw]
    spatial = jnp.einsum("ih,jw->ijhw", modes, modes).reshape(9, hw, hw)
    coef = jax.random.normal(kb, (cfg.n_classes, 9, cfg.in_ch))
    basis = jnp.einsum("kfc,fhw->khwc", coef, spatial)
    basis = basis / jnp.linalg.norm(
        basis.reshape(cfg.n_classes, -1), axis=1)[:, None, None, None] * 12.0
    y = jax.random.randint(kx, (n,), 0, cfg.n_classes)
    x = basis[y] + jax.random.normal(kn, (n, hw, hw, cfg.in_ch)) * 1.0
    return x.astype(jnp.float32), y


@functools.lru_cache(maxsize=1)
def trained_cnn_teacher():
    """Train (or load) the FP CNN teacher; returns (params, eval_fn, data)."""
    cache = RESULTS / "cnn_teacher.npz"
    key = jax.random.PRNGKey(0)
    params = init_cnn(key, CNN_CFG, None)
    flat, treedef = jax.tree_util.tree_flatten(params)
    xtr, ytr = synth_images(jax.random.PRNGKey(1), 4096)
    xte, yte = synth_images(jax.random.PRNGKey(2), 1024)

    if cache.exists():
        data = np.load(cache)
        flat = [jnp.asarray(data[f"arr_{i}"]) for i in range(len(flat))]
        params = jax.tree_util.tree_unflatten(treedef, flat)
    else:
        from repro.optim.adam import Adam
        opt = Adam(lr=3e-3)
        state = opt.init(params)

        def loss_fn(p, x, y):
            logits = forward_cnn(p, CNN_CFG, None, x)["logits"]
            return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(len(y)), y])

        @jax.jit
        def step(p, s, x, y):
            l, g = jax.value_and_grad(loss_fn)(p, x, y)
            p, s = opt.update(g, s, p)
            return p, s, l

        steps = 300 if FAST else 1500
        bs = 128
        for i in range(steps):
            j = (i * bs) % (len(xtr) - bs)
            params, state, l = step(params, state, xtr[j:j + bs],
                                    ytr[j:j + bs])
        # induce heterogeneous channel ranges (the paper's MobileNet
        # pathology): scale conv_i's out-channels by exp(N(0,1.5)) and
        # divide conv_{i+1}'s matching in-channels — function-preserving
        # through ReLU, but catastrophic for layerwise 4-bit grids.  This is
        # exactly the imbalance CLE (App. D) exists to equalize.
        kimb = jax.random.PRNGKey(555)
        for i in range(len(params["convs"]) - 1):
            c = jnp.exp(jax.random.normal(jax.random.fold_in(kimb, i),
                                          (params["convs"][i]["w"].shape[-1],))
                        * 1.5)
            params["convs"][i]["w"] = params["convs"][i]["w"] * c
            params["convs"][i]["b"] = params["convs"][i]["b"] * c
            params["convs"][i + 1]["w"] = \
                params["convs"][i + 1]["w"] / c[None, None, :, None]
        np.savez(cache, *[np.asarray(l) for l in
                          jax.tree_util.tree_flatten(params)[0]])

    @jax.jit
    def acc_fn(p_any, qcfg_marker=None):
        raise RuntimeError  # placeholder, not used

    def accuracy(p, qcfg):
        logits = forward_cnn(p, CNN_CFG, qcfg, xte)["logits"]  # qft: noqa[QFT002] fixture: raw-qcfg ladder is the subject
        return float(jnp.mean(jnp.argmax(logits, -1) == yte))

    return params, accuracy, (xtr, ytr, xte, yte)


# ----------------------------------------------------------------- LM fixture

TINY_LM = ModelConfig(name="bench-lm", family="dense", n_layers=3, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=160, vocab=512,
                      head_dim=16, qk_norm=True, scan_layers=False,
                      remat=False)


@functools.lru_cache(maxsize=1)
def lm_teacher():
    return init_model(jax.random.PRNGKey(42), TINY_LM, None)


def lm_data(n=2048, seq=32, bs=16):
    return CalibDataset(CalibConfig(n_samples=n, seq_len=seq, batch_size=bs,
                                    vocab=TINY_LM.vocab, seed=3))


def lm_degradation(student, qcfg, batches=4):
    """(distill loss, top-1 next-token agreement vs teacher)."""
    from repro.core import backbone_l2
    teacher = lm_teacher()
    data = iter(lm_data())
    losses, agree = [], []
    for _ in range(batches):
        b = {k: jnp.asarray(v) for k, v in next(data).items()}
        so = forward(student, TINY_LM, qcfg, b)  # qft: noqa[QFT002] fixture: raw-qcfg ladder is the subject
        to = forward(teacher, TINY_LM, None, b)
        losses.append(float(backbone_l2(so["hidden"], to["hidden"])))
        agree.append(float(jnp.mean(
            jnp.argmax(so["logits"], -1) == jnp.argmax(to["logits"], -1))))
    return float(np.mean(losses)), float(np.mean(agree))
