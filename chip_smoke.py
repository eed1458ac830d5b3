"""Bring-up check of the main path on a TPU, at Qwen3-8B's published widths.

    python chip_smoke.py               # one chip: quantize → export → serve
    python chip_smoke.py --four-chips  # four chips: sharded QFT training

One chip runs ``run_pipeline`` (calibrate → MMSE init → a few QFT steps →
export → evaluate with the Pallas route on) on the one-chip share of
qwen3-8b (``configs/qwen3_8b.py``: ``REDUCED``, 2 layers and 1/8 of the
vocabulary), drops the teacher and student, and serves the int4 artifact
through ``Engine.from_artifact`` with the decode-attention kernel on every
layer.  It then checks the decode kernel against its XLA reference at the
engine's cache shape.

``--four-chips`` runs only the sharded trainer of ``launch/train.py``: a few
steps of a 4-layer, full-vocabulary model that one chip cannot hold, its
per-device memory, and the first step of the one-chip cut sharded over four
chips against the same step on the first device alone.

Each phase prints its wall time (compilation included; no speed is
claimed).  Any failed check raises, so the exit code is non-zero.  The last
line of stdout is one JSON object naming the device JAX ran on.  It runs
only on a TPU: on any other backend it exits non-zero before any work.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "qwen3-8b"

#: pipeline/cli.py's export-parity bar: dequantized artifact vs the
#: student's fake-quant weights, elementwise
EXPORT_PARITY_TOL = 1e-3
#: kernel_route_check: the int4 kernel vs an f32 dequantize-then-matmul on a
#: [4, 4096] probe.  Outputs are O(1); both sides accumulate 4096 f32
#: products, in different orders, so agreement is to f32 rounding.
KERNEL_ROUTE_TOL = 1e-3
#: decode kernel vs the masked-XLA paged reference, both f32 softmax over
#: the same int8 cache; outputs are O(1)
DECODE_ATTN_TOL = 1e-4
#: sharded vs single-device first QFT step, relative.  The forward runs in
#: bf16, and a row-parallel matmul rounds each chip's partial sum to bf16
#: before the all-reduce, so activations differ at bf16's 2^-8 relative
#: rounding (4 virtual CPU devices: ~9e-4 on loss and grad norm).  A
#: sharding fault — a missing all-reduce, a wrong split — is off by O(1).
SHARDED_STEP_RTOL = 1e-2


def require_tpu(devices) -> None:
    """Refuse to run (non-zero exit) unless JAX's first device is a TPU."""
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX's first device is "
                         f"{d.platform} ({d.device_kind})")


@contextlib.contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    print(f"[{name}] start", flush=True)
    yield
    print(f"[{name}] done in {time.perf_counter() - t0:.3f}s", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)
    print(f"  ok: {what}", flush=True)


def _peak_bytes(device) -> int:
    return device.memory_stats()["peak_bytes_in_use"]


# --------------------------------------------------------------- one chip

def quantize(pcfg):
    """run_pipeline end to end; checks finetune, export parity, kernel route."""
    from repro.pipeline import run_pipeline
    result = run_pipeline(pcfg, log=lambda s: print(f"  {s}", flush=True))
    ft = result.metrics["finetune"]
    print(f"  finetune loss {ft['first_loss']!r} -> {ft['final_loss']!r} "
          f"over {ft['steps']} steps")
    for h in result.history:
        print(f"    step {h['step']}: loss {h['loss']!r} at {h['t']!r}s")
    check(all(math.isfinite(h["loss"]) for h in result.history),
          "every QFT loss is finite")
    ev = result.metrics["evaluate"]
    err = ev["export_parity_max_err"]
    check(err <= EXPORT_PARITY_TOL,
          f"export_parity_max_err {err!r} <= {EXPORT_PARITY_TOL}")
    route = ev.get("kernel_route")
    print(f"  kernel_route_check: {route}")
    check(route is not None and route["pallas"],
          "kernel_route_check traced the Pallas quant_matmul (pallas: true)")
    check(route["max_err"] <= KERNEL_ROUTE_TOL,
          f"kernel_route max_err {route['max_err']!r} <= {KERNEL_ROUTE_TOL}")
    return result


def serve(cfg, plan, artifact, scfg, prompt_lens, new_tokens: int,
          seed: int = 0):
    """Engine.from_artifact on the kernel route; greedy-decodes a few
    requests and checks lengths, token range and the per-layer route."""
    import numpy as np
    from repro.serve.engine import Engine, Request
    cfg = dataclasses.replace(cfg, scan_layers=False, remat=False)
    engine = Engine.from_artifact(cfg, plan, artifact, scfg)
    rng = np.random.default_rng(seed)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, n).tolist(),
                    max_new_tokens=new_tokens, seed=seed + i)
            for i, n in enumerate(prompt_lens)]
    t0 = time.perf_counter()
    outs = engine.generate(reqs)
    print(f"  generate: {len(reqs)} requests, prompts {list(prompt_lens)}, "
          f"{new_tokens} new tokens each, {time.perf_counter() - t0:.3f}s "
          f"(compiles included)")
    stats = engine.stats()
    print(f"  Engine.stats(): {json.dumps(stats)}")
    check(all(len(o) == new_tokens for o in outs),
          f"every request emitted {new_tokens} tokens")
    check(all(0 <= t < cfg.vocab for o in outs for t in o),
          f"every token id is in [0, {cfg.vocab})")
    check(stats["decode_attn_ref_layers"] == 0
          and stats["decode_attn_pallas_layers"] == cfg.n_layers,
          f"all {cfg.n_layers} decode attention layers on the kernel route")
    return outs, stats


def decode_kernel_parity(S: int, T: int, Hkv: int, G: int, hd: int,
                         seed: int = 0) -> float:
    """The flash-decode kernel vs models/attention's masked-XLA paged
    reference on one int8 cache of the engine's shape."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.decode_attention import decode_attention
    from repro.models.attention import _paged_sdpa
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (S, 1, Hkv * G, hd), jnp.float32)
    k8 = jax.random.randint(ks[1], (S, T, Hkv, hd), -127, 128, jnp.int8)
    v8 = jax.random.randint(ks[2], (S, T, Hkv, hd), -127, 128, jnp.int8)
    k_scale = jax.random.uniform(ks[3], (S, Hkv), jnp.float32, 1e-3, 2e-2)
    v_scale = jax.random.uniform(ks[4], (S, Hkv), jnp.float32, 1e-3, 2e-2)
    # a length-1 slot, a mid-block one, a block-aligned one, a full one
    lengths = jnp.asarray([1, T // 3 + 7, T // 2, T][:S], jnp.int32)
    out = decode_attention(q[:, 0].reshape(S, Hkv, G, hd), k8, v8, lengths,
                           k_scale=k_scale, v_scale=v_scale)
    with jax.default_matmul_precision("highest"):
        ref = _paged_sdpa(q, k8, v8, lengths, k_scale, v_scale)
    err = float(jnp.max(jnp.abs(out.reshape(S, 1, Hkv * G, hd) - ref)))
    check(bool(jnp.all(jnp.isfinite(out))), "decode kernel output is finite")
    check(err <= DECODE_ATTN_TOL,
          f"decode kernel vs XLA reference max_err {err!r} <= "
          f"{DECODE_ATTN_TOL}")
    return err


def one_chip(devices) -> None:
    from repro.configs.registry import get_module
    from repro.pipeline import PipelineConfig
    from repro.serve.engine import ServeConfig
    m = get_module(ARCH)
    print(f"config: {ARCH} REDUCED, cut {m.reduced} of the published "
          f"config; stands for: {m.DEPLOYMENT}")
    pcfg = PipelineConfig(
        arch=ARCH, smoke=False, reduced=True, steps=3, use_pallas=True,
        calib_samples=64, calib_seq_len=128, calib_batch_size=8,
        calib_batches=2, eval_batches=1, log_every=1)
    with phase("quantize"):
        result = quantize(pcfg)
    print(f"  peak_bytes_in_use after quantize: {_peak_bytes(devices[0])}")
    cfg, plan, artifact = result.model_cfg, result.plan, result.artifact
    del result              # teacher and student go before the engine is built
    gc.collect()
    scfg = ServeConfig(max_slots=4, max_len=2048, prefill_chunk=256)
    with phase("serve"):
        serve(cfg, plan, artifact, scfg,
              prompt_lens=(256, 320, 448, 512, 384, 300), new_tokens=32)
    with phase("decode kernel parity"):
        decode_kernel_parity(S=scfg.max_slots, T=scfg.max_len,
                             Hkv=cfg.n_kv_heads_padded,
                             G=cfg.n_heads_padded // cfg.n_kv_heads_padded,
                             hd=cfg.head_dim)
    print(f"peak_bytes_in_use: {_peak_bytes(devices[0])}")


# ------------------------------------------------------------- four chips

def _calib(cfg, n: int, seq_len: int = 128, batch_size: int = 8):
    from repro.data.calib import CalibConfig, CalibDataset
    from repro.launch.train import calib_batches
    return calib_batches(CalibDataset(CalibConfig(
        n_samples=4 * batch_size, seq_len=seq_len, batch_size=batch_size,
        vocab=cfg.vocab)), n)


def _floats(metrics) -> dict:
    return {k: float(v) for k, v in metrics.items()}


def _sharded(cfg, mesh, batches):
    """launch/train's sharded trainer on ``mesh`` for batches shaped like
    ``batches``, with its ``PRNGKey`` state from the first two."""
    from repro.core import deployment_oriented
    from repro.launch.train import ShardedQFT, batch_like, random_state
    qft = ShardedQFT(cfg, deployment_oriented(), mesh, batch_like(batches[0]))
    return qft, random_state(qft, batches[:2])


def sharded_steps(cfg, mesh, steps: int):
    """``steps`` QFT steps of launch/train's sharded trainer on ``mesh``;
    returns the per-step metrics and the live state bytes per device."""
    import jax
    batches = _calib(cfg, 2 + steps)
    with jax.set_mesh(mesh):
        qft, (student, opt_state, teacher) = _sharded(cfg, mesh, batches)
        per_device: dict = {}
        for leaf in jax.tree.leaves((student, opt_state, teacher)):
            for shard in leaf.addressable_shards:
                d = shard.device.id
                per_device[d] = per_device.get(d, 0) + shard.data.nbytes
        metrics = []
        for b in batches[2:]:
            student, opt_state, m = qft.step(
                student, opt_state, teacher,
                jax.device_put(b, qft.batch_sharding))
            metrics.append(_floats(m))
    return metrics, per_device


def first_step_pair(cfg, mesh, device):
    """The first QFT step from one sharded init, run sharded on ``mesh`` and
    again on ``device`` alone from a host copy of the same state (both at
    the mesh's padded sizes).

    Both start from the same state on purpose: calibration takes activation
    maxima of a bf16 forward, whose rounding depends on how the matmuls are
    split, so two inits differ by a few tenths of a percent in some
    activation scales — a different start, not a sharding fault."""
    import jax
    from repro.launch.mesh import make_host_mesh
    batches = _calib(cfg, 3)
    with jax.set_mesh(mesh):
        qft, state = _sharded(cfg, mesh, batches)
        student, opt_state, teacher = state
        state = jax.device_get(state)
        sharded = _floats(qft.step(student, opt_state, teacher,
                                   jax.device_put(batches[2],
                                                  qft.batch_sharding))[2])
    del student, opt_state, teacher
    with jax.set_mesh(make_host_mesh()):
        state = jax.device_put(state, device)
        single = _floats(jax.jit(qft.trainer.train_step,
                                 donate_argnums=(0, 1))(*state, batches[2])[2])
    return sharded, single


def four_chips(devices) -> None:
    from repro.configs.registry import get_config
    from repro.launch.mesh import make_elastic_mesh
    check(len(devices) == 4, f"four devices present ({len(devices)})")
    mesh = make_elastic_mesh(4, model_parallel=4)
    print(f"mesh: {dict(mesh.shape)} over devices {[d.id for d in devices]}")

    deep = dataclasses.replace(get_config(ARCH), n_layers=4)
    print(f"deep config: {deep.n_layers} layers, vocab {deep.vocab}, "
          f"{deep.n_params()} params (~{deep.n_params() * 20 / 1e9:.1f} GB "
          f"of QFT state at 20 B/param)")
    with phase("sharded QFT, 4 layers, full vocabulary"):
        metrics, live = sharded_steps(deep, mesh, steps=3)
    for i, m in enumerate(metrics):
        print(f"  step {i}: {m}")
    check(all(math.isfinite(v) for m in metrics for v in m.values()),
          "every sharded loss and grad norm is finite")
    print(f"  live state bytes per device: {live}")
    print(f"  peak_bytes_in_use per device: "
          f"{ {d.id: _peak_bytes(d) for d in devices} }")
    total = sum(live.values())
    check(max(live.values()) < total / 2,
          f"state spread over the devices (largest share "
          f"{max(live.values())} of {total} bytes)")

    cut = get_config(ARCH, reduced=True)
    with phase("first step of the one-chip cut: 4 chips vs device 0"):
        sharded, single = first_step_pair(cut, mesh, devices[0])
    print(f"  4 chips:  {sharded}\n  device 0: {single}")
    for k in ("loss", "grad_norm"):
        rel = abs(sharded[k] - single[k]) / abs(single[k])
        check(rel <= SHARDED_STEP_RTOL,
              f"sharded {k} matches device 0 (rel diff {rel!r} <= "
              f"{SHARDED_STEP_RTOL})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded QFT path on four chips")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    devices = jax.devices()
    require_tpu(devices)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    print(f"devices: {len(devices)} x {devices[0].device_kind}; "
          f"jax {jax.__version__}")
    if args.four_chips:
        four_chips(devices)
    else:
        one_chip(devices)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
