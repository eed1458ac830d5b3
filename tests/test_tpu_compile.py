"""The main-path Pallas kernels compile for a TPU v5e, without a chip.

Interpret mode (every other kernel test) checks the math but not the Mosaic
compiler's layout rules — 8-bit vector shifts, (8, 128)-aligned blocks — so
each kernel is also lowered and compiled here for a described v5e topology
at Qwen3-8B's widths (d_model 4096, d_ff 12288, 8 KV heads, head_dim 128).
The topology is described inside a fixture, never at import: where libtpu
cannot describe one, the compile tests skip.

Also: ``chip_smoke.py`` refuses to run on a CPU backend.
"""
from __future__ import annotations

import importlib.util
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (decode_attention,
                                            paged_decode_attention)
from repro.kernels.quant_matmul import quant_matmul

ROOT = pathlib.Path(__file__).resolve().parents[1]
D, FF, HKV, G, HD = 4096, 12288, 8, 4, 128


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 topology (no chip needed)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no libtpu log files
    try:
        from jax.experimental import topologies
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:             # no libtpu, or it cannot describe one
        pytest.skip(f"cannot describe a v5e topology: {e}")


@pytest.fixture(scope="module")
def v5e(topo):
    """One device of the described v5e:2x2."""
    return SingleDeviceSharding(topo.devices[0])


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("layout", ["channel", "group:128"])
@pytest.mark.parametrize("M,K,N,dtype", [
    (128, D, FF, jnp.bfloat16),      # prefill tile, gate/up projection
    (4, FF, D, jnp.float32),         # decode batch, down projection
], ids=["prefill-up", "decode-down"])
def test_quant_matmul_compiles_for_v5e(v5e, layout, M, K, N, dtype):
    s_wr = (N,) if layout == "channel" else (K // 128, N)
    c = _compile(v5e, lambda x, q, a, b: quant_matmul(x, q, a, b,
                                                      interpret=False),
                 ((M, K), dtype), ((K // 2, N), jnp.uint8),
                 ((K,), jnp.float32), (s_wr, jnp.float32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("cache", ["bf16", "int8"])
def test_decode_attention_compiles_for_v5e(v5e, cache):
    S, T = 8, 4096
    kv = jnp.bfloat16 if cache == "bf16" else jnp.int8
    shapes = [((S, HKV, G, HD), jnp.bfloat16), ((S, T, HKV, HD), kv),
              ((S, T, HKV, HD), kv), ((S,), jnp.int32)]
    if cache == "int8":
        shapes += [((S, HKV), jnp.float32)] * 2
    c = _compile(v5e, lambda q, k, v, n, *sc: decode_attention(
        q, k, v, n, *sc, interpret=False), *shapes)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("S", [64, 128], ids=["chat", "batch"])
def test_paged_decode_attention_compiles_for_v5e(v5e, S):
    """The serving cells' shapes: S slots of max_len 4,096 in pages of 16,
    a two-layer stacked int8 pool and scales.  Its VMEM blocks and the page
    table in SMEM fit the chip, and the pool reaches the kernel as a
    bitcast, not a copy."""
    n_pg, P, L = 4096 // 16, 16, 2
    pool = ((L, S * n_pg + 1, P, HKV, HD), jnp.int8)
    c = _compile(v5e, lambda q, k, v, n, pt, ks, vs: paged_decode_attention(
        q, k, v, n, pt, ks, vs, layer=1, interpret=False),
        ((S, HKV, G, HD), jnp.bfloat16), pool, pool, ((S,), jnp.int32),
        ((S, n_pg), jnp.int32), ((L, S, HKV), jnp.float32),
        ((L, S, HKV), jnp.float32))
    text = c.as_text()
    assert "tpu_custom_call" in text
    entry = text[text.index("\nENTRY"):]
    pool_ops = re.findall(rf"= s8\[{L},{S * n_pg + 1},[\d,]*\]\{{[^}}]*\}} "
                          r"([\w-]+)\(", entry)
    assert set(pool_ops) == {"parameter", "bitcast"}, pool_ops


def test_sharded_qft_step_fits_four_v5e_chips(topo):
    """The step of the four-chip cell ``qwen3-8b-tp4.qft`` (4 layers, the
    151,936-row vocabulary padded to 152,064, 2 x 2,048 tokens, W4A8),
    built by ``launch/train.ShardedQFT`` on a described v5e:2x2 mesh, data
    1 x model 4: it compiles, fits a chip's 16 GB, and its all-reduces are
    counted (one [2, 2,048, 4,096] bf16 activation each)."""
    import dataclasses
    import numpy as np
    from jax.sharding import AxisType, Mesh
    from repro.configs import get_config
    from repro.core import deployment_oriented
    from repro.launch.train import ShardedQFT
    from repro.models import set_runtime
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    cfg = dataclasses.replace(get_config("qwen3-8b"), n_layers=4)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 2048), jnp.int32)}
    try:
        qft = ShardedQFT(cfg, deployment_oriented(), mesh, batch)
    finally:
        set_runtime(act_spec=None)     # the builder pins it process-wide
    assert qft.cfg.vocab_padded == 152064
    m = qft.compiled.memory_analysis()
    per_device = (m.argument_size_in_bytes + m.output_size_in_bytes
                  - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert per_device < 16e9, per_device
    # per layer at least: the teacher's and the student's forward, its
    # remat and its backward, two row-parallel sums each
    activation = 2 * 2048 * 4096 * 2
    assert qft.collective_bytes["all-reduce"] >= 8 * 4 * activation


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_device_check_refuses_cpu():
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as e:
        _chip_smoke().require_tpu(jax.devices())
    assert e.value.code != 0 and "needs a TPU" in str(e.value.code)


def test_chip_smoke_script_exits_nonzero_on_cpu():
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, timeout=120,
                         cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0, out.stdout
    assert "needs a TPU" in out.stderr
    assert '"ok"' not in out.stdout, out.stdout
