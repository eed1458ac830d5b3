"""The collective-bytes counter (sharding/collectives.py): bytes per device
of each kind of collective in a compiled SPMD program, loops counted once
per iteration."""
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

from repro.sharding.collectives import KINDS, collective_bytes, shape_bytes

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_a_column_row_parallel_pair_on_four_devices():
    """x [8, 64] @ w1 [64, 256] (columns split 4 ways) then @ w2 [256, 64]
    (rows split 4 ways): each device's partial product is summed by one
    all-reduce of the f32 [8, 64] result, 2,048 bytes; scanned over 3
    layers, three of them.  A subprocess: the device count is fixed when
    jax starts."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.sharding.collectives import collective_bytes
        mesh = jax.make_mesh((1, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)

        def arg(spec, *shape):
            return jax.ShapeDtypeStruct(shape, jnp.float32,
                                        sharding=NamedSharding(mesh, spec))

        def pair(x, w1, w2):
            return jnp.tanh(x @ w1) @ w2

        def scanned(x, w1s, w2s):
            return jax.lax.scan(lambda h, w: (pair(h, *w), None), x,
                                (w1s, w2s))[0]

        out = NamedSharding(mesh, P())
        one = jax.jit(pair, out_shardings=out).lower(
            arg(P(), 8, 64), arg(P(None, "model"), 64, 256),
            arg(P("model", None), 256, 64)).compile()
        three = jax.jit(scanned, out_shardings=out).lower(
            arg(P(), 8, 64), arg(P(None, None, "model"), 3, 64, 256),
            arg(P(None, "model", None), 3, 256, 64)).compile()
        print(collective_bytes(one.as_text()))
        print(collective_bytes(three.as_text()))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, cwd=ROOT,
                         env={**os.environ, "PYTHONPATH": str(ROOT / "src"),
                              "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    one, three = (eval(s) for s in out.stdout.strip().splitlines()[-2:])
    by_hand = 8 * 64 * 4
    assert one == {**dict.fromkeys(KINDS, 0), "all-reduce": by_hand}
    assert three == {**dict.fromkeys(KINDS, 0), "all-reduce": 3 * by_hand}


@pytest.mark.parametrize("shape,nbytes", [
    ("f32[2,2048,4096]{2,1,0}", 2 * 2048 * 4096 * 4),
    ("bf16[2,2048,4096]{2,1,0:T(8,128)(2,1)S(1)}", 2 * 2048 * 4096 * 2),
    ("(f32[4096]{0}, f32[1,128]{1,0}, f32[]{:T(128)})", 4 * (4096 + 128 + 1)),
    ("pred[]", 1),
])
def test_shape_bytes(shape, nbytes):
    assert shape_bytes(shape) == nbytes


HLO = """\
HloModule m

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

%cond (p: (s32[], bf16[8,128])) -> pred[] {
  %p = (s32[], bf16[8,128]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

%body (p: (s32[], bf16[8,128])) -> (s32[], bf16[8,128]) {
  %p = (s32[], bf16[8,128]) parameter(0)
  %x = bf16[8,128]{1,0} get-tuple-element(%p), index=1
  %ar = bf16[8,128]{1,0} all-reduce(%x), replica_groups=[1,4]<=[4], to_apply=%add
  %ags = (bf16[8,32]{1,0}, bf16[8,128]{1,0}) all-gather-start(%x), replica_groups={{0,1,2,3}}, dimensions={1}
  %agd = bf16[8,128]{1,0} all-gather-done(%ags)
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], bf16[8,128]) tuple(%i, %agd)
}

ENTRY %main (x: bf16[8,128]) -> bf16[8,128] {
  %x = bf16[8,128]{1,0} parameter(0)
  %zero = s32[] constant(2)
  %start = s32[] copy(%zero)
  %init = (s32[], bf16[8,128]) tuple(%start, %x)
  %w = (s32[], bf16[8,128]) while(%init), condition=%cond, body=%body
  %rs = f32[2,128]{1,0} reduce-scatter(%x), replica_groups=[1,4]<=[4], dimensions={0}, to_apply=%add
  %w2 = (s32[], bf16[8,128]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
  ROOT %y = bf16[8,128]{1,0} get-tuple-element(%w), index=1
}
"""


def test_loops_async_pairs_and_reduce_scatter_by_hand():
    """The first loop runs from 2 to 5 (3 times), the second 7 times (its
    known trip count); each iteration all-reduces and all-gathers a bf16
    [8, 128] (2,048 bytes; the gather counted once, at its ``-done``); the
    reduce-scatter consumes its result times the group of 4."""
    got = collective_bytes(HLO)
    assert got == {**dict.fromkeys(KINDS, 0),
                   "all-reduce": 10 * 2048, "all-gather": 10 * 2048,
                   "reduce-scatter": 2 * 128 * 4 * 4}
