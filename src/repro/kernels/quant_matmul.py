"""Pallas TPU kernel: W4 (int4-nibble-packed) integer-operand matmul.

The deployment hot-spot of a QFT-quantized model:  y = x @ (S_wL ⊙ Ŵ ⊙ S_wR)
with Ŵ stored packed (two int4 per byte) in HBM.  TPU adaptation of the
paper's recode stage (DESIGN.md §2): unpack happens in VMEM on MXU-aligned
tiles and the weight operand enters the dot *as int8* — it never materializes
as an f32 [bk, bn] tile, so the int4 memory win becomes a compute win too.

Scale hoisting (DESIGN.md "Decode-path kernel fusion"):

- ``s_wl`` (1/S_a of the input stream) is a row scale over K — it commutes
  with the contraction, so it is applied to the [bm, bk] **x-tile** (bm·bk
  multiplies) instead of the [bk, bn] weight tile (bk·bn multiplies, plus an
  f32 weight materialization).
- ``s_wr`` is constant within a K-group, so it hoists *out* of the dot
  entirely: the kernel keeps one int8-operand partial sum per group and
  applies the [n_groups, bn] scale to the [.., bm, bn] partials — the
  broadcast-to-[bk, bn] f32 dequant disappears.

One kernel body covers every layout (core.qconfig.QLayout): rank-1
(layerwise / channel) s_wr[N] is staged as a single "group" (the whole
K-tile is one group), group:g uses s_wr[K/g, N] with a [bk/g, bn] scale
tile per K-step.  Tiling constraint: ``bk % g == 0`` (a K-tile holds whole
groups) — callers (kernels.ops.pallas_tiles_ok) fall back to the XLA
reference otherwise.  Compiled for the TPU, a group must also be a whole
number of 128-lane vregs (g % 128 == 0); interpret mode takes any g.

``variant="dequant"`` keeps the original dequantize-then-f32-dot body; its
one use is the analyzer tests' planted f32-dequant fault.  Production always
wants the default ``"int8dot"``.

Tiling: grid (M/bm, N/bn, K/bk); x tile [bm, bk] and packed-weight tile
[bk/2, bn] are staged into VMEM per step; f32 accumulation in a VMEM scratch
tile [bm, bn] across the K grid dimension (revisiting pattern), written out
on the last K step.  bm/bn/bk default to 128/128/256 — MXU-aligned (128) and
a working set of ~0.3 MB ≪ 16 MB VMEM, leaving room for double-buffering.

``interpret=None`` auto-selects: the kernel body runs compiled on TPU and in
Pallas interpret mode elsewhere (CPU tests/dry-runs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: f32 operands stay f32 on the MXU (a TPU's default f32 dot rounds them to
#: bf16), so the kernel agrees with its f32 reference on the chip too
_HIGHEST = jax.lax.Precision.HIGHEST


def default_interpret() -> bool:
    """Pallas interpret mode unless we are actually on TPU."""
    return jax.default_backend() != "tpu"


def _unpack_tile(packed: jax.Array, unit: int) -> jax.Array:
    """uint8 [bk//2, bn] nibble pairs → int32 [bk, bn], rows unit-major.

    Packed row i holds weight rows 2i (low nibble) and 2i+1 (high nibble).
    Within each ``unit`` of weight rows (one K-group, or the whole tile) the
    even rows come first and the odd rows second; the wrapper permutes the
    x columns and s_wl the same way (``_unit_major``), so the dot is
    unchanged.  Nibbles are sign-extended with int32 shifts: TPU vector
    units have no 8-bit shifts, and a row interleave would need a sublane
    shuffle."""
    p = packed.astype(jnp.int32)
    lo = (p << 28) >> 28
    hi = (p << 24) >> 28
    h = unit // 2
    parts = []
    for j in range(packed.shape[0] // h):
        parts += [lo[j * h:(j + 1) * h], hi[j * h:(j + 1) * h]]
    return jnp.concatenate(parts, axis=0)


def _unit_major(a: jax.Array, unit: int) -> jax.Array:
    """Reorder the last (K) axis to the row order ``_unpack_tile`` emits:
    within each ``unit`` of K, even indices first, then odd ones."""
    K = a.shape[-1]
    lead = a.shape[:-1]
    a = a.reshape(*lead, K // unit, unit // 2, 2)
    return jnp.swapaxes(a, -1, -2).reshape(*lead, K)


def _qmm_int8_kernel(x_ref, qw_ref, swl_ref, swg_ref, o_ref, acc_ref, *,
                     n_k: int, unit: int):
    """One (m, n, k) grid step — integer weight operand, any layout.

    x_ref:   [bm, bk]        bf16/f32 activations tile (unit-major K order)
    qw_ref:  [bk//2, bn]     uint8 packed int4 weights tile
    swl_ref: [1, bk]         f32 left scale slice (1/S_a of the input
                             stream, unit-major K order)
    swg_ref: [1, n_bg, bn]   f32 right-scale tile, one row per K-group in
                             the tile (n_bg == 1 for layerwise/channel)
    o_ref:   [bm, bn]        output tile
    acc_ref: [bm, bn]        f32 VMEM accumulator scratch

    The weight tile stays int8 into the dot (mixed-precision dot_general with
    f32 accumulation — on MXU hardware the integer operand feeds the
    systolic array directly); s_wl rides on the x-tile; s_wr multiplies the
    per-group partial sums, never a [bk, bn] broadcast.
    """
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _unpack_tile(qw_ref[...], unit).astype(jnp.int8)      # [bk, bn]
    xs = x_ref[...].astype(jnp.float32) * swl_ref[...]        # [bm, bk]
    sg = swg_ref[0]                                           # [n_bg, bn]
    # one dot per K-group in this tile (a single dot for layerwise/channel),
    # each partial scaled by its group's row of s_wr
    for j in range(sg.shape[0]):
        p = jax.lax.dot_general(
            xs[:, j * unit:(j + 1) * unit], w[j * unit:(j + 1) * unit],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=_HIGHEST)
        acc_ref[...] += p * sg[j:j + 1]

    @pl.when(k_step == n_k - 1)
    def _out():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _qmm_dequant_kernel(x_ref, qw_ref, swl_ref, swg_ref, o_ref, acc_ref, *,
                        n_k: int, unit: int):
    """Baseline body (variant="dequant"): dequantize the weight tile to f32
    *before* the dot, block-broadcasting the [n_bg, bn] scale tile over each
    group's rows — the f32 materialization the int8dot kernel exists to
    remove.  Kept only so the micro-bench can quantify what the restructure
    buys; swl_ref here is the [bk, 1] column layout the f32 dequant wants."""
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _unpack_tile(qw_ref[...], unit).astype(jnp.float32)   # [bk, bn]
    sg = swg_ref[0]                                           # [n_bg, bn]
    sg = jnp.concatenate([jnp.broadcast_to(sg[j:j + 1], (unit, sg.shape[1]))
                          for j in range(sg.shape[0])], axis=0)
    w = w * swl_ref[...] * sg

    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HIGHEST)

    @pl.when(k_step == n_k - 1)
    def _out():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret", "variant"))
def quant_matmul(x: jax.Array, qw: jax.Array, s_wl: jax.Array,
                 s_wr: jax.Array, bm: int = 128, bn: int = 128, bk: int = 256,
                 interpret: bool | None = None,
                 variant: str = "int8dot") -> jax.Array:
    """y = x @ dequant(qw) for int4-packed qw.

    x: [M, K]; qw: [K//2, N] uint8; s_wl: [K] f32;
    s_wr: [N] f32 (layerwise/channel) or [K//g, N] f32 (group layout)
    → y [M, N].

    Shapes must tile evenly, and for group scales each K-tile must hold whole
    groups (``bk % g == 0``) — callers gate via kernels.ops.pallas_tiles_ok
    (production shapes are MXU-aligned by construction).
    interpret=None auto-selects by backend; True forces the CPU interpreter.
    ``variant``: "int8dot" (default — integer weight operand, hoisted scales)
    or "dequant" (the pre-fusion f32-dequant body, analyzer tests only).
    """
    if interpret is None:
        interpret = default_interpret()
    if variant not in ("int8dot", "dequant"):
        raise ValueError(f"unknown quant_matmul variant {variant!r}")
    M, K = x.shape
    Kh, N = qw.shape
    assert Kh * 2 == K, (K, Kh)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    n_k = K // bk
    grid = (M // bm, N // bn, n_k)

    if s_wr.ndim == 2:                        # group layout: [K//g, N]
        n_groups = s_wr.shape[0]
        assert K % n_groups == 0, (K, n_groups)
        unit = K // n_groups
        assert bk % unit == 0, (bk, unit)
        # [n_k, groups per tile, N]: the block's last two dims are then the
        # whole group axis and a lane-aligned bn — the TPU tiling rule
        swg_arg = s_wr.reshape(n_k, bk // unit, N)
        swg_spec = pl.BlockSpec((1, bk // unit, bn), lambda m, n, k: (k, 0, n))
    else:                                     # rank-1: one group per tile
        unit = bk
        swg_arg = s_wr[None, None, :]
        swg_spec = pl.BlockSpec((1, 1, bn), lambda m, n, k: (0, 0, n))
    x = _unit_major(x, unit)
    s_wl = _unit_major(s_wl, unit)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
        pl.BlockSpec((bk // 2, bn), lambda m, n, k: (k, n)),
    ]
    if variant == "int8dot":
        # s_wl staged as a [1, K] row → multiplies the x-tile in-kernel
        in_specs.append(pl.BlockSpec((1, bk), lambda m, n, k: (0, k)))
        swl_arg = s_wl[None, :]
        body = _qmm_int8_kernel
    else:                                     # "dequant" baseline
        # s_wl staged as a [K, 1] column → multiplies the f32 weight tile
        in_specs.append(pl.BlockSpec((bk, 1), lambda m, n, k: (k, 0)))
        swl_arg = s_wl[:, None]
        body = _qmm_dequant_kernel
    in_specs.append(swg_spec)
    kernel = functools.partial(body, n_k=n_k, unit=unit)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, qw, swl_arg, swg_arg)
