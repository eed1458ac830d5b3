"""Pallas TPU kernel: slot-masked flash-decode over the serving KV cache.

The continuous-batching engine's per-step decode attention: every slot holds
one query token at its own sequence offset, and the naive XLA path
(models/attention.py `_sdpa` vector-pos branch) materializes logits and a
mask over the ENTIRE [max_slots, max_len] cache every step.  This kernel
streams the cache in [bk]-sized KV blocks with an online softmax instead:

- grid (slots, kv_heads, max_len/bk) — one program per slot × KV head ×
  KV block; the GQA query group [G, hd] for that head stays VMEM-resident
  across the KV grid dimension (m/l/acc scratch, the flash pattern of
  kernels/flash_attention.py);
- each slot's valid prefix length rides in as a [slots] int32 SMEM operand;
  the in-block mask is ``block_start + lane < length``;
- blocks entirely past a slot's length are *skipped* via ``pl.when`` — a
  slot at pos 17 touches one block of a 4096-deep cache instead of all 32.

Lengths must be >= 1 (the engine guarantees this: a decode step always
writes the current token at ``pos`` before attending, so the valid prefix
is ``pos + 1``); block 0 is therefore always live and l never ends at 0.

**Quantized KV** (the paged int8 cache): pass per-slot per-kv-head
``k_scale``/``v_scale`` ``[S, Hkv]`` and int8 ``k``/``v``.  Dequantization
is fused into the existing flash math at no extra bandwidth: the K scale is
a scalar per (slot, head) program, so it folds into the [G, hd] query
before the QK^T dot (exactly where the softmax 1/sqrt(hd) already lives),
and the V scale multiplies the [G, hd] accumulator once at output — the
int8 blocks feed both dots through the same ``astype(f32)`` the bf16 path
uses.  No dequantized cache copy exists at any block size.

Decode is memory-bound (every step re-reads the whole live KV), so skipped
blocks translate ~linearly into decode latency on real hardware; in
interpret mode (CPU tests) the win shows up as deterministic work units in
benchmarks/BENCH_kernels.json.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quant_matmul import default_interpret

_NEG = -1e30
#: f32 operands stay f32 on the MXU (a TPU's default f32 dot rounds them to
#: bf16), so the kernel agrees with its f32 reference on the chip too
_HIGHEST = jax.lax.Precision.HIGHEST


def decode_tiles_ok(max_len: int, bk: int = 128) -> bool:
    """The decode kernel streams the cache in whole [bk] blocks: max_len must
    tile evenly by the (clamped) block size.  Callers fall back to the
    masked-XLA `_sdpa` path otherwise."""
    if max_len < 1:
        return False
    bk = min(bk, max_len)
    return max_len % bk == 0


def _fd_kernel(len_ref, *refs, bk: int, n_k: int, scale: float,
               quantized: bool):
    """One (slot, kv_head, kv_block) grid step.

    len_ref: [S]           int32 valid-prefix lengths, in SMEM (>= 1)
    quantized → two [S, Hkv] f32 SMEM refs follow: the K and V dequant
    scales, read at this program's (slot, head).
    q_ref:   [1, 1, G, hd] the slot's query group for this KV head
    k_ref:   [1, bk, hd]   this head's columns of the [S, T, Hkv*hd] cache
    v_ref:   [1, bk, hd]
    o_ref:   [1, 1, G, hd]
    m/l/acc: [G, 1] / [G, 1] / [G, hd] f32 VMEM online-softmax state
    """
    if quantized:
        ks_ref, vs_ref, *refs = refs
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    s_id, h_id, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[s_id]

    def _block():
        qscale = scale if not quantized else scale * ks_ref[s_id, h_id]
        q = q_ref[0, 0].astype(jnp.float32) * qscale      # [G, hd]
        k = k_ref[0].astype(jnp.float32)                  # [bk, hd]
        v = v_ref[0].astype(jnp.float32)                  # [bk, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_HIGHEST)               # [G, bk]
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, _NEG)             # per-slot prefix
        m_prev = m_ref[...]                               # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_HIGHEST)
        m_ref[...] = m_new

    # fully-dead blocks (entirely past this slot's length) are skipped —
    # the memory-bound win: work scales with the slot's live prefix, not
    # with max_len
    pl.when(j * bk < length)(_block)

    @pl.when(j == n_k - 1)
    def _out():
        acc = acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
        if quantized:
            acc = acc * vs_ref[s_id, h_id]
        o_ref[0, 0] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, k_scale: jax.Array | None = None,
                     v_scale: jax.Array | None = None, bk: int = 128,
                     interpret: bool | None = None) -> jax.Array:
    """Slot-masked flash-decode.

    q: [S, Hkv, G, hd] — one query token per slot, grouped kv-head-major
       (head h == kv*G + g, exactly `_sdpa`'s GQA grouping);
    k, v: [S, T, Hkv, hd] — the slot-indexed KV cache (T == max_len), bf16
       or — with scales — int8;
    lengths: [S] int32 — per-slot valid prefix (pos + 1, always >= 1);
    k_scale, v_scale: optional [S, Hkv] f32 — per-slot per-kv-head dequant
       scales for an int8 cache (both or neither)
    → [S, Hkv, G, hd].

    The cache is viewed as [S, T, Hkv*hd] (a free reshape), so one head's
    KV block is a [bk, hd] tile with hd lane-aligned; lengths and scales
    live in SMEM, read as scalars per program.

    ``decode_tiles_ok(T, bk)`` must hold; interpret=None auto-selects by
    backend (models/attention.py gates the call and falls back to the
    masked-XLA `_sdpa` / `_paged_sdpa` path otherwise).
    """
    S, Hkv, G, hd = q.shape
    T = k.shape[1]
    bk = min(bk, T)
    assert T % bk == 0, (T, bk)
    quantized = k_scale is not None
    assert (k_scale is None) == (v_scale is None)
    n_k = T // bk
    grid = (S, Hkv, n_k)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [smem]
    operands = [lengths.astype(jnp.int32)]
    if quantized:
        in_specs += [smem, smem]
        operands += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    in_specs += [
        pl.BlockSpec((1, 1, G, hd), lambda s, h, j: (s, h, 0, 0)),
        pl.BlockSpec((1, bk, hd), lambda s, h, j: (s, j, h)),
        pl.BlockSpec((1, bk, hd), lambda s, h, j: (s, j, h)),
    ]
    operands += [q, k.reshape(S, T, -1), v.reshape(S, T, -1)]
    return pl.pallas_call(
        functools.partial(_fd_kernel, bk=bk, n_k=n_k, scale=hd ** -0.5,
                          quantized=quantized),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda s, h, j: (s, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((S, Hkv, G, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, hd), jnp.float32)],
        interpret=interpret if interpret is not None else default_interpret(),
    )(*operands)
