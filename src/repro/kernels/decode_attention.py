"""Pallas TPU kernels: flash-decode over the serving KV cache.

The continuous-batching engine's per-step decode attention: every slot holds
one query token at its own sequence offset.  Two kernels, one per cache
layout; the masked-XLA paths in models/attention.py (`_sdpa`, `_paged_sdpa`)
are their oracles and fallbacks.

:func:`decode_attention` — the monolithic ``[slots, max_len]`` cache
(``kv_mode="monolithic"``), streamed in [bk]-sized KV blocks with an online
softmax:

- grid (slots, kv_heads, max_len/bk) — one program per slot × KV head ×
  KV block; the GQA query group [G, hd] for that head stays VMEM-resident
  across the KV grid dimension (m/l/acc scratch, the flash pattern of
  kernels/flash_attention.py);
- each slot's valid prefix length rides in as a [slots] int32 SMEM operand;
  the in-block mask is ``block_start + lane < length``;
- blocks entirely past a slot's length skip their *compute* via
  ``pl.when``, but not their DMA: the ``BlockSpec`` index map still copies
  every block into VMEM, so the kernel's time follows ``max_len``, not the
  live tokens (on a TPU v5e it costs ~0.3 µs a grid program, dead or live).

:func:`paged_decode_attention` — the paged int8 cache, which paged serving
routes to: it reads each live page of the pool in place through the page
table and never builds a per-slot view.  Its design is at the function.

Lengths must be >= 1 (the engine guarantees this: a decode step always
writes the current token at ``pos`` before attending, so the valid prefix
is ``pos + 1``); block 0 is therefore always live and l never ends at 0.

**Quantized KV** (int8 pages, or an int8 monolithic cache): per-slot
per-kv-head ``k_scale``/``v_scale`` (``[S, Hkv]``; the paged kernel takes
every layer's, ``[L, S, Hkv]``).  Dequantization is fused
into the flash math at no extra bandwidth: the K scale folds into the
[G, hd] query before the QK^T dot (exactly where the softmax 1/sqrt(hd)
already lives), and the V scale multiplies the accumulator once at output —
the int8 blocks feed both dots through a bare convert.  No dequantized cache
copy exists at any block size.
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


# --------------------------------------------------------------------------
# Paged flash-decode: the int8 page pool, read in place
# --------------------------------------------------------------------------

#: int8 bytes of K (and of V) that one block of the paged kernel copies into
#: VMEM: 16 pages of 16 tokens x 8 heads x 128 at Qwen3-8B's widths
_PAGE_BLOCK_BYTES = 256 * 1024


def _bf16_split(a: jax.Array) -> jax.Array:
    """f32 ``a`` [M, K] as its three-way bf16 split stacked on rows,
    [3M, K]: ``a == a1 + a2 + a3`` exactly (each piece carries the next 8
    of f32's 24 significant bits, and every remainder is exact in f32)."""
    a1 = a.astype(jnp.bfloat16)
    r = a - a1.astype(jnp.float32)
    a2 = r.astype(jnp.bfloat16)
    a3 = (r - a2.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([a1, a2, a3])


def _split_dot(a: jax.Array, b: jax.Array, dims) -> jax.Array:
    """f32 ``a`` times ``b`` of int8 values, in one bf16 MXU pass with f32
    accumulation.  ``b`` is exact in bf16 and each ``a_i * b`` exact in
    f32, so this sums the very products an f32 dot at ``HIGHEST`` sums —
    in another order — at a sixth of its passes."""
    m = a.shape[0]
    out = jax.lax.dot_general(_bf16_split(a), b.astype(jnp.bfloat16), dims,
                              preferred_element_type=jnp.float32)
    return out[:m] + out[m:2 * m] + out[2 * m:]


def paged_block_pages(page_size: int, n_kv_heads: int, head_dim: int) -> int:
    """Pages per block of :func:`paged_decode_attention`: as many as fill
    ``_PAGE_BLOCK_BYTES`` of int8 K, from the page's shape."""
    return max(1, _PAGE_BLOCK_BYTES // (page_size * n_kv_heads * head_dim))


def paged_decode_tiles_ok(page_size: int, n_kv_heads: int,
                          head_dim: int) -> bool:
    """Whether :func:`paged_decode_attention` can read this pool on a TPU.

    The kernel views a page ``[P, Hkv, hd]`` as its ``[P*Hkv, hd]`` slab.
    That view is free (a bitcast, no copy of the pool) only when the int8
    pool's (8, 128) HBM tiles hold whole rows of heads: ``hd`` a multiple of
    128 and ``Hkv`` of 8 (Qwen3-8B: 8 x 128; with 4 KV heads or
    ``hd`` 96 XLA would relayout the whole pool every step).  Each page's
    slab must also start on an int8 (32, 128) tile of the VMEM buffer:
    ``P*Hkv`` a multiple of 32.  Callers fall back to the gathered view and
    `_paged_sdpa` otherwise."""
    return (page_size >= 1 and head_dim % 128 == 0 and n_kv_heads % 8 == 0
            and (page_size * n_kv_heads) % 32 == 0)


def _paged_fd_kernel(layer_ref, len_ref, pt_ref, ks_ref, vs_ref, q_ref,
                     pool_k, pool_v, o_ref, kbuf, vbuf, sem, nxt_ref, *,
                     ppb: int, page_size: int, n_kv_heads: int, group: int,
                     n_pg: int, scale: float):
    """One slot: every live block of its pages, double-buffered.

    Scalar prefetch (SMEM): layer_ref [1], len_ref [S], pt_ref [S*n_pg].
    ks_ref, vs_ref: [L, S, Hkv] f32 dequant scales (SMEM).
    q_ref, o_ref:   [1, Hkv*G, hd] — the slot's queries, row h*G + g.
    pool_k/pool_v:  [L, n_pages+1, P*Hkv, hd] int8 in HBM (``pl.ANY``).
    kbuf/vbuf:      [2, ppb*P*Hkv, hd] int8 VMEM, one block per buffer.
    sem:            two DMA semaphores, one per buffer.
    nxt_ref:        [1] int32 SMEM — the buffer holding this slot's first
                    block, which the previous program already started.
    """
    s, n_slots = pl.program_id(0), pl.num_programs(0)
    P, Hkv, G = page_size, n_kv_heads, group
    rows = P * Hkv                       # slab rows of one page
    bk = ppb * P                         # tokens of one block
    layer = layer_ref[0]

    def length(slot):
        # rows past the page table are outside the view, as `_paged_sdpa`'s
        # mask has them; at least one page, so every block started is waited
        return jnp.clip(len_ref[slot], 1, n_pg * P)

    def block_copies(slot, j, b, op):
        """``op`` ("start" or "wait") the copies of block ``j`` of ``slot``
        into buffer ``b``: one per live page, none past the length."""
        n_live = (length(slot) + P - 1) // P - j * ppb

        def page(i, _):
            pg = pt_ref[slot * n_pg + j * ppb + i]
            dst = pl.ds(pl.multiple_of(i * rows, rows), rows)
            for pool, buf in ((pool_k, kbuf), (pool_v, vbuf)):
                getattr(pltpu.make_async_copy(pool.at[layer, pg],
                                              buf.at[b, dst], sem.at[b]),
                        op)()
            return _

        jax.lax.fori_loop(0, jnp.clip(n_live, 0, ppb), page, 0)

    def start(slot, j, b):
        block_copies(slot, j, b, "start")

    @pl.when(s == 0)
    def _prime():
        nxt_ref[0] = 0
        start(0, 0, 0)

    n_len = length(s)
    n_blocks = ((n_len + P - 1) // P + ppb - 1) // ppb
    HG = Hkv * G
    head = jnp.zeros((HG, 1), jnp.int32)          # KV head of each q row
    qrow = jax.lax.broadcasted_iota(jnp.int32, (HG, 1), 0)
    kcol = jnp.zeros((HG, 1), jnp.float32)
    vcol = jnp.zeros((HG, 1), jnp.float32)
    for h in range(Hkv):
        mine = (qrow >= h * G) & (qrow < (h + 1) * G)
        head = jnp.where(mine, h, head)
        kcol = jnp.where(mine, ks_ref[layer, s, h], kcol)
        vcol = jnp.where(mine, vs_ref[layer, s, h], vcol)
    q = q_ref[0].astype(jnp.float32) * (scale * kcol)       # [HG, hd]
    col = jax.lax.broadcasted_iota(jnp.int32, (HG, ppb * rows), 1)
    # slab row r holds token r // Hkv, head r % Hkv: every head's scores
    # come from one dense dot, and a q row keeps only its own head's rows
    own_head = jax.lax.rem(col, Hkv) == head

    def body(j, carry):
        b, m, l, acc = carry

        @pl.when(j + 1 < n_blocks)
        def _next_block():
            start(s, j + 1, 1 - b)

        @pl.when((j + 1 == n_blocks) & (s + 1 < n_slots))
        def _next_slot():
            start(s + 1, 0, 1 - b)

        block_copies(s, j, b, "wait")
        k = kbuf[b].astype(jnp.float32)                     # [R, hd]
        v = vbuf[b].astype(jnp.float32)
        sc = _split_dot(q, k, (((1,), (1,)), ((), ())))     # [HG, R]
        live = own_head & (col < (n_len - j * bk) * Hkv)
        sc = jnp.where(live, sc, _NEG)
        m_new = jnp.maximum(m, jnp.max(sc, -1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, -1, keepdims=True)
        acc = acc * alpha + _split_dot(p, v, (((1,), (0,)), ((), ())))
        return 1 - b, m_new, l, acc

    init = (nxt_ref[0], jnp.full((HG, 1), _NEG, jnp.float32),
            jnp.zeros((HG, 1), jnp.float32),
            jnp.zeros((HG, q.shape[1]), jnp.float32))
    b, _, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
    nxt_ref[0] = b                     # the next slot's first block is there
    o_ref[0] = (acc / jnp.maximum(l, 1e-20) * vcol).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q: jax.Array, pool_k: jax.Array,
                           pool_v: jax.Array, lengths: jax.Array,
                           pt: jax.Array, k_scale: jax.Array,
                           v_scale: jax.Array, layer: jax.Array | int = 0,
                           interpret: bool | None = None) -> jax.Array:
    """Paged flash-decode over the int8 page pool, read in place.

    q: [S, Hkv, G, hd] — one query token per slot, grouped kv-head-major;
    pool_k, pool_v: the layer-stacked int8 pools ``[L, n_pages+1, P, Hkv,
       hd]``, ``layer`` naming the layer read (handing over the stacks keeps
       XLA from copying a layer's slice out of them);
    lengths: [S] int32 — per-slot valid prefix (pos + 1, always >= 1);
    pt: [S, max_pages] int32 page table;
    k_scale, v_scale: [L, S, Hkv] f32 per-slot per-kv-head dequant scales
    → [S, Hkv, G, hd], `_paged_sdpa`'s math: the K scale and 1/sqrt(hd)
    fold into q, online softmax in f32, the V scale applies to the
    accumulator at the end, both dots f32-exact (`_split_dot`: the products
    of ``HIGHEST`` in one bf16 pass, since the int8 K and V are exact in
    bf16).

    - grid (S,): one program per slot, each covering all Hkv heads, walking
      the slot's live blocks of ``paged_block_pages`` pages; dead blocks
      and pages past the length issue no DMA and no compute;
    - the pool stays in HBM; each live page's ``[P*Hkv, hd]`` slab is one
      async copy into a VMEM block buffer, double-buffered: the next block,
      or the next slot's first, loads while this one computes;
    - the page table, lengths and layer ride in SMEM (scalar prefetch),
      as do the scales.

    ``paged_decode_tiles_ok(P, Hkv, hd)`` must hold on a TPU; interpret=None
    auto-selects by backend (models/attention.py gates the call).
    """
    L, n_pool, P, Hkv, hd = pool_k.shape
    S, _, G, _ = q.shape
    n_pg = pt.shape[1]
    ppb = min(paged_block_pages(P, Hkv, hd), n_pg)
    rows = P * Hkv
    slab = (L, n_pool, rows, hd)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    qspec = pl.BlockSpec((1, Hkv * G, hd), lambda s, *_: (s, 0, 0))
    out = pl.pallas_call(
        functools.partial(_paged_fd_kernel, ppb=ppb, page_size=P,
                          n_kv_heads=Hkv, group=G, n_pg=n_pg,
                          scale=hd ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[smem, smem, qspec, hbm, hbm],
            out_specs=qspec,
            scratch_shapes=[pltpu.VMEM((2, ppb * rows, hd), jnp.int8),
                            pltpu.VMEM((2, ppb * rows, hd), jnp.int8),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((S, Hkv * G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret if interpret is not None else default_interpret(),
        name="paged_decode_attention",
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      lengths.astype(jnp.int32), pt.reshape(-1).astype(jnp.int32),
      k_scale.astype(jnp.float32), v_scale.astype(jnp.float32),
      q.reshape(S, Hkv * G, hd), pool_k.reshape(slab), pool_v.reshape(slab))
    return out.reshape(S, Hkv, G, hd)
