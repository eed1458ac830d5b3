"""Paged int8 KV cache: page geometry, host page allocator, prefill buckets.

The serve engine stores decode KV in fixed-size **pages** drawn from one
shared per-layer pool instead of a monolithic ``[max_slots, max_len]``
preallocation.  Geometry:

- pools  ``k``/``v``: int8 ``[L, n_pages + 1, page, Hkv, hd]`` — one extra
  **trash page** (index ``n_pages``) at the end.  Unused page-table entries
  point at it, so the decode step's unconditional scatter write (every slot
  writes its current token, dead or alive) lands somewhere harmless without
  a branch in the jaxpr.
- page table ``pt``: int32 ``[max_slots, max_pages_per_slot]``, threaded
  through the forward like ``pos`` (shared across layers, excluded from the
  layer scan).
- scales ``k_scale``/``v_scale``: f32 ``[L, max_slots, Hkv]`` — per-layer,
  per-slot, per-kv-head.  Fitted by MMSE (PPQ) over the slot's prefill at
  install time, then frozen for the slot's lifetime; they ride the decode
  step as plain cache leaves, so the one-transfer invariant is untouched.

Pages are allocated **up front at admission** for the request's worst case
(``ceil((len(prompt) + max_new_tokens) / page)``): admission is the only
host decision point, so the decode step never needs to grow a slot, and
the one-transfer-per-step invariant holds trivially.

The same module owns the **prefill bucket menu** (powers of two up to the
configured chunk) shared by the engine and the static analyzer, so the
``trace.prefill-recompile`` budget is derived from the exact set of shapes
the engine can request.
"""
from __future__ import annotations

import dataclasses
import heapq
import math

import jax.numpy as jnp

from ..core.fakequant import quantize
from ..core.plan import KV_CACHE_FAMILIES as PAGED_KV_FAMILIES

#: families whose prefill tolerates right-padded chunks (causal attention
#: masks pad keys away from real queries).  SSM-family recurrences consume
#: every input frame into state, so they keep exact-length chunks — the
#: documented recompile-vs-correctness fallback.
BUCKETED_PREFILL_FAMILIES = ("dense", "moe", "vlm", "mla_moe")


@dataclasses.dataclass(frozen=True)
class KVSpec:
    """Resolved paged-KV geometry for one engine instance."""
    page_size: int            # tokens per page
    n_pages: int              # pool pages (excluding the trash page)
    max_pages_per_slot: int   # page-table width = ceil(max_len / page_size)
    kv_bits: int = 8          # only int8 is implemented

    @property
    def trash_page(self) -> int:
        """Write-sink page id: scatters through unused pt entries land here."""
        return self.n_pages

    @property
    def view_len(self) -> int:
        """Per-slot gathered KV length (``max_pages_per_slot * page_size``)."""
        return self.max_pages_per_slot * self.page_size

    def pages_for(self, n_tokens: int) -> int:
        return max(1, math.ceil(n_tokens / self.page_size))


def resolve_kv_spec(cfg, scfg, kv_bits: int = 8) -> KVSpec | None:
    """KVSpec for (model config, serve config), or None → monolithic cache.

    ``scfg.kv_pages == 0`` auto-sizes the pool to the capacity-equivalent
    default ``max_slots * ceil(max_len / page)`` — same worst-case token
    capacity as the monolithic layout, so paging alone never loses
    admission capacity; the win comes from int8 (2x vs bf16) and from
    requests that reserve fewer than ``max_pages_per_slot`` pages.
    """
    if scfg.kv_mode == "monolithic" or cfg.family not in PAGED_KV_FAMILIES:
        return None
    if scfg.kv_mode != "paged":
        raise ValueError(f"kv_mode must be 'paged' or 'monolithic', "
                         f"got {scfg.kv_mode!r}")
    if kv_bits == 0:
        return None
    if kv_bits != 8:
        raise ValueError(f"paged KV supports kv_bits=8 only, got {kv_bits}")
    page = int(scfg.kv_page_size)
    if page < 1:
        raise ValueError(f"kv_page_size must be >= 1, got {page}")
    per_slot = max(1, math.ceil(scfg.max_len / page))
    n_pages = int(scfg.kv_pages) or scfg.max_slots * per_slot
    return KVSpec(page_size=page, n_pages=n_pages,
                  max_pages_per_slot=per_slot, kv_bits=kv_bits)


def quantize_kv(x, scale):
    """Symmetric int8 encode of ``x`` by per-kv-head ``scale``.

    x: ``[..., Hkv, hd]`` float; scale: ``[..., Hkv]`` (broadcast over hd).
    Same grid as every other tensor class (core.fakequant, paper Eq. 1).
    """
    return quantize(x, scale[..., None], 8).astype(jnp.int8)


class PageAllocator:
    """Deterministic host-side free pool over the page ids.

    Mirrors the slot Scheduler's discipline: ``alloc`` hands out the lowest
    free page id first — allocation order is a pure function of the
    admission sequence, which keeps the conformance tier's bit-identical
    batch-composition checks meaningful.  ``free`` is a min-heap of the
    free ids and ``_is_free`` a flag per page, so ``alloc(n)`` and
    ``release(pages)`` cost O(pages · log n_pages) and never scan the free
    pool (the serve engine releases on every retire, at tens of thousands
    of free pages).
    """

    def __init__(self, n_pages: int):
        self.n_pages = int(n_pages)
        self.free = list(range(self.n_pages))     # ascending: a valid heap
        self._is_free = bytearray(b"\x01") * self.n_pages

    def can_alloc(self, n: int) -> bool:
        return n <= len(self.free)

    def alloc(self, n: int) -> list[int]:
        if not self.can_alloc(n):
            raise RuntimeError(f"page pool exhausted: want {n}, "
                               f"have {len(self.free)}")
        pages = [heapq.heappop(self.free) for _ in range(n)]
        for p in pages:
            self._is_free[p] = 0
        return pages

    def release(self, pages: list[int]) -> None:
        """Return ``pages`` to the pool; every id is checked before any is
        returned, so a refused release leaves the allocator unchanged."""
        seen = set()
        for p in pages:
            if not 0 <= p < self.n_pages:
                raise ValueError(f"page id {p} outside pool of "
                                 f"{self.n_pages}")
            if self._is_free[p] or p in seen:
                raise ValueError(f"double free of page {p}")
            seen.add(p)
        for p in pages:
            self._is_free[p] = 1
            heapq.heappush(self.free, p)

    @property
    def n_free(self) -> int:
        return len(self.free)


def prefill_buckets(chunk: int) -> tuple[int, ...]:
    """The fixed menu of prefill chunk lengths, ascending.

    Powers of two up to ``chunk`` plus ``chunk`` itself.  Every prompt
    piece is padded up to the smallest bucket that holds it, so the number
    of distinct prefill traces is ``len(prefill_buckets(chunk))`` no matter
    what prompt lengths arrive — that bound is what the analyzer's
    ``trace.prefill-recompile`` budget asserts.
    """
    chunk = max(1, int(chunk))
    menu = []
    b = 1
    while b < chunk:
        menu.append(b)
        b *= 2
    menu.append(chunk)
    return tuple(menu)


def bucket_for(n: int, chunk: int) -> int:
    """Smallest menu bucket holding ``n`` tokens (n must be ≤ chunk)."""
    for b in prefill_buckets(chunk):
        if n <= b:
            return b
    raise ValueError(f"chunk length {n} exceeds prefill_chunk {chunk}")
