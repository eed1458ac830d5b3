"""PR 10 conformance tier: quantized int8 KV cache + paged slot memory.

The contract, layered from structure up to behavior:

- **Page bookkeeping** (no jax): the PageAllocator hands out lowest-id
  pages deterministically, in exactly the order of a plain sorted-list
  reference, refuses double frees and foreign ids without changing any
  state, and a hypothesis property drives random alloc/release
  interleavings against the conservation invariant (free + held == pool,
  no aliasing).
- **Numerics**: the fused-scale decode attention (scales folded into q
  pre-dot / context post-dot, int8 operands in the dots) matches the
  dequantize-first f32 oracle to float tolerance, on both the XLA
  reference path and the Pallas flash-decode kernel (interpret mode), and
  the two paths match each other.
- **Engine conformance**: paged-engine tokens are bit-identical solo vs
  static batch vs interleaved arrival (the repo's headline invariance,
  re-proved over the paged cache with page reuse in the mix); the FIRST
  emitted token of every request matches the monolithic f32 engine
  exactly (it is drawn from the f32 prefill logits in both layouts);
  eviction returns every page (stats-visible), freed pages go out again
  lowest first into the slots' page-table rows, and admission is gated by
  free pages, not just free slots; a paged int8 pool that holds no more
  bytes than a monolithic one runs strictly more slots at once.
- **Bugfix satellites**: bucketed pad-and-mask prefill ≡ exact-length
  prefill; Engine construction refuses an MoE capacity_factor that could
  silently drop decode tokens; SMOKE configs re-derive their padded
  fields instead of inheriting full-size padding.
- **Analyzer**: the trace.kv-* rules catch a plan/cache precision
  mismatch and the prefill budget equals the bucket menu.
"""
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import permissive
from repro.core.mmse import ppq_scale
from repro.kernels.decode_attention import decode_attention
from repro.models import ModelConfig, init_model
from repro.models.config import MoEConfig
from repro.serve.deploy import make_deploy_plan
from repro.serve.engine import Engine, Request, ServeConfig
from repro.serve.kv_cache import (KVSpec, PageAllocator, bucket_for,
                                  prefill_buckets, quantize_kv,
                                  resolve_kv_spec)

CONFIGS = {
    "dense": ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                         n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                         head_dim=8, scan_layers=False, remat=False),
    "moe": ModelConfig(name="m", family="moe", n_layers=1, d_model=32,
                       n_heads=4, n_kv_heads=4, d_ff=0, vocab=64, head_dim=8,
                       scan_layers=False, remat=False,
                       moe=MoEConfig(n_experts=4, top_k=2, n_shared=1,
                                     d_ff_expert=32, capacity_factor=8.0)),
}

REQS = [Request(prompt=[1, 2, 3], max_new_tokens=5),
        Request(prompt=[7, 8], max_new_tokens=3),
        Request(prompt=list(range(1, 12)), max_new_tokens=4),
        Request(prompt=[5, 4, 3, 2, 1], max_new_tokens=6),
        Request(prompt=[9, 9], max_new_tokens=2, eos_id=0)]


@functools.lru_cache(maxsize=None)
def engine_for(family: str, kv_mode: str = "paged",
               max_slots: int = 3) -> Engine:
    cfg = CONFIGS[family]
    params = init_model(jax.random.PRNGKey(0), cfg, permissive())
    return Engine(cfg, permissive(), params,
                  ServeConfig(max_slots=max_slots, max_len=64,
                              prefill_chunk=8, kv_mode=kv_mode,
                              kv_page_size=16))


# ---------------------------------------------------------------------------
# Page-table bookkeeping (pure host code)
# ---------------------------------------------------------------------------

def test_page_allocator_deterministic_lowest_first():
    pa = PageAllocator(6)
    assert pa.alloc(3) == [0, 1, 2]
    assert pa.alloc(1) == [3]
    pa.release([1])
    # freed page is reissued before untouched higher ids
    assert pa.alloc(2) == [1, 4]
    assert pa.n_free == 1 and pa.can_alloc(1) and not pa.can_alloc(2)


def test_page_allocator_refuses_bad_releases():
    pa = PageAllocator(4)
    held = pa.alloc(2)
    pa.release(held)
    with pytest.raises(ValueError, match="double free"):
        pa.release([held[0]])
    with pytest.raises(ValueError, match="outside pool"):
        pa.release([99])
    with pytest.raises(RuntimeError, match="exhausted"):
        pa.alloc(5)


class _SortedListPool:
    """Plain reference allocator: a sorted list, lowest id handed out first."""

    def __init__(self, n_pages: int):
        self.free = list(range(n_pages))

    def alloc(self, n: int) -> list[int]:
        pages, self.free = self.free[:n], self.free[n:]
        return pages

    def release(self, pages: list[int]) -> None:
        self.free = sorted(self.free + list(pages))


@pytest.mark.parametrize("seed", range(4))
def test_page_allocator_order_matches_sorted_list_reference(seed):
    """Random alloc/release sequences hand out exactly the pages the plain
    sorted-list reference hands out, call for call."""
    rng = np.random.RandomState(seed)
    n_pages = int(rng.randint(1, 200))
    pa, ref = PageAllocator(n_pages), _SortedListPool(n_pages)
    held: list[list[int]] = []
    for _ in range(400):
        if held and rng.rand() < 0.45:
            pages = held.pop(int(rng.randint(len(held))))
            pa.release(pages)
            ref.release(pages)
        else:
            want = int(rng.randint(1, max(2, n_pages // 4)))
            if pa.can_alloc(want):
                pages = pa.alloc(want)
                assert pages == ref.alloc(want)
                held.append(pages)
        assert pa.n_free == len(ref.free)
        assert sorted(pa.free) == ref.free


@pytest.mark.parametrize("bad, match", [
    ([0, 0], "double free"),            # a held page twice
    ([0, 2, 2], "double free"),         # duplicate after a valid page
    ([0, 5], "double free"),            # a page that is already free
    ([0, 8], "outside pool"),           # past the end of the pool
    ([0, -1], "outside pool"),          # negative id
])
def test_page_allocator_failed_release_changes_nothing(bad, match):
    """A refused release mutates nothing: the held pages before the bad id
    stay held, ``n_free`` is unchanged, and the next alloc is unchanged."""
    pa = PageAllocator(8)
    assert pa.alloc(5) == [0, 1, 2, 3, 4]
    pa.release([1, 3])                                  # free: 1 3 5 6 7
    with pytest.raises(ValueError, match=match):
        pa.release(bad)
    assert pa.n_free == 5
    assert pa.alloc(5) == [1, 3, 5, 6, 7]
    pa.release([0, 2])            # still held, so they release cleanly
    assert pa.alloc(2) == [0, 2]


try:                     # optional dev dependency — only these tests skip
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

    def given(**kw):
        return lambda f: pytest.mark.skip(
            reason="optional dev dependency (pip install .[dev])")(f)

    def settings(**kw):
        return lambda f: f

    class st:            # noqa: N801 — mirrors the hypothesis module name
        @staticmethod
        def data():
            return None


@settings(max_examples=50, deadline=None)
@given(data=st.data())
def test_page_allocator_conservation_property(data):
    """Random alloc/release interleavings: pages are conserved, never
    aliased across holders, and every release makes them reusable."""
    n_pages = data.draw(st.integers(min_value=1, max_value=12))
    pa = PageAllocator(n_pages)
    held: list[list[int]] = []
    for _ in range(data.draw(st.integers(min_value=1, max_value=30))):
        if held and data.draw(st.booleans()):
            pa.release(held.pop(data.draw(
                st.integers(min_value=0, max_value=len(held) - 1))))
        else:
            want = data.draw(st.integers(min_value=1, max_value=n_pages))
            if pa.can_alloc(want):
                held.append(pa.alloc(want))
        flat = [p for h in held for p in h]
        assert len(flat) == len(set(flat))              # no aliasing
        assert pa.n_free + len(flat) == n_pages         # conservation
        assert not (set(flat) & set(pa.free))           # held ∩ free = ∅


def test_resolve_kv_spec_geometry():
    scfg = ServeConfig(max_slots=3, max_len=64, prefill_chunk=8,
                       kv_page_size=16)
    kv = resolve_kv_spec(CONFIGS["dense"], scfg)
    assert kv == KVSpec(page_size=16, n_pages=12, max_pages_per_slot=4)
    assert kv.trash_page == 12 and kv.view_len == 64
    assert kv.pages_for(1) == 1 and kv.pages_for(17) == 2
    # monolithic mode / non-KV families / kv_bits=0 all opt out
    assert resolve_kv_spec(CONFIGS["dense"], ServeConfig(
        max_slots=3, max_len=64, kv_mode="monolithic")) is None
    assert resolve_kv_spec(CONFIGS["dense"], scfg, kv_bits=0) is None


def test_prefill_bucket_menu():
    assert prefill_buckets(8) == (1, 2, 4, 8)
    assert prefill_buckets(12) == (1, 2, 4, 8, 12)
    assert [bucket_for(n, 8) for n in (1, 2, 3, 5, 8)] == [1, 2, 4, 8, 8]
    with pytest.raises(ValueError, match="exceeds prefill_chunk"):
        bucket_for(9, 8)


# ---------------------------------------------------------------------------
# Numerics: fused-scale attention vs dequantize-first f32 oracle
# ---------------------------------------------------------------------------

def _quantized_kv_case(seed: int = 0):
    S, T, H, Hkv, hd = 3, 32, 4, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (S, 1, H, hd), jnp.float32)
    kf = jax.random.normal(ks[1], (S, T, Hkv, hd), jnp.float32)
    vf = jax.random.normal(ks[2], (S, T, Hkv, hd), jnp.float32)
    lengths = jnp.asarray([5, 17, 32], jnp.int32)
    # per-slot per-kv-head MMSE scales, the install-time fit
    k_scale = ppq_scale(kf, 8, axes=(1, 3))[:, 0, :, 0]
    v_scale = ppq_scale(vf, 8, axes=(1, 3))[:, 0, :, 0]
    k8 = quantize_kv(kf, k_scale[:, None, :])
    v8 = quantize_kv(vf, v_scale[:, None, :])
    return q, k8, v8, lengths, k_scale, v_scale


def test_fused_scale_attention_matches_dequant_oracle():
    q, k8, v8, lengths, k_scale, v_scale = _quantized_kv_case()
    kf = k8.astype(jnp.float32) * k_scale[:, None, :, None]
    vf = v8.astype(jnp.float32) * v_scale[:, None, :, None]
    oracle = decode_attention(q, kf, vf, lengths)
    fused = decode_attention(q, k8, v8, lengths,
                             k_scale=k_scale, v_scale=v_scale)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)


def test_kernel_and_reference_agree_on_quantized_kv():
    """The Pallas flash-decode kernel (interpret mode) and the masked-XLA
    reference must agree on the SAME int8 inputs — the kernel's in-body
    scale folding is the same math as the reference's."""
    q, k8, v8, lengths, k_scale, v_scale = _quantized_kv_case(seed=3)
    ref = decode_attention(q, k8, v8, lengths,
                           k_scale=k_scale, v_scale=v_scale)
    kern = decode_attention(q, k8, v8, lengths, k_scale=k_scale,
                            v_scale=v_scale, bk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Engine conformance over the paged cache
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", sorted(CONFIGS))
def test_paged_batch_composition_invariance(family):
    """Solo ≡ static ≡ interleaved, bit-exact, on the paged engine — with
    5 requests over 3 slots the run exercises eviction, slot refill and
    page reuse mid-stream."""
    engine = engine_for(family)
    assert engine.stats()["kv_page_size"] == 16      # actually paged
    ref = []
    for r in REQS:
        engine.reset()
        ref.append(engine.generate([r])[0])

    engine.reset()
    assert engine.generate(REQS) == ref

    rng = np.random.RandomState(13)
    order = rng.permutation(len(REQS))
    engine.reset()
    rid_of, collected = {}, {}
    for j in order:
        rid_of[j] = engine.submit(REQS[j])
        for _ in range(int(rng.randint(0, 3))):
            if engine.pending():
                collected.update(engine.step())
    while engine.pending():
        collected.update(engine.step())
    assert [collected[rid_of[j]] for j in range(len(REQS))] == ref


@pytest.mark.parametrize("family", sorted(CONFIGS))
def test_first_token_matches_f32_oracle(family):
    """The first emitted token is drawn from the f32 prefill logits in
    BOTH layouts (install-time quantization happens after the draw), so it
    must match the monolithic engine exactly; later tokens may diverge
    within int8 tolerance and are covered by the numerics tests above."""
    paged, mono = engine_for(family), engine_for(family, "monolithic")
    for r in REQS:
        paged.reset()
        mono.reset()
        assert paged.generate([r])[0][0] == mono.generate([r])[0][0]


def test_eviction_returns_pages_and_stats_report_occupancy():
    engine = engine_for("dense")
    engine.reset()
    s0 = engine.stats()
    assert s0["kv_pages_total"] == 12 and s0["kv_pages_free"] == 12
    rid = engine.submit(Request(prompt=[1, 2, 3], max_new_tokens=20))
    collected = engine.step()
    s1 = engine.stats()
    # ceil((3 + 20) / 16) = 2 pages reserved up front at admission
    assert s1["kv_pages_free"] == 10 and s1["slots_active"] == 1
    while engine.pending():
        collected.update(engine.step())
    assert len(collected[rid]) == 20
    s2 = engine.stats()
    assert s2["kv_pages_free"] == 12        # eviction returned every page
    assert s2["max_concurrent_slots" if "max_concurrent_slots" in s2
              else "peak_slots_active"] >= 1
    assert s2["slot_cache_bytes"] < engine_for(
        "dense", "monolithic").stats()["slot_cache_bytes"]


def test_admission_gated_by_free_pages_not_just_slots():
    """A pool smaller than the slot count admits by pages: requests queue
    until pages free up, and every stream still completes correctly."""
    cfg = CONFIGS["dense"]
    params = init_model(jax.random.PRNGKey(0), cfg, permissive())
    # 3 slots but only 2 pages: long requests serialize on the pool
    engine = Engine(cfg, permissive(), params,
                    ServeConfig(max_slots=3, max_len=64, prefill_chunk=8,
                                kv_mode="paged", kv_page_size=16,
                                kv_pages=2))
    reqs = [Request(prompt=[1, 2, 3], max_new_tokens=14),   # 2 pages
            Request(prompt=[7, 8], max_new_tokens=14),      # 2 pages
            Request(prompt=[5, 4], max_new_tokens=3)]       # 1 page
    outs = engine.generate(reqs)
    assert [len(o) for o in outs] == [14, 14, 3]
    assert engine.stats()["kv_pages_free"] == 2
    # the pool bound is enforced at submit for impossible requests
    with pytest.raises(ValueError, match="kv_pages"):
        engine.submit(Request(prompt=list(range(1, 40)), max_new_tokens=20))


class _RecordingAllocator(PageAllocator):
    """PageAllocator that logs every alloc and release, in call order."""

    def __init__(self, n_pages: int):
        super().__init__(n_pages)
        self.log: list[tuple[str, list[int]]] = []

    def alloc(self, n: int) -> list[int]:
        pages = super().alloc(n)
        self.log.append(("alloc", pages))
        return pages

    def release(self, pages: list[int]) -> None:
        super().release(pages)
        self.log.append(("release", list(pages)))


def test_engine_reuses_freed_pages_lowest_first():
    """Mixed-length requests over 3 slots retire and re-admit mid-stream:
    every slot receives the lowest free pages (replayed on the sorted-list
    reference), its page-table row holds exactly those pages, freed pages
    are handed out again, and the tokens equal the batch-1 runs."""
    engine = engine_for("dense")
    rng = np.random.RandomState(7)
    reqs = [Request(prompt=rng.randint(1, 64, int(n)).tolist(),
                    max_new_tokens=int(m))
            for n, m in zip(rng.randint(2, 30, 9), rng.randint(2, 24, 9))]
    solo = []
    for r in reqs:
        engine.reset()
        solo.append(engine.generate([r])[0])

    engine.reset()
    kv = engine._kv
    engine._pager = rec = _RecordingAllocator(kv.n_pages)
    rids = [engine.submit(r) for r in reqs]
    collected = {}
    while engine.pending():
        collected.update(engine.step())
        pt = np.asarray(engine.cache["pt"])
        for slot, pages in engine._slot_pages.items():
            assert pt[slot].tolist() == pages + [kv.trash_page] * (
                kv.max_pages_per_slot - len(pages))
    assert [collected[rid] for rid in rids] == solo
    assert engine.stats()["kv_pages_free"] == kv.n_pages

    ref, released, reused = _SortedListPool(kv.n_pages), set(), False
    for op, pages in rec.log:
        if op == "alloc":
            assert pages == ref.alloc(len(pages))
            reused |= bool(released & set(pages))
        else:
            ref.release(pages)
            released |= set(pages)
    assert reused                    # freed pages were handed out again


@pytest.mark.parametrize("family", sorted(CONFIGS))
def test_paged_pool_holds_more_slots_in_monolithic_bytes(family):
    """The int8 page pool buys concurrency: under one burst of 16 requests,
    8 paged slots whose pool holds the token capacity of 4 monolithic slots
    (and no more slot-cache bytes) run strictly more slots at once, at
    fewer peak live bytes per active slot."""
    cfg = CONFIGS[family]
    params = init_model(jax.random.PRNGKey(0), cfg, permissive())
    mono_slots, max_len, page = 4, 64, 16
    engines = {
        "monolithic": Engine(cfg, permissive(), params, ServeConfig(
            max_slots=mono_slots, max_len=max_len, prefill_chunk=8,
            kv_mode="monolithic")),
        "paged": Engine(cfg, permissive(), params, ServeConfig(
            max_slots=2 * mono_slots, max_len=max_len, prefill_chunk=8,
            kv_mode="paged", kv_page_size=page,
            kv_pages=mono_slots * max_len // page)),
    }
    rng = np.random.default_rng(0)
    burst = [Request(prompt=rng.integers(1, cfg.vocab, 5).tolist(),
                     max_new_tokens=10) for _ in range(16)]
    stats = {}
    for mode, engine in engines.items():
        assert all(len(t) == 10 for t in engine.generate(burst))
        stats[mode] = engine.stats()
    mono, paged = stats["monolithic"], stats["paged"]
    assert paged["slot_cache_bytes"] <= mono["slot_cache_bytes"]
    assert paged["peak_slots_active"] > mono["peak_slots_active"]
    assert (paged["peak_live_bytes"] / paged["peak_slots_active"]
            < mono["peak_live_bytes"] / mono["peak_slots_active"])


def test_moe_capacity_footgun_refused_at_construction():
    """An MoE capacity_factor that cannot hold a worst-case decode batch
    (all slots routed to one expert) would silently drop tokens; the
    Engine must refuse to build and name the minimum."""
    cfg = ModelConfig(name="m", family="moe", n_layers=1, d_model=32,
                      n_heads=4, n_kv_heads=4, d_ff=0, vocab=64, head_dim=8,
                      scan_layers=False, remat=False,
                      moe=MoEConfig(n_experts=4, top_k=2, n_shared=1,
                                    d_ff_expert=32, capacity_factor=1.0))
    params = init_model(jax.random.PRNGKey(0), cfg, permissive())
    with pytest.raises(ValueError, match=r"capacity_factor >= 2"):
        Engine(cfg, permissive(), params,
               ServeConfig(max_slots=3, max_len=64, prefill_chunk=8))


# ---------------------------------------------------------------------------
# Bucketed prefill ≡ exact-length prefill
# ---------------------------------------------------------------------------

def test_bucketed_prefill_matches_exact_length():
    from repro.models import init_cache
    from repro.train.steps import make_bucketed_prefill_step, \
        make_prefill_step
    cfg = CONFIGS["dense"]
    params = init_model(jax.random.PRNGKey(1), cfg, permissive())
    exact = make_prefill_step(cfg, permissive())
    bucketed = make_bucketed_prefill_step(cfg, permissive())
    for n in (1, 3, 5, 8):
        toks = jax.random.randint(jax.random.PRNGKey(n), (1, n), 1, 64)
        lo, co = exact(params, init_cache(cfg, 1, 64), {"tokens": toks})
        b = bucket_for(n, 8)
        padded = jnp.pad(toks, ((0, 0), (0, b - n)))
        lb, cb = bucketed(params, init_cache(cfg, 1, 64),
                          {"tokens": padded}, jnp.asarray(n, jnp.int32))
        np.testing.assert_allclose(np.asarray(lb), np.asarray(lo),
                                   rtol=1e-5, atol=1e-6)
        assert (int(jnp.asarray(cb["pos"]).ravel()[0])
                == int(jnp.asarray(co["pos"]).ravel()[0]) == n)
        # cache rows below pos agree; pad rows sit beyond the decode mask
        np.testing.assert_allclose(np.asarray(cb["k"][:, 0, :n]),
                                   np.asarray(co["k"][:, 0, :n]),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# SMOKE configs re-derive padding (the inherited-padding footgun)
# ---------------------------------------------------------------------------

def test_smoke_configs_rederive_padded_fields():
    from repro.configs import registry
    for arch in registry.ARCH_IDS:
        smoke = registry.get_config(arch, smoke=True)
        assert smoke.vocab_padded == smoke.vocab, arch
        assert smoke.n_heads_padded == smoke.n_heads, arch
        assert smoke.n_kv_heads_padded == smoke.n_kv_heads, arch


# ---------------------------------------------------------------------------
# Analyzer: the trace.kv-* rules see through a precision mismatch
# ---------------------------------------------------------------------------

def test_analyzer_flags_plan_cache_precision_mismatch():
    """Plan says int8 KV but the traced cache is monolithic float — the
    silent-fallback case trace.kv-cache exists to catch."""
    from repro.analysis.jaxpr_checks import check_kv_cache
    cfg = CONFIGS["dense"]
    cache = jax.eval_shape(
        lambda: {"k": jnp.zeros((2, 3, 64, 2, 8), jnp.bfloat16),
                 "v": jnp.zeros((2, 3, 64, 2, 8), jnp.bfloat16),
                 "pos": jnp.zeros((3,), jnp.int32)})
    plan = types.SimpleNamespace(quant_plan=types.SimpleNamespace(
        get=lambda path, default=None: types.SimpleNamespace(w_bits=8)))
    diags = check_kv_cache("t", cfg, {"kv": None, "cache": cache}, plan)
    errs = [d for d in diags if d.check == "trace.kv-cache"
            and d.severity == "error"]
    assert errs and "silent precision fallback" in errs[0].message


def test_analyzer_prefill_budget_is_the_bucket_menu():
    from repro.analysis.jaxpr_checks import ANALYZER_SCFG
    chunk = ANALYZER_SCFG["prefill_chunk"]
    assert len(prefill_buckets(chunk)) < chunk   # strictly tighter than old


# ---------------------------------------------------------------------------
# The paged kernel: routing, structure of the routed step, pages read
# ---------------------------------------------------------------------------

#: heads the paged kernel can read in place (8 KV heads of 128; see
#: kernels/decode_attention.paged_decode_tiles_ok), everything else tiny
ROUTABLE = ModelConfig(name="routable", family="dense", n_layers=2,
                       d_model=32, n_heads=16, n_kv_heads=8, d_ff=64,
                       vocab=64, head_dim=128, scan_layers=False,
                       remat=False)


def _routed_decode_jaxpr(cfg, S=3, max_len=64):
    from repro.serve.deploy import init_slot_cache, init_slot_state
    from repro.train.steps import make_slot_decode_step
    kv = resolve_kv_spec(cfg, ServeConfig(max_slots=S, max_len=max_len))
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg,
                                               None))
    cache = jax.eval_shape(lambda: init_slot_cache(cfg, S, max_len, kv=kv))
    state = jax.eval_shape(lambda: init_slot_state(S))
    step = make_slot_decode_step(cfg, None, use_pallas=True)
    return jax.make_jaxpr(step)(params, cache, state), cache


def test_routed_paged_decode_reads_the_pool_in_place():
    """A routed paged decode step holds one pallas_call per attention
    layer, reading the whole layer-stacked pool (as its page-slab view):
    no gather of the pool, and nothing but the token's scatter and that
    view makes an array of a layer's pool extent (no per-layer slice or
    re-stack of it)."""
    from repro.analysis.jaxpr_checks import iter_eqns
    closed, cache = _routed_decode_jaxpr(ROUTABLE)
    pool = cache["k"].shape                        # [L, n_pages+1, P, Hkv, hd]
    eqns = list(iter_eqns(closed))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == ROUTABLE.n_layers
    for e in calls:
        assert sum(v.aval.dtype == jnp.int8 and v.aval.size == np.prod(pool)
                   for v in e.invars) == 2        # the K and the V stack
    assert not [e for e in eqns if e.primitive.name == "gather"
                and e.outvars[0].aval.dtype == jnp.int8]
    makers = {e.primitive.name for e in eqns for v in e.outvars
              if getattr(v.aval, "size", 0) >= np.prod(pool[1:])}
    assert makers == {"scatter", "reshape"}, makers


def test_paged_route_agrees_across_engine_analyzer_and_trace():
    """decode_route decides the paged kernel by the page's shape; the
    engine's route counters and the analyzer's trace.kernel-route agree
    with it, routed (8 KV heads of 128) and not (the tiny heads)."""
    from repro.analysis.jaxpr_checks import ANALYZER_SCFG, check_kernel_route
    from repro.models.attention import decode_route
    from repro.serve.deploy import abstract_deploy_surfaces
    for cfg, want in ((ROUTABLE, True), (CONFIGS["dense"], False)):
        assert decode_route(cfg, 64, True, page_size=16) is want
        assert not decode_route(cfg, 64, False, page_size=16)
        params = init_model(jax.random.PRNGKey(0), cfg, permissive())
        plan = make_deploy_plan(permissive(), arch=cfg.name,
                                family=cfg.family, use_pallas=True,
                                params=params, model_cfg=cfg)
        stats = Engine(cfg, permissive(), params,
                       ServeConfig(max_slots=2, max_len=64), plan=plan).stats()
        assert stats["decode_attn_pallas_layers"] == (cfg.n_layers if want
                                                      else 0)
        plan, _, deployed = abstract_deploy_surfaces(cfg, permissive(),
                                                     use_pallas=True)
        (d,) = check_kernel_route(cfg.name, cfg,
                                  ServeConfig(**ANALYZER_SCFG), deployed,
                                  plan)
        assert d.severity == "info" and d.value is want, d


def test_decode_kv_pages_counts_live_pages():
    """decode_kv_pages sums ceil(length / P) over the live slots of every
    decode step, the token written this step included: a request of
    prompt p and n new tokens decodes at lengths p+1 .. p+n."""
    engine = Engine(CONFIGS["dense"], permissive(),
                    init_model(jax.random.PRNGKey(0), CONFIGS["dense"],
                               permissive()),
                    ServeConfig(max_slots=2, max_len=64, prefill_chunk=8,
                                kv_page_size=4))
    reqs = [Request(prompt=list(range(1, 1 + p)), max_new_tokens=n)
            for p, n in ((3, 6), (8, 5), (1, 9), (12, 4))]
    engine.reset()
    engine.generate(reqs)
    want = sum(-(-(len(r.prompt) + i) // 4)
               for r in reqs for i in range(1, r.max_new_tokens + 1))
    s = engine.stats()
    assert s["decode_kv_pages"] == want
    assert s["decode_live_slot_rows"] == sum(r.max_new_tokens for r in reqs)


def test_paged_kernel_serves_the_reference_route_tokens():
    """An engine whose decode reads pages through the paged kernel emits
    the tokens of the same engine on the gathered view and `_paged_sdpa`,
    over slot refill and page reuse."""
    params = init_model(jax.random.PRNGKey(0), ROUTABLE, permissive())
    out = []
    for use_pallas in (False, True):
        plan = make_deploy_plan(permissive(), arch=ROUTABLE.name,
                                family=ROUTABLE.family,
                                use_pallas=use_pallas, params=params,
                                model_cfg=ROUTABLE)
        engine = Engine(ROUTABLE, permissive(), params,
                        ServeConfig(max_slots=3, max_len=64,
                                    prefill_chunk=8), plan=plan)
        assert engine.stats()["decode_attn_pallas_layers"] == (
            ROUTABLE.n_layers if use_pallas else 0)
        out.append(engine.generate(REQS))
    assert out[0] == out[1]
