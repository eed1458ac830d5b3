"""Serving conformance tier: continuous-batching engine correctness.

The headline contract is **batch-composition invariance**: a request's
output tokens are bit-identical whether it is served alone, in a static
batch, or interleaved under continuous batching with random arrival order.
The engine earns this by prefilling every request alone (batch 1, chunked)
and keeping decode slots computationally independent — see DESIGN.md
"Serving: continuous batching".

Also here: the Scheduler's FIFO/refill bookkeeping, the one-host-transfer-
per-decode-step regression guard (PR 2's device-side bookkeeping), request
validation errors, and a hypothesis no-starvation property.

PR 9 extends the contract to SEEDED SAMPLING (per-request temperature /
top_k / top_p / seed, drawn device-side inside the same jitted step): a
sampled request's tokens are bit-identical solo vs static-batch vs
interleaved, the same seed twice reproduces, different seeds diverge
(non-vacuity), and eos still stops a sampled stream early in any
composition.  The token-streaming consumer API (Engine.stream /
submit(on_token=...)) is covered next: emission order, ownership
transfer, bounded memory.

Then open-loop arrivals: seeded poisson, bursty and long-tailed traces
submitted at their arrival steps while the engine steps, checked against
solo runs (greedy, sampled and on the paged decode kernel), and continuous
refill against static waves of ``max_slots``; then ``Engine.stats()``
accounting.
"""
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import permissive
from repro.models import ModelConfig, init_model
from repro.models.config import MoEConfig, SSMConfig
from repro.serve.deploy import init_slot_cache, make_deploy_plan
from repro.serve.engine import (STEP_COUNTERS, Engine, Request, Scheduler,
                                ServeConfig)

CONFIGS = {
    "dense": ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                         n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                         head_dim=8, scan_layers=False, remat=False),
    # capacity_factor 8 → C covers every routed assignment even if all of
    # them hit one expert: capacity DROPS would couple a slot's output to
    # what else shares the decode batch and break composition invariance
    "moe": ModelConfig(name="m", family="moe", n_layers=1, d_model=32,
                       n_heads=4, n_kv_heads=4, d_ff=0, vocab=64, head_dim=8,
                       scan_layers=False, remat=False,
                       moe=MoEConfig(n_experts=4, top_k=2, n_shared=1,
                                     d_ff_expert=32, capacity_factor=8.0)),
    "ssm": ModelConfig(name="s", family="ssm", n_layers=2, d_model=32,
                       n_heads=0, n_kv_heads=0, d_ff=0, vocab=64, head_dim=8,
                       tie_embeddings=True, scan_layers=False, remat=False,
                       ssm=SSMConfig(d_state=8, d_conv=4, expand=2,
                                     head_dim=8, chunk=8)),
}

# prompt 11 > prefill_chunk exercises chunked prefill; 5 requests over
# 3 slots exercise queueing + slot refill
REQS = [Request(prompt=[1, 2, 3], max_new_tokens=5),
        Request(prompt=[7, 8], max_new_tokens=3),
        Request(prompt=list(range(1, 12)), max_new_tokens=4),
        Request(prompt=[5, 4, 3, 2, 1], max_new_tokens=6),
        Request(prompt=[9, 9], max_new_tokens=2, eos_id=0)]


@functools.lru_cache(maxsize=None)
def engine_for(family: str, max_slots: int = 3) -> Engine:
    """One engine per (family, slot count) for the whole module — the jitted
    steps are shared per ModelConfig and ``reset()`` makes reuse exact."""
    cfg = CONFIGS[family]
    params = init_model(jax.random.PRNGKey(0), cfg, permissive())
    return Engine(cfg, permissive(), params,
                  ServeConfig(max_slots=max_slots, max_len=64,
                              prefill_chunk=8))


def solo_reference(family: str) -> list[list[int]]:
    engine = engine_for(family)
    outs = []
    for r in REQS:
        engine.reset()
        outs.append(engine.generate([r])[0])
    return outs


# ---------------------------------------------------------------------------
# Tentpole: batch-composition invariance (bit-exact tokens across modes)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", sorted(CONFIGS))
def test_batch_composition_invariance(family):
    engine = engine_for(family)
    ref = solo_reference(family)

    # static batch: first 3 fill the whole slot pool at once (the remaining
    # 2 queue and land on freed slots — the refill path)
    engine.reset()
    static = engine.generate(REQS)
    for r, s in zip(ref, static):
        assert jnp.array_equal(jnp.asarray(r), jnp.asarray(s)), (r, s)

    # continuous: random arrival order with random gaps between submissions
    rng = np.random.RandomState(7)
    order = rng.permutation(len(REQS))
    engine.reset()
    rid_of = {}
    collected = {}
    for j in order:
        rid_of[j] = engine.submit(REQS[j])
        for _ in range(int(rng.randint(0, 3))):
            if engine.pending():
                collected.update(engine.step())
    while engine.pending():
        collected.update(engine.step())
    for j in range(len(REQS)):
        got = collected[rid_of[j]]
        assert jnp.array_equal(jnp.asarray(ref[j]), jnp.asarray(got)), \
            (family, j, ref[j], got)
    assert not engine._results and not engine._work   # nothing retained


def test_eos_stops_early_in_any_composition():
    """A request whose eos fires mid-stream keeps its early stop under
    continuous batching (budgets of co-tenants must not leak)."""
    engine = engine_for("dense")
    engine.reset()
    base = engine.generate([Request(prompt=[3, 1], max_new_tokens=8)])[0]
    eos = base[2] if len(base) > 2 else base[-1]
    engine.reset()
    solo = engine.generate([Request(prompt=[3, 1], max_new_tokens=8,
                                    eos_id=eos)])[0]
    assert len(solo) < 8 and solo[-1] == eos
    engine.reset()
    mixed = engine.generate([REQS[0],
                             Request(prompt=[3, 1], max_new_tokens=8,
                                     eos_id=eos),
                             REQS[3]])
    assert mixed[1] == solo


# ---------------------------------------------------------------------------
# Tentpole PR 7: same conformance with the flash-decode kernel routed in,
# and Engine.stats() kernel-route counters
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def routed_engine_for(family: str, max_slots: int = 3) -> Engine:
    """Engine whose DeployPlan routes the slot decode through the Pallas
    flash-decode kernel (interpret mode on CPU): the paged kernel, so the
    attention families get heads it can read in place (8 KV heads of 128,
    models/attention.decode_route)."""
    cfg = CONFIGS[family]
    if cfg.family != "ssm":
        cfg = dataclasses.replace(cfg, n_heads=2 * 8 if family == "dense"
                                  else 8, n_kv_heads=8, head_dim=128,
                                  n_heads_padded=0, n_kv_heads_padded=0)
    params = init_model(jax.random.PRNGKey(0), cfg, permissive())
    plan = make_deploy_plan(permissive(), arch=cfg.name, family=cfg.family,
                            use_pallas=True, interpret=None, params=params,
                            model_cfg=cfg)
    return Engine(cfg, permissive(), params,
                  ServeConfig(max_slots=max_slots, max_len=64,
                              prefill_chunk=8), plan=plan)


@pytest.mark.parametrize("family", sorted(CONFIGS))
def test_batch_composition_invariance_with_decode_kernel(family):
    """The conformance contract must survive the kernel route: per-request
    tokens identical solo vs batched vs interleaved on the SAME routed
    engine (slots stay computationally independent inside the kernel —
    per-slot grid rows, per-slot lengths)."""
    engine = routed_engine_for(family)
    ref = []
    for r in REQS:
        engine.reset()
        ref.append(engine.generate([r])[0])

    engine.reset()
    static = engine.generate(REQS)
    assert static == ref

    rng = np.random.RandomState(11)
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
def test_stats_reports_kernel_route_counters(family):
    """stats() must expose the per-layer decode-attention route: all
    attention layers on the Pallas kernel for a routed dense/moe engine,
    zero for the default (XLA-reference) engine; SSM has no attention to
    route either way."""
    n_attn = {"dense": CONFIGS["dense"].n_layers,
              "moe": CONFIGS["moe"].n_layers, "ssm": 0}[family]
    routed = routed_engine_for(family).stats()
    assert routed["decode_attn_pallas_layers"] == n_attn
    assert routed["decode_attn_ref_layers"] == 0
    default = engine_for(family).stats()
    assert default["decode_attn_pallas_layers"] == 0
    assert default["decode_attn_ref_layers"] == n_attn


# ---------------------------------------------------------------------------
# Scheduler bookkeeping (pure host logic)
# ---------------------------------------------------------------------------

def test_scheduler_fifo_admission_and_refill():
    s = Scheduler(max_slots=2)
    rids = [s.submit(Request(prompt=[1])) for _ in range(4)]
    assert rids == [0, 1, 2, 3]                  # arrival order ids
    admitted = s.admit()
    assert [(slot, r.rid) for slot, r in admitted] == [(0, 0), (1, 1)]
    assert s.admit() == []                       # pool exhausted
    assert s.pending == 4
    assert s.evict(0) == 0                       # slot 0 frees...
    admitted = s.admit()                         # ...and refills FIFO
    assert [(slot, r.rid) for slot, r in admitted] == [(0, 2)]
    s.evict(1)
    assert [(slot, r.rid) for slot, r in s.admit()] == [(1, 3)]
    s.evict(0), s.evict(1)
    assert s.pending == 0


def test_init_slot_cache_vectorizes_pos():
    cfg = CONFIGS["dense"]
    cache = init_slot_cache(cfg, 3, 16)
    assert cache["pos"].shape == (3,) and cache["pos"].dtype == jnp.int32
    assert cache["k"].shape == (cfg.n_layers, 3, 16, cfg.n_kv_heads, 8)
    ssm_cache = init_slot_cache(CONFIGS["ssm"], 3, 16)
    assert "pos" not in ssm_cache                # SSM state has no positions
    assert ssm_cache["ssm_state"].shape[1] == 3


# ---------------------------------------------------------------------------
# Satellite: PR 2's device-side decode bookkeeping — one transfer per step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", sorted(CONFIGS))
@pytest.mark.parametrize("max_slots", [1, 5])
def test_decode_step_one_transfer_surface(family, max_slots):
    """Structural proof of the one-transfer invariant: the traced decode
    jaxpr has exactly one host-transfer surface (the output fetch; zero
    callback primitives), for every family x slot count — no engine built,
    nothing run.  Replaces the monkeypatch-counted device_get regression
    test; test_decode_loop_runtime_transfer_sentinel below keeps one
    runtime probe alive so this analyzer cannot rot into vacuity."""
    from repro.analysis.jaxpr_checks import transfer_surfaces
    from repro.serve.deploy import abstract_deploy_surfaces
    from repro.serve.engine import serve_trace_surfaces

    cfg = CONFIGS[family]
    scfg = ServeConfig(max_slots=max_slots, max_len=64, prefill_chunk=8)
    plan, _ex, deployed = abstract_deploy_surfaces(cfg, permissive())
    s = serve_trace_surfaces(cfg, plan=plan, scfg=scfg)
    closed = jax.make_jaxpr(s["decode_fn"])(deployed, s["cache"], s["state"])
    assert transfer_surfaces(closed) == 1


def test_decode_loop_runtime_transfer_sentinel(monkeypatch):
    """Runtime sentinel for the structural check above: count actual
    jax.device_get calls for one (family, slot count) cell.  If the engine
    ever moves its sync off jax.device_get (where the analyzer counts
    callback primitives instead), this still fails loudly."""
    engine = engine_for("dense", max_slots=3)
    engine.reset()
    for _ in range(4):                           # overfill: queueing too
        engine.submit(Request(prompt=[1, 2], max_new_tokens=4))
    calls = [0]
    real = jax.device_get

    def counting(x):
        calls[0] += 1
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    steps = 0
    while engine.pending():
        calls[0] = 0
        engine.step()
        steps += 1
        # prompts fit one chunk, so every step runs a decode: exactly ONE
        # host transfer regardless of slot count / queue depth
        assert calls[0] == 1, (steps, calls[0])
        assert steps < 50
    assert steps > 1


# ---------------------------------------------------------------------------
# Satellite: request validation (clear errors, not jit shape errors)
# ---------------------------------------------------------------------------

def test_generate_validates_requests():
    engine = engine_for("dense")
    engine.reset()
    with pytest.raises(ValueError, match="non-empty request list"):
        engine.generate([])
    with pytest.raises(ValueError, match="non-empty token list"):
        engine.generate([Request(prompt=[])])
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.generate([Request(prompt=[1], max_new_tokens=0)])
    with pytest.raises(ValueError, match="cache positions"):
        # 60 + 30 > max_len=64 — would previously shape-error inside jit
        engine.generate([Request(prompt=list(range(60)),
                                 max_new_tokens=30)])
    with pytest.raises(ValueError, match="non-empty token list"):
        # bad request mid-list: validation is all-or-nothing — the valid
        # request ahead of it must NOT stay enqueued
        engine.generate([Request(prompt=[1, 2]), Request(prompt=[])])
    assert engine.pending() == 0                 # rejected, nothing enqueued


def test_generate_drains_earlier_submissions_without_tripping():
    """generate()'s no-progress watchdog must budget for ALL outstanding
    work, and results it drains for foreign rids stay retrievable."""
    engine = engine_for("dense")
    engine.reset()
    rid = engine.submit(Request(prompt=list(range(1, 30)),  # 4 chunks
                                max_new_tokens=16))
    out = engine.generate([Request(prompt=[1], max_new_tokens=1)])
    assert len(out) == 1 and len(out[0]) == 1
    foreign = engine.result(rid)                 # drained by generate above
    assert len(foreign) == 16
    with pytest.raises(KeyError):                # handed out exactly once
        engine.result(rid)


def test_serve_config_rejects_nonsense():
    with pytest.raises(ValueError, match="max_slots"):
        engine_for("dense", max_slots=0)
    # legacy spelling still accepted
    assert ServeConfig(slots=6).max_slots == 6


# ---------------------------------------------------------------------------
# Open-loop arrivals: seeded traces submitted while the engine steps
# ---------------------------------------------------------------------------

ARRIVAL_KINDS = ("poisson", "bursty", "longtail")


def arrival_trace(kind: str, sampled: bool = False, n: int = 16,
                  max_prompt: int = 24, max_new: int = 12):
    """Seeded ``(arrival step, Request)`` pairs, arrivals non-decreasing.

    ``poisson``: Poisson gaps between arrivals.  ``bursty``: on/off, bursts
    of 2-5 requests on one step with 4-10 idle steps between.
    ``longtail``: Poisson arrivals with lognormal prompt and output lengths
    clipped to the limits.  ``sampled``: every third request samples
    (seeded ``temperature`` 0.8, ``top_p`` 0.9); the rest are greedy."""
    rng = np.random.default_rng(ARRIVAL_KINDS.index(kind))
    if kind == "bursty":
        arrivals, t = [], 0
        while len(arrivals) < n:
            arrivals += [t] * int(rng.integers(2, 6))
            t += int(rng.integers(4, 11))
        arrivals = arrivals[:n]
    else:
        arrivals = np.cumsum(rng.poisson(2.0, n)).tolist()
    if kind == "longtail":
        plens = np.clip(rng.lognormal(1.5, 0.9, n), 1, max_prompt)
        gens = np.clip(rng.lognormal(1.2, 0.8, n), 1, max_new)
    else:
        plens = rng.integers(1, max_prompt + 1, n)
        gens = rng.integers(1, max_new + 1, n)
    trace = []
    for i, (t, p, g) in enumerate(zip(arrivals, plens, gens)):
        knobs = (dict(temperature=0.8, top_p=0.9, seed=100 + i)
                 if sampled and i % 3 == 0 else {})
        trace.append((int(t), Request(prompt=rng.integers(1, 64, int(p)).tolist(),
                                      max_new_tokens=int(g), **knobs)))
    return trace


def serve_arrivals(engine: Engine, trace, waves: bool = False):
    """Serve ``trace`` from a reset engine, one ``step()`` per tick; return
    (each request's tokens in trace order, ticks until all finished).

    Continuous: each request is submitted at its arrival tick.  ``waves``:
    arrived requests are held until the engine drains, then submitted
    ``max_slots`` at a time (static batching)."""
    engine.reset()
    index, out, held = {}, {}, []
    nxt = tick = 0
    while nxt < len(trace) or held or engine.pending():
        assert tick < 4000, "open-loop run did not drain"
        while nxt < len(trace) and trace[nxt][0] <= tick:
            held.append(nxt)
            nxt += 1
        if not waves or not engine.pending():
            wave = held[:engine.scfg.max_slots] if waves else held
            for j in wave:
                index[engine.submit(trace[j][1])] = j
            held = held[len(wave):]
        if engine.pending():
            for rid, toks in engine.step().items():
                out[index[rid]] = toks
        tick += 1
    assert sorted(out) == list(range(len(trace)))    # every request finished
    return [out[j] for j in range(len(trace))], tick


def step_counters(engine: Engine) -> dict[str, int]:
    stats = engine.stats()
    return {k: stats[k] for k in STEP_COUNTERS}


@functools.lru_cache(maxsize=None)
def solo_arrival_tokens(make, family: str, kind: str, sampled: bool = False):
    """Each trace request served alone on ``make(family)``'s engine."""
    engine = make(family)
    out = []
    for _, r in arrival_trace(kind, sampled):
        engine.reset()
        out.append(engine.generate([r])[0])
    return out


@pytest.mark.parametrize("family", sorted(CONFIGS))
@pytest.mark.parametrize("kind", ARRIVAL_KINDS)
def test_open_loop_arrivals_serve_solo_tokens(kind, family):
    """Requests submitted at their arrival steps while the engine steps all
    finish with their solo tokens, and a second run of the trace repeats
    the step counters exactly."""
    engine = engine_for(family)
    trace = arrival_trace(kind)
    got, _ = serve_arrivals(engine, trace)
    counters = step_counters(engine)
    assert serve_arrivals(engine, trace)[0] == got
    assert step_counters(engine) == counters
    assert got == solo_arrival_tokens(engine_for, family, kind)


@pytest.mark.parametrize("kind", ARRIVAL_KINDS)
def test_open_loop_arrivals_with_decode_kernel(kind):
    """The same contract on the routed engine: the paged Pallas decode
    kernel, with pages retired and refilled as requests come and go."""
    engine = routed_engine_for("dense")
    trace = arrival_trace(kind)
    got, _ = serve_arrivals(engine, trace)
    counters = step_counters(engine)
    stats = engine.stats()
    assert stats["decode_attn_pallas_layers"] == CONFIGS["dense"].n_layers
    assert stats["kv_pages_free"] == stats["kv_pages_total"]
    assert counters["retires"] == len(trace)
    assert serve_arrivals(engine, trace)[0] == got
    assert step_counters(engine) == counters
    assert got == solo_arrival_tokens(routed_engine_for, "dense", kind)


@pytest.mark.parametrize("kind", ARRIVAL_KINDS)
def test_open_loop_mixed_greedy_and_sampled(kind):
    """A third of the requests sample, so the decode step's sampler branch
    is taken on some steps and skipped on others; every request still
    emits its solo tokens."""
    engine = engine_for("dense")
    trace = arrival_trace(kind, sampled=True)
    got, _ = serve_arrivals(engine, trace)
    counters = step_counters(engine)
    assert 0 < counters["decode_sampled_steps"] < counters["decode_steps"]
    assert got == solo_arrival_tokens(engine_for, "dense", kind, sampled=True)


@pytest.mark.parametrize("kind", ARRIVAL_KINDS)
def test_continuous_refill_beats_static_waves(kind):
    """Continuous submission finishes each trace in fewer steps than static
    waves of ``max_slots`` (submit a wave, drain it, repeat), with the same
    tokens."""
    engine = engine_for("dense")
    trace = arrival_trace(kind)
    continuous, continuous_ticks = serve_arrivals(engine, trace)
    static, static_ticks = serve_arrivals(engine, trace, waves=True)
    assert static == continuous
    assert continuous_ticks < static_ticks


# ---------------------------------------------------------------------------
# Engine.stats() accounting
# ---------------------------------------------------------------------------

def test_engine_stats_accounting():
    cfg = ModelConfig(name="stats-t", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab=64, head_dim=8,
                      scan_layers=False, remat=False)
    params = init_model(jax.random.PRNGKey(0), cfg, permissive())
    eng = Engine(cfg, permissive(), params,
                 ServeConfig(max_slots=2, max_len=32, prefill_chunk=4))
    s0 = eng.stats()
    for k in ("params_bytes", "artifact_bytes", "slot_cache_bytes",
              "live_bytes", "peak_live_bytes"):
        assert s0[k] > 0, k
    assert s0["queue_depth"] == 0 and s0["slots_active"] == 0
    assert s0["prefill_bytes"] == 0
    assert s0["peak_live_bytes"] == s0["live_bytes"]

    # 3 requests into 2 slots: all queue until step() admits, then one is
    # left waiting; peak must include the admitted slots' prefill caches
    for _ in range(3):
        eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=4))
    assert eng.stats()["queue_depth"] == 3
    eng.step()
    assert eng.stats()["queue_depth"] == 1
    s1 = eng.stats()
    assert s1["peak_live_bytes"] > s0["peak_live_bytes"]
    while eng.pending():
        eng.step()
    s2 = eng.stats()
    # drained: live falls back to the static floor, peak is sticky
    assert s2["live_bytes"] == s0["live_bytes"]
    assert s2["peak_live_bytes"] == s1["peak_live_bytes"]
    assert s2["queue_depth"] == 0 and s2["slots_active"] == 0
    # reset() rebases the peak
    eng.reset()
    assert eng.stats()["peak_live_bytes"] == s0["peak_live_bytes"]


# ---------------------------------------------------------------------------
# Satellite: hypothesis property — the scheduler never starves a request
# ---------------------------------------------------------------------------

try:                     # optional dev dependency — only this test skips,
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:      # not the whole conformance module
    _HAVE_HYPOTHESIS = False

    def given(**kw):     # no-op decorators so the def below still parses
        return lambda f: pytest.mark.skip(
            reason="optional dev dependency (pip install .[dev])")(f)

    def settings(**kw):
        return lambda f: f

    class st:            # noqa: N801 — mirrors the hypothesis module name
        @staticmethod
        def data():
            return None


# ---------------------------------------------------------------------------
# Tentpole PR 9: seeded-sampling conformance — the batch-composition
# contract extended to stochastic decoding
# ---------------------------------------------------------------------------

# per-request sampling configs exercising every knob (and their stacking);
# seeds far apart so accidental chain collisions can't mask a bug
SAMPLED_REQS = [
    Request(prompt=[1, 2, 3], max_new_tokens=5, temperature=0.9, seed=11),
    Request(prompt=[7, 8], max_new_tokens=3, temperature=1.3, top_k=8,
            seed=22),
    Request(prompt=list(range(1, 12)), max_new_tokens=4, temperature=0.7,
            top_p=0.85, seed=33),
    Request(prompt=[5, 4, 3, 2, 1], max_new_tokens=6, temperature=1.0,
            top_k=16, top_p=0.9, seed=44),
    Request(prompt=[9, 9], max_new_tokens=5, temperature=0.8, seed=55),
]


@pytest.mark.parametrize("family", sorted(CONFIGS))
def test_sampled_batch_composition_invariance(family):
    """A SAMPLED request's tokens are bit-identical solo, in a static
    batch, and interleaved under random arrivals: each slot's draw comes
    from its own (seed, step) key chain, so co-tenants cannot perturb it."""
    engine = engine_for(family)
    ref = []
    for r in SAMPLED_REQS:
        engine.reset()
        ref.append(engine.generate([r])[0])

    engine.reset()
    static = engine.generate(SAMPLED_REQS)
    assert static == ref

    rng = np.random.RandomState(13)
    order = rng.permutation(len(SAMPLED_REQS))
    engine.reset()
    rid_of, collected = {}, {}
    for j in order:
        rid_of[j] = engine.submit(SAMPLED_REQS[j])
        for _ in range(int(rng.randint(0, 3))):
            if engine.pending():
                collected.update(engine.step())
    while engine.pending():
        collected.update(engine.step())
    assert [collected[rid_of[j]] for j in range(len(SAMPLED_REQS))] == ref


def test_sampling_seeded_reproducible_and_nonvacuous():
    """Same seed twice → identical tokens; different seed → different
    tokens; and the sampled stream differs from greedy — proving the
    categorical actually draws (the tier can't silently pass with sampling
    wired to argmax)."""
    engine = engine_for("dense")

    def run(**kw):
        engine.reset()
        return engine.generate([Request(prompt=[1, 2, 3], max_new_tokens=8,
                                        **kw)])[0]

    a = run(temperature=1.0, seed=3)
    b = run(temperature=1.0, seed=3)
    assert a == b                                  # bit-reproducible
    c = run(temperature=1.0, seed=4)
    assert c != a                                  # seed actually matters
    greedy = run()
    assert a != greedy or c != greedy              # draws are not argmax


def test_sampled_eos_stops_early_in_any_composition():
    """eos fired by a SAMPLED token keeps its early stop solo and mixed —
    the done bookkeeping sees the drawn token, not the argmax."""
    engine = engine_for("dense")
    engine.reset()
    base = engine.generate([Request(prompt=[3, 1], max_new_tokens=8,
                                    temperature=1.1, seed=17)])[0]
    eos = base[2]
    stopper = Request(prompt=[3, 1], max_new_tokens=8, temperature=1.1,
                      seed=17, eos_id=eos)
    engine.reset()
    solo = engine.generate([stopper])[0]
    assert len(solo) < 8 and solo[-1] == eos
    engine.reset()
    mixed = engine.generate([SAMPLED_REQS[0], stopper, REQS[3]])
    assert mixed[1] == solo


def test_sampling_param_validation():
    engine = engine_for("dense")
    engine.reset()
    with pytest.raises(ValueError, match="temperature"):
        engine.submit(Request(prompt=[1], temperature=-0.5))
    with pytest.raises(ValueError, match="temperature"):
        engine.submit(Request(prompt=[1], temperature=float("nan")))
    with pytest.raises(ValueError, match="top_k"):
        engine.submit(Request(prompt=[1], top_k=-1))
    with pytest.raises(ValueError, match="top_p"):
        engine.submit(Request(prompt=[1], top_p=0.0))
    with pytest.raises(ValueError, match="top_p"):
        engine.submit(Request(prompt=[1], top_p=1.5))
    assert engine.pending() == 0


# ---------------------------------------------------------------------------
# Satellite PR 9: token streaming — per-rid iterators + on_token callbacks
# ---------------------------------------------------------------------------

def test_stream_tokens_match_generate():
    """Iterating a TokenStream yields tokens in emission order and the
    concatenation is exactly what generate() returns for the same request —
    for both a sampled and a greedy request sharing the engine."""
    engine = engine_for("dense")
    engine.reset()
    want = engine.generate([SAMPLED_REQS[0], REQS[1]])
    engine.reset()
    s0 = engine.stream(SAMPLED_REQS[0])
    s1 = engine.stream(REQS[1])
    got0, got1 = [], []
    it0, it1 = iter(s0), iter(s1)     # alternate: emission order preserved
    for sink, it in ((got0, it0), (got1, it1)) * 10:
        try:
            sink.append(next(it))
        except StopIteration:
            pass
    assert [got0, got1] == want


def test_finished_streams_are_popped():
    """Ownership transfer: once the final token is buffered the engine
    drops its consumer reference AND retains no token copy — completed
    streams cost the engine nothing (bounded memory)."""
    engine = engine_for("dense")
    engine.reset()
    ts = engine.stream(Request(prompt=[1, 2], max_new_tokens=4,
                               temperature=1.0, seed=5))
    toks = list(ts)
    assert len(toks) == 4 and ts.finished
    assert ts.rid not in engine._consumers
    assert not engine._results and not engine._work
    # exhausted stream stays exhausted (no engine interaction)
    with pytest.raises(StopIteration):
        next(iter(ts))


def test_stream_survives_foreign_generate_drain():
    """A stream submitted before someone else's generate() keeps its
    tokens: the drain finishes the streamed request but delivers to the
    stream's buffer, never to generate()'s collected results."""
    engine = engine_for("dense")
    engine.reset()
    want = engine.generate([SAMPLED_REQS[3]])[0]
    engine.reset()
    ts = engine.stream(SAMPLED_REQS[3])
    out = engine.generate([Request(prompt=[6, 7], max_new_tokens=2)])
    assert len(out) == 1 and len(out[0]) == 2
    assert ts.finished                 # drained by the foreign generate...
    assert list(ts) == want            # ...into the stream's own buffer


def test_stream_drives_engine_and_stashes_foreign_results():
    """__next__ drives engine.step() when the buffer is empty; buffered
    requests finished by those ticks stay retrievable via result()."""
    engine = engine_for("dense")
    engine.reset()
    ts = engine.stream(Request(prompt=[1, 2, 3], max_new_tokens=6,
                               temperature=0.9, seed=9))
    rid = engine.submit(Request(prompt=[5, 6], max_new_tokens=3))
    toks = list(ts)                    # drives the engine to completion
    assert len(toks) == 6
    foreign = engine.result(rid)       # stashed while the stream drove
    assert len(foreign) == 3
    with pytest.raises(KeyError):      # handed out exactly once
        engine.result(rid)


def test_on_token_callback_delivery():
    """submit(on_token=...) pushes every token with a done flag on the
    last; callback rids never appear in step()'s finished dict and leave
    no engine-side buffer behind."""
    engine = engine_for("dense")
    engine.reset()
    want = engine.generate([SAMPLED_REQS[1]])[0]
    engine.reset()
    seen = []
    engine.submit(SAMPLED_REQS[1],
                  on_token=lambda t, done: seen.append((t, done)))
    while engine.pending():
        assert engine.step() == {}     # ownership went to the callback
    assert [t for t, _ in seen] == want
    assert [done for _, done in seen] == \
        [False] * (len(want) - 1) + [True]
    assert not engine._consumers and not engine._results


@settings(max_examples=10, deadline=None)
@given(data=st.data())
def test_no_request_starves(data):
    """Any submitted request completes within a bounded number of steps,
    for random arrival orders/gaps, prompt lengths, budgets, slot counts."""
    max_slots = data.draw(st.integers(1, 3), label="max_slots")
    n = data.draw(st.integers(1, 5), label="n_requests")
    reqs = [Request(prompt=data.draw(
                        st.lists(st.integers(1, 63), min_size=1, max_size=6),
                        label=f"prompt{i}"),
                    max_new_tokens=data.draw(st.integers(1, 5),
                                             label=f"budget{i}"))
            for i in range(n)]
    gaps = [data.draw(st.integers(0, 2), label=f"gap{i}") for i in range(n)]
    engine = engine_for("dense", max_slots=max_slots)
    engine.reset()
    chunk = engine.scfg.prefill_chunk
    # worst case fully serializes: every request's prefill chunks + budget,
    # plus the idle gap steps taken during submission
    bound = sum(math.ceil(len(r.prompt) / chunk) + r.max_new_tokens
                for r in reqs) + sum(gaps) + 8
    rids = []
    steps = 0
    collected = {}
    for req, gap in zip(reqs, gaps):
        rids.append(engine.submit(req))
        for _ in range(gap):
            collected.update(engine.step())
            steps += 1
    while engine.pending():
        assert steps <= bound, f"starved: {steps} > bound {bound}"
        collected.update(engine.step())
        steps += 1
    for rid, req in zip(rids, reqs):
        assert len(collected[rid]) == req.max_new_tokens
