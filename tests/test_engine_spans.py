"""The serving engine's profiler spans (serve/spans.py) and the step
counters of ``Engine.stats()``.

With no profiler session the span helper is free: one shared null context,
no annotation built.  Inside a CPU profiler session, ``Engine.step``'s
phases come back from the trace as ``repro:`` host events nested as the
code nests, and their arguments sum to the growth of the counters.
"""
import glob

import jax
import pytest

from repro.core import permissive
from repro.models import ModelConfig, init_model
from repro.serve import spans
from repro.serve.engine import STEP_COUNTERS, Engine, Request, ServeConfig

CFG = ModelConfig(name="spans-t", family="dense", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab=64, head_dim=8,
                  scan_layers=False, remat=False)
CHILDREN = ("admit", "prefill", "install", "decode", "sync", "deliver")


@pytest.fixture(scope="module")
def engine():
    """The tiny dense paged engine: 2 slots, chunks of 4 prompt tokens."""
    params = init_model(jax.random.PRNGKey(0), CFG, permissive())
    eng = Engine(CFG, permissive(), params,
                 ServeConfig(max_slots=2, max_len=32, prefill_chunk=4))
    assert eng.stats()["kv_page_size"] > 0                  # paged
    return eng


def counters(eng) -> dict:
    s = eng.stats()
    return {k: s[k] for k in STEP_COUNTERS}


class _Refused:
    """An annotation that may not be built."""

    @staticmethod
    def is_enabled():
        return False

    def __init__(self, *a, **kw):
        raise AssertionError("a TraceAnnotation was built with the "
                             "profiler off")


def test_span_off_is_the_shared_null_context(monkeypatch, engine):
    monkeypatch.setattr(spans, "TraceAnnotation", _Refused)
    a, b = spans.span("engine.step"), spans.span("engine.decode", live=3)
    assert a is b
    with a as sp:
        sp.set_metadata(admitted=1)
    # a whole generate() builds none either
    engine.reset()
    out = engine.generate([Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=3),
                           Request(prompt=[6], max_new_tokens=2)])
    assert [len(t) for t in out] == [3, 2]


def test_span_on_is_a_prefixed_annotation(monkeypatch):
    made = []

    class Recording:
        @staticmethod
        def is_enabled():
            return True

        def __init__(self, name, **args):
            made.append((name, args))

    monkeypatch.setattr(spans, "TraceAnnotation", Recording)
    assert isinstance(spans.span("engine.decode", live=3, slots=8),
                      Recording)
    assert made == [("repro:engine.decode", {"live": 3, "slots": 8})]


def test_counters_by_hand_and_reset(engine):
    """3 requests of 3 prompt tokens and 4 new into 2 slots, chunk 4:
    the first two share steps 1-4, the third runs alone in steps 5-8."""
    engine.reset()
    assert set(counters(engine).values()) == {0}
    out = engine.generate([Request(prompt=[1, 2, 3], max_new_tokens=4)
                           for _ in range(3)])
    assert [len(t) for t in out] == [4, 4, 4]
    assert counters(engine) == {
        "steps": 8, "prefill_chunks": 3, "prefill_tokens": 9,
        "prefill_bucket_tokens": 12,            # 3 tokens pad to bucket 4
        "installs": 3, "decode_steps": 8,
        "decode_live_slot_rows": 2 * 4 + 1 * 4,
        "decode_kv_pages": 2 * 4 + 1 * 4,       # lengths 4-7: one page each
        "decode_sampled_steps": 0,              # all greedy
        "tokens_emitted": 12, "retires": 3}
    engine.reset()
    assert set(counters(engine).values()) == {0}


def test_sampled_steps_count_the_mix(engine):
    """Greedy A (4 new), sampled B (2 new), greedy C (4 new), 3 prompt
    tokens each, 2 slots: A and B decode in steps 1-2, B retires, C takes
    its slot from step 3.  Only steps 1-2 hold a sampling slot."""
    engine.reset()
    out = engine.generate([
        Request(prompt=[1, 2, 3], max_new_tokens=4),
        Request(prompt=[4, 5, 6], max_new_tokens=2, temperature=0.9,
                top_k=5, seed=3),
        Request(prompt=[7, 8, 9], max_new_tokens=4)])
    assert [len(t) for t in out] == [4, 2, 4]
    c = counters(engine)
    assert (c["decode_steps"], c["decode_sampled_steps"]) == (6, 2)
    engine.reset()


def _program_spans(logdir) -> list:
    """The ``repro:`` host events of the session: [name, start, end, args]."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [[e.name[len(spans.PREFIX):], e.start_ns,
                     e.start_ns + e.duration_ns, dict(e.stats)]
                    for e in line.events if e.name.startswith(spans.PREFIX)]
    return out


@pytest.fixture(scope="module")
def session(engine, tmp_path_factory):
    """A profiler session around generate(): prompts of 1-9 tokens (one to
    three chunks, some bucket-padded), more requests than slots."""
    engine.reset()
    engine.generate([Request(prompt=[7], max_new_tokens=1)])   # warm
    before = counters(engine)
    prompts = [list(range(1, 1 + n)) for n in (9, 3, 6, 1, 5)]
    rids = list(range(engine.sched._next_rid,
                      engine.sched._next_rid + len(prompts)))
    logdir = tmp_path_factory.mktemp("profile")
    jax.profiler.start_trace(str(logdir))
    try:                            # the 6-token prompt's request samples
        engine.generate([Request(prompt=p, max_new_tokens=3,
                                 temperature=0.8 if len(p) == 6 else 0.0,
                                 seed=len(p))
                         for p in prompts])
    finally:
        jax.profiler.stop_trace()
    after = counters(engine)
    growth = {k: after[k] - before[k] for k in STEP_COUNTERS}
    return _program_spans(logdir), growth, dict(zip(rids, prompts))


def _inside(s, outer) -> bool:
    return outer[1] <= s[1] and s[2] <= outer[2]


def test_every_step_holds_its_phases(session):
    sp, growth, _ = session
    steps = [s for s in sp if s[0] == "engine.step"]
    assert len(steps) == growth["steps"] > 0
    by_name = {c: [s for s in sp if s[0] == f"engine.{c}"]
               for c in CHILDREN + ("retire",)}
    for c, children in by_name.items():
        assert children, c
        for s in children:                      # each in exactly one step
            assert sum(_inside(s, st) for st in steps) == 1, (c, s)
    for st in steps:
        mine = {c: [s for s in by_name[c] if _inside(s, st)]
                for c in CHILDREN}
        assert len(mine["admit"]) == 1
        assert len(mine["decode"]) == len(mine["sync"]) \
            == len(mine["deliver"]) <= 1
        # in the code's order: admit, prefill/install, decode, sync, deliver
        order = [mine[c][0][1] for c in ("admit", "decode", "sync",
                                         "deliver") if mine[c]]
        assert order == sorted(order)
    for s in by_name["retire"]:
        assert sum(_inside(s, d) for d in by_name["deliver"]) == 1


def test_span_arguments_sum_to_the_counters(session):
    sp, growth, _ = session

    def total(name, arg):
        return sum(s[3][arg] for s in sp if s[0] == f"engine.{name}")

    def count(name):
        return sum(1 for s in sp if s[0] == f"engine.{name}")

    assert count("prefill") == growth["prefill_chunks"]
    assert total("prefill", "tokens") == growth["prefill_tokens"]
    assert total("prefill", "bucket") == growth["prefill_bucket_tokens"]
    assert total("prefill", "bucket") > total("prefill", "tokens")
    assert count("install") == growth["installs"] == total("admit",
                                                           "admitted") == 5
    assert count("decode") == growth["decode_steps"]
    assert total("decode", "live") == growth["decode_live_slot_rows"]
    assert total("decode", "kv_pages") == growth["decode_kv_pages"]
    # one sampling request of 3 new tokens: live in 3 decode steps
    assert total("decode", "sampled") == 3
    assert sum(1 for s in sp if s[0] == "engine.decode"
               and s[3]["sampled"] > 0) == growth["decode_sampled_steps"] == 3
    assert {s[3]["slots"] for s in sp if s[0] == "engine.decode"} == {2}
    assert total("deliver", "emitted") == growth["tokens_emitted"] == 15
    assert total("deliver", "finished") == count("retire") \
        == growth["retires"] == 5


def test_prefill_and_install_spans_carry_their_rid(session):
    sp, _, prompts = session
    for rid, prompt in prompts.items():
        chunks = [s[3] for s in sp
                  if s[0] == "engine.prefill" and s[3]["rid"] == rid]
        assert sum(c["tokens"] for c in chunks) == len(prompt)
        assert len({c["slot"] for c in chunks}) == 1
        (install,) = [s[3] for s in sp
                      if s[0] == "engine.install" and s[3]["rid"] == rid]
        assert install["plen"] == len(prompt)
        assert install["slot"] == chunks[0]["slot"]


def test_one_transfer_per_step_while_traced(engine, monkeypatch, tmp_path):
    """The spans add no device→host transfer: traced, a step that decodes
    still calls ``jax.device_get`` once."""
    engine.reset()
    for _ in range(3):
        engine.submit(Request(prompt=[1, 2], max_new_tokens=3))
    calls = [0]
    real = jax.device_get

    def counting(x):
        calls[0] += 1
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    jax.profiler.start_trace(str(tmp_path))
    try:
        steps = 0
        while engine.pending():
            calls[0] = 0
            engine.step()
            steps += 1
            assert calls[0] == 1, (steps, calls[0])
            assert steps < 50
    finally:
        jax.profiler.stop_trace()
    assert steps > 1
