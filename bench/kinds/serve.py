"""Serving traffic: the program's continuous-batching ``Engine`` on an int4
artifact, driven through ``Engine.submit`` and ``Engine.step``.

Set-up makes the weights from the seed, builds the artifact the way the
pipeline does (``build_student`` -> MMSE ``init_scales`` ->
``export_for_layers``, in one jitted call; no finetune, no calibration:
serving reads neither), builds the engine, and warms every prefill bucket,
the install, the decode step and the retire by serving one short request
per bucket.  The window then offers the traffic's requests:

- ``"loop": "open"``: each request is submitted when it is due (seeded
  Poisson arrivals at a fixed rate), whatever the engine's state;
- ``"loop": "closed"``: the queue is kept at ``queue`` requests.

Requests due inside the window are timed from when they were due.  After
the window the open loop keeps offering load until every request due in
the window has finished.  Then a seeded sample of the finished requests,
the longest among them, is compared with the reference.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from .. import flops, reference, traffic as gen
from ..harness import Check, log, percentile
from ..program import (check_plan, import_program, model_config,
                       quant_config, same_tree)

#: how long after the window the loop waits for the window's requests
DRAIN_LIMIT_S = 60.0
#: the reference runs each sampled sequence right-padded to a multiple of
#: this (a few compiled lengths, none longer than needed by much)
PAD_TO = 1024


class Record:
    """One request's delivery, timed on the host's clock."""

    def __init__(self, req: dict, due: float, window: bool):
        self.plen = len(req["prompt"])
        self.prompt = req["prompt"]
        self.budget = req["max_new_tokens"]
        self.due = due
        self.window = window
        self.tokens: list[int] = []
        self.times: list[float] = []
        self.done = False

    def on_token(self, token: int, fin: bool) -> None:
        self.times.append(time.perf_counter())
        self.tokens.append(token)
        self.done = fin


class Setup:
    def __init__(self, cell, seed: int):
        import_program()
        import jax
        from repro.models import init_model
        from repro.pipeline.adapters import resolve_quant_plan
        self.tr = tr = cell.traffic
        self.c = reference.dims(cell.config["model"])
        self.mcfg = model_config(cell.config)
        self.qcfg = quant_config(tr["quant"])
        self.qplan = resolve_quant_plan(self.mcfg, self.qcfg)
        check_plan(self.qplan, tr["quant"])
        self.q = {"w_bits": tr["quant"]["w_bits"],
                  "embed_bits": tr["quant"]["embed_bits"]}
        self.seed = seed
        self.teacher_like = jax.eval_shape(
            lambda k: init_model(k, self.mcfg, None),
            jax.ShapeDtypeStruct((2,), np.uint32))

    def engine(self):
        import jax
        from repro.serve.deploy import export_for_layers, make_deploy_plan
        from repro.serve.engine import Engine, ServeConfig
        from repro.train.qft_trainer import build_student, init_scales
        mcfg, qcfg, qplan = self.mcfg, self.qcfg, self.qplan
        plan = make_deploy_plan(qcfg, arch=mcfg.name, family=mcfg.family,
                                use_pallas=True, quant_plan=qplan)
        teacher = reference.make_weights(self.c, self.seed)
        same_tree(teacher, self.teacher_like, "weights")
        # the key is an argument: a constant would make a program per seed
        artifact = jax.jit(lambda t, key: export_for_layers(init_scales(
            build_student(key, mcfg, qcfg, t), mcfg, qcfg, plan=qplan),
            plan))(teacher, reference.seed_key(self.seed))
        del teacher
        tr = self.tr
        scfg = ServeConfig(max_slots=tr["max_slots"], max_len=tr["max_len"],
                           prefill_chunk=tr["prefill_chunk"],
                           kv_mode=tr["kv_mode"])
        serve_cfg = dataclasses.replace(mcfg, scan_layers=False, remat=False)
        return Engine.from_artifact(serve_cfg, plan, artifact, scfg)

    def warm(self, engine) -> None:
        """Compile every shape the window uses: one request per prefill
        bucket, each through install, decode and retire."""
        from repro.serve.engine import Request
        from repro.serve.kv_cache import prefill_buckets
        r = gen.rng(self.seed, 9)
        reqs = [Request(prompt=r.integers(0, self.c["V"], b).tolist(),
                        max_new_tokens=2)
                for b in prefill_buckets(self.tr["prefill_chunk"])]
        engine.generate(reqs)
        engine.reset()

    def reference_gaps(self, records, lowp: bool = False) -> np.ndarray:
        """Every served token's gap below the reference's best logit, over
        the sampled requests (with ``lowp``, the gap of the token the fp8
        forward puts first)."""
        import jax
        import jax.numpy as jnp
        c, T = self.c, self.tr["max_len"]
        teacher = reference.make_weights(c, self.seed)
        params = jax.jit(lambda t: reference.quantize_for_serving(
            t, c, self.q))(teacher)
        del teacher
        gap = reference.make_served_gap(c, lowp=lowp)
        out = []
        for rec in records:
            seq = rec.prompt + rec.tokens[:-1]
            pad = min(-(-len(seq) // PAD_TO) * PAD_TO, T)
            padded = jnp.asarray(seq + [0] * (pad - len(seq)), jnp.int32)
            out.append(gap(params, padded, rec.plen, rec.tokens))
        gaps = np.concatenate(out) if out else np.full(1, np.inf)
        log(f"reference{' (fp8 control)' if lowp else ''}: "
            f"{len(records)} requests, {gaps.size} served tokens; gap "
            f"mean {float(gaps.mean())!r}, widest {float(gaps.max())!r}")
        return gaps


def _sample(records, n: int, seed: int):
    """``n`` finished window requests drawn from the seed, the longest
    always among them."""
    done = [r for r in records if r.window and r.done]
    if not done:
        return []
    longest = max(done, key=lambda r: r.plen + len(r.tokens))
    rest = [r for r in done if r is not longest]
    pick = gen.rng(seed, 7).permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


def drive(rt, st: Setup, engine) -> dict:
    """The window; returns the records and what the metrics need."""
    from repro.serve.engine import Request
    tr = st.tr
    open_loop = tr["loop"] == "open"
    n = (gen.open_loop_count(tr, rt.seconds) if open_loop
         else tr["requests"])
    reqs = gen.requests(tr, rt.seed, n, st.c["V"], rt.seconds)
    n_win = gen.window_count(tr, rt.seconds) if open_loop else n
    records, late, steps = [], [], 0
    i = 0
    with rt.tracer.window():
        t0 = time.perf_counter()
        end = t0 + rt.seconds
        while True:
            now = time.perf_counter()
            if open_loop:
                while i < n and t0 + reqs[i]["due"] <= now:
                    rec = Record(reqs[i], t0 + reqs[i]["due"], i < n_win)
                    engine.submit(Request(prompt=rec.prompt,
                                          max_new_tokens=rec.budget),
                                  on_token=rec.on_token)
                    late.append(now - rec.due)
                    records.append(rec)
                    i += 1
            else:
                while engine.pending() < tr["queue"] and now < end:
                    req = reqs[i % n]
                    rec = Record(req, now, True)
                    engine.submit(Request(prompt=rec.prompt,
                                          max_new_tokens=rec.budget),
                                  on_token=rec.on_token)
                    records.append(rec)
                    i += 1
            if open_loop:
                if all(r.done for r in records[:n_win]) and i >= n_win:
                    break
                if now > end + DRAIN_LIMIT_S:
                    break
            elif now >= end:
                break
            if engine.pending():
                with rt.spans.span("engine.step"):
                    engine.step()
                steps += 1
            elif i < n:
                time.sleep(max(min(t0 + reqs[i]["due"] - now, 0.002), 0.0))
            else:
                break
        t_stop = time.perf_counter()
    return {"records": records, "late": late, "steps": steps, "t0": t0,
            "end": end, "t_stop": t_stop, "n_win": n_win}


def summarize(rt, st: Setup, w: dict) -> tuple[dict, dict, int]:
    """End-to-end metrics, counts for the per-layer readers, and the
    number of window requests that never finished."""
    win = [r for r in w["records"] if r.window]
    # an open loop waits for every request due in its window: one that
    # never finished is missing; a closed loop's requests still in flight
    # when the window closes are not
    missing = (sum(1 for r in win if not r.done)
               if st.tr["loop"] == "open" else 0)
    stop = w["t_stop"]
    ttft = [1e3 * ((r.times[0] if r.times else stop) - r.due) for r in win]
    itl = [1e3 * (b - a) for r in win for a, b in zip(r.times, r.times[1:])]
    delivered = sum(1 for r in w["records"] for t in r.times
                    if w["t0"] <= t <= w["end"])
    e2e = {"ttft_p95_ms": percentile(ttft, 95),
           "itl_p95_ms": percentile(itl, 95) if itl else float("nan"),
           "output_tokens_per_s": delivered / (w["end"] - w["t0"])}
    log(f"window: {len(win)} requests, {missing} unfinished, "
        f"{w['steps']} engine steps, {delivered} tokens in the window; "
        f"ttft ms median {percentile(ttft, 50)!r} p95 {e2e['ttft_p95_ms']!r}"
        f" ({len(ttft)} samples); itl ms median "
        f"{percentile(itl, 50) if itl else None!r} p95 {e2e['itl_p95_ms']!r}"
        f" ({len(itl)} samples)")
    if w["late"]:
        log(f"generator lateness s: median {percentile(w['late'], 50)!r} "
            f"max {max(w['late'])!r}")
    # work of the engine's steps: prompts of requests that got their first
    # token, and every emitted token's forward at its context length
    c = st.c
    chunk = st.tr["prefill_chunk"]
    ops, lengths = 0, []
    for r in w["records"]:
        if not r.times:
            continue
        for off in range(0, r.plen, chunk):
            ops += flops.prefill_chunk(c, off, min(chunk, r.plen - off))
        for j in range(len(r.times)):
            ops += flops.decode_token(c, r.plen + j + 1)
            lengths.append(r.plen + j + 1)
    counts = {"dims": c, "model_ops": ops, "decode_lengths": lengths}
    return e2e, counts, missing


def run(rt, variant: str | None = None) -> dict:
    """One run of a serving cell.  ``variant`` (calibration and tests
    only): ``"control"`` puts the fp8 reference in the program's place, so
    the compared gaps are those of the fp8 forward's picks; ``"altered"``
    plants a fault: every emitted token is shifted by one."""
    import jax
    st = Setup(rt.cell, rt.seed)
    engine = st.engine()
    log(f"set-up: engine built at {time.perf_counter() - rt.t_start!r} s")
    st.warm(engine)
    if variant == "altered":
        decode, V = engine._decode, st.c["V"]

        def altered(params, cache, state):
            cache, state, emitted, emit = decode(params, cache, state)
            return cache, state, (emitted + 1) % V, emit
        engine._decode = altered
    jax.block_until_ready(engine.cache)
    setup_s = time.perf_counter() - rt.t_start
    log(f"set-up {setup_s!r} s; engine {engine.stats()}")

    w = drive(rt, st, engine)
    log(f"Engine.stats() after the window: {engine.stats()}")
    peak = rt.memory_peak()
    log(f"peak_bytes_in_use: {peak}")
    e2e, counts, missing = summarize(rt, st, w)
    e2e["setup_s"] = setup_s
    del engine
    gc.collect()

    t_ref = time.perf_counter()
    sample = _sample(w["records"], st.tr["check_requests"], rt.seed)
    # the control: the fp8 reference's picks stand in for the served tokens
    gaps = st.reference_gaps(sample, lowp=variant == "control")
    log(f"reference: longest request {sample[0].plen if sample else 0} + "
        f"{len(sample[0].tokens) if sample else 0} tokens; "
        f"{time.perf_counter() - t_ref!r} s")
    limit = st.tr["limits"]["served_logit_gap_mean"]
    checks = [Check("served_logit_gap_mean", float(gaps.mean()), limit)]
    return {"checks": checks, "attempted": len([r for r in w["records"]
                                                if r.window]),
            "failed": missing, "end_to_end": e2e,
            "memory_peak_bytes": peak, "counts": counts}
