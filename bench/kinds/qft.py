"""QFT finetune traffic: the program's jitted ``QFTTrainer.train_step`` with
donated state, driven on seeded calibration batches.

Set-up makes the teacher's weights from the seed, runs the pre-QFT step
(calibration and PPQ scales, ``reference.init_student``) to make the
student, compiles the step and drives it through the first
``check_steps`` steps: those are compared with the reference.  The window
then drives the same object on further batches, two steps in flight, and
reads the loss every ``log_every`` steps as a training loop would.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from .. import reference, traffic as gen
from ..harness import Check, log
from ..program import (check_plan, import_program, model_config,
                       quant_config, same_tree)

#: leaves whose reference gradient is under this share of the median
#: leaf's are nought to rounding (the lm_head and head stream, which the
#: hidden-state loss never reaches): left out of the per-leaf numbers
ZERO_GRAD_SHARE = 1e-3


def _leaf_names(tree):
    import jax
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def _leaf_norms(tree) -> np.ndarray:
    return np.asarray(reference.leaf_norms(tree), np.float64)


def _host_diff_norms(a, b) -> np.ndarray:
    """Per leaf, the norm of ``a - b`` (host copies; float32 differences
    of nearby values are exact)."""
    import jax
    return np.asarray([float(np.linalg.norm((np.asarray(x) - np.asarray(y))
                                            .ravel()))
                       for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))],
                      np.float64)


def gap_of_norms(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray):
    """Worst leaf: |‖prog‖ − ‖ref‖| over the larger of the reference
    leaf's norm and the median leaf's.  Returns (gap, leaf index)."""
    floor = float(np.median(ref[keep]))
    gaps = np.where(keep, np.abs(prog - ref) / np.maximum(ref, floor), 0.0)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


class Setup:
    """The cell's sizes and the program objects, shared by the run and
    the calibration tool."""

    def __init__(self, cell, seed: int):
        import_program()
        import jax
        from repro.models import init_model
        from repro.pipeline.adapters import resolve_quant_plan
        self.tr = tr = cell.traffic
        self.c = reference.dims(cell.config["model"])
        self.mcfg = model_config(cell.config)
        self.qcfg = quant_config(tr["quant"])
        self.plan = resolve_quant_plan(self.mcfg, self.qcfg)
        check_plan(self.plan, tr["quant"])
        self.q = {"w_bits": tr["quant"]["w_bits"],
                  "embed_bits": tr["quant"]["embed_bits"]}
        self.B, self.S = tr["batch"], tr["seq_len"]
        self.seed = seed
        key = jax.ShapeDtypeStruct((2,), np.uint32)
        self.teacher_like = jax.eval_shape(
            lambda k: init_model(k, self.mcfg, None), key)
        self.student_like = jax.eval_shape(
            lambda k: init_model(k, self.mcfg, self.qcfg), key)
        self.calib = gen.token_rows(seed, 2, tr["calib_batches"] * self.B,
                                    self.S, self.c["V"])
        n = tr["check_steps"] + tr["pool_batches"]
        self.rows = gen.token_rows(seed, 3, n * self.B, self.S, self.c["V"])
        self.opt = {"base_lr": tr["base_lr"], "b1": 0.9, "b2": 0.999,
                    "eps": 1e-8,
                    "steps_per_cycle": tr["steps_per_epoch"] * 4}

    def batch(self, i: int) -> np.ndarray:
        return self.rows[i * self.B:(i + 1) * self.B]

    def weights(self):
        teacher = reference.make_weights(self.c, self.seed)
        same_tree(teacher, self.teacher_like, "teacher")
        return teacher

    def student(self, teacher):
        import jax
        c, q = self.c, self.q
        student = jax.jit(lambda t, cal: reference.init_student(
            t, c, q, cal))(teacher, self.calib)
        same_tree(student, self.student_like, "student")
        return student

    def trainer(self):
        from repro.train.qft_trainer import QFTConfig, QFTTrainer
        return QFTTrainer(self.mcfg, self.qcfg, None,
                          QFTConfig(ce_proportion=self.tr["ce_proportion"],
                                    base_lr=self.tr["base_lr"]),
                          steps_per_epoch=self.tr["steps_per_epoch"],
                          plan=self.plan)

    def reference_readings(self, p0_host, lowp: bool = False):
        """The reference's ``check_steps`` steps from the same start."""
        import jax
        teacher = self.weights()
        student = jax.device_put(p0_host)
        run = reference.make_qft_reference(self.c, self.q, self.opt,
                                           lowp=lowp)
        batches = [self.batch(i) for i in range(self.tr["check_steps"])]
        losses, g1n, params = run(student, teacher, batches)
        del teacher
        p_host = jax.device_get(params)
        del params
        gc.collect()
        return {"loss": losses, "grad_norms": np.asarray(g1n, np.float64),
                "change_norms": _host_diff_norms(p_host, p0_host)}


def program_first_steps(st: Setup, step, student, opt_state, teacher,
                        b1: float):
    """Drive the program's step through the first ``check_steps`` steps.
    Returns the readings and the state to hand on to the window."""
    import jax
    p0_host = jax.device_get(student)
    losses, g1n = [], None
    for i in range(st.tr["check_steps"]):
        student, opt_state, m = step(student, opt_state, teacher,
                                     jax.device_put({"tokens": st.batch(i)}))
        losses.append(float(m["loss"]))
        if i == 0:   # the first gradient, from Adam's state: m = (1-b1) g
            g1n = _leaf_norms(opt_state["m"]) / (1.0 - b1)
    p_host = jax.device_get(student)
    readings = {"loss": losses, "grad_norms": g1n,
                "change_norms": _host_diff_norms(p_host, p0_host)}
    del p_host
    return readings, p0_host, student, opt_state


def compare(prog: dict, ref: dict, limits: dict, names) -> list[Check]:
    keep = ref["grad_norms"] >= ZERO_GRAD_SHARE * np.median(ref["grad_norms"])
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"],
                                                       ref["loss"]))
    g_gap, gi = gap_of_norms(prog["grad_norms"], ref["grad_norms"], keep)
    c_gap, ci = gap_of_norms(prog["change_norms"], ref["change_norms"], keep)
    log(f"losses program {prog['loss']!r} reference {ref['loss']!r}")
    log(f"leaves left out (zero gradient): "
        f"{[n for n, k in zip(names, keep) if not k]}")
    log(f"worst gradient leaf {names[gi]}: program {prog['grad_norms'][gi]!r}"
        f" reference {ref['grad_norms'][gi]!r}")
    log(f"worst change leaf {names[ci]}: program "
        f"{prog['change_norms'][ci]!r} reference {ref['change_norms'][ci]!r}")
    return [Check("loss_rel_gap", loss_gap, limits["loss_rel_gap"]),
            Check("grad_norm_gap", g_gap, limits["grad_norm_gap"]),
            Check("change_norm_gap", c_gap, limits["change_norm_gap"])]


def run(rt, variant: str | None = None) -> dict:
    """One run of a QFT cell.  ``variant`` (calibration and tests only):
    ``"control"`` puts the low-precision reference in the program's place;
    ``"unchanged"`` and ``"half_batch"`` plant a fault in the step."""
    import jax
    st = Setup(rt.cell, rt.seed)
    tr = st.tr
    trainer = st.trainer()
    train_step = trainer.train_step
    if variant == "unchanged":
        train_step = (lambda s, o, t, b:
                      (s, o, trainer.train_step(s, o, t, b)[2]))
    elif variant == "half_batch":
        train_step = (lambda s, o, t, b: trainer.train_step(
            s, o, t, {"tokens": b["tokens"][:st.B // 2]}))
    step = jax.jit(train_step, donate_argnums=(0, 1))

    teacher = st.weights()
    student = st.student(teacher)
    jax.block_until_ready(student)
    log(f"set-up: weights and pre-QFT init at "
        f"{time.perf_counter() - rt.t_start!r} s")
    if variant == "control":     # no window: the readings are all it needs
        p0_host = jax.device_get(student)
        del student, teacher
        prog = st.reference_readings(p0_host, lowp=True)
        ref = st.reference_readings(p0_host)
        return {"checks": compare(prog, ref, tr["limits"],
                                  _leaf_names(p0_host)),
                "attempted": tr["check_steps"], "failed": 0,
                "end_to_end": {}, "memory_peak_bytes": rt.memory_peak(),
                "counts": {}}
    opt_state = trainer.opt.init(student)
    pool = [jax.device_put({"tokens": st.batch(tr["check_steps"] + i)})
            for i in range(tr["pool_batches"])]
    prog, p0_host, student, opt_state = program_first_steps(
        st, step, student, opt_state, teacher, trainer.opt.b1)
    jax.block_until_ready(student)
    setup_s = time.perf_counter() - rt.t_start
    log(f"set-up {setup_s!r} s; first steps' losses {prog['loss']!r}")

    # ---- the window
    tokens = st.B * st.S
    n, in_flight, losses = 0, [], []
    with rt.tracer.window():
        t0 = time.perf_counter()
        while True:
            with rt.spans.span("qft.step"):
                student, opt_state, m = step(student, opt_state, teacher,
                                             pool[n % len(pool)])
            n += 1
            in_flight.append(m["loss"])
            if len(in_flight) > 2:
                jax.block_until_ready(in_flight.pop(0))
            if n % tr["log_every"] == 0:
                with rt.spans.span("qft.loss_read"):
                    losses.append(float(m["loss"]))
            if time.perf_counter() - t0 >= rt.seconds:
                break
        jax.block_until_ready((student, opt_state))
        window = time.perf_counter() - t0
    log(f"window: {n} steps of {tokens} tokens in {window!r} s; "
        f"losses read {losses!r}")
    peak = rt.memory_peak()
    del student, opt_state, teacher, pool, m, in_flight
    gc.collect()

    t_ref = time.perf_counter()
    ref = st.reference_readings(p0_host)
    log(f"reference: {time.perf_counter() - t_ref!r} s")
    checks = compare(prog, ref, tr["limits"], _leaf_names(p0_host))
    ok_losses = all(np.isfinite(losses))
    return {"checks": checks, "attempted": n,
            "failed": 0 if ok_losses else 1,
            "end_to_end": {"qft_tokens_per_s": n * tokens / window,
                           "setup_s": setup_s},
            "memory_peak_bytes": peak,
            "counts": {"steps": n, "window_s": window, "dims": st.c,
                       "seq_len": st.S, "batch": st.B,
                       "ce_proportion": tr["ce_proportion"]}}

