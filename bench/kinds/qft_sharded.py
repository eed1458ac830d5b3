"""Tensor-parallel QFT traffic: the program's sharded QFT step
(``launch/train.ShardedQFT``) on a mesh of the cell's chips, driven as
``bench/kinds/qft.py`` drives the one-chip step.

Set-up makes the teacher's weights from the seed and the reference's
pre-QFT student (``reference.init_weights``, ``reference.init_student``),
each in one jitted call whose outputs land in the program's layout, so no
device holds a whole copy.  The program pads the vocabulary for the mesh
(``ModelConfig.with_padding``); the rows after the configuration's are
zero, no token id draws them, and they stay zero.  The first
``check_steps`` steps are read for the comparison, the window runs two
steps in flight, and then, with the program's state freed, the reference
runs the same steps on the same mesh: its plain-``jnp`` functions jitted
over sharded inputs.  Both sides are read on the real rows only, and
``compare`` of ``bench/kinds/qft.py`` decides.

The pre-QFT student is made again from the seed wherever a reading needs
it, by the same compiled call, so it is the same state bit for bit and no
host copy of ~8 GB is kept.
"""
from __future__ import annotations

import functools
import gc
import time

import numpy as np

from .. import reference
from ..harness import BenchError, Check, log
from ..program import same_tree
from .qft import Setup, _leaf_names, compare

def _vocab_axis(path) -> int | None:
    """Where a leaf holds one entry per vocabulary row: the embedding's rows
    (its weights and per-row scales) and the lm_head's columns."""
    keys = [getattr(k, "key", None) for k in path]
    if keys[0] == "embed":
        return 0
    if keys[0] == "lm_head" and keys[-1] == "w":
        return 1
    return None


def pad_rows(tree, rows: int):
    """``rows`` zero rows after the vocabulary's, in every leaf that has
    one entry per vocabulary row."""
    import jax
    import jax.numpy as jnp

    def pad(path, x):
        axis = _vocab_axis(path)
        if axis is None or rows == 0:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, rows)
        return jnp.pad(x, widths)

    return jax.tree_util.tree_map_with_path(pad, tree)


def real_rows(tree, vocab: int):
    """``tree`` with only the first ``vocab`` vocabulary rows."""
    import jax

    def cut(path, x):
        axis = _vocab_axis(path)
        return x if axis is None else jax.lax.slice_in_dim(x, 0, vocab,
                                                           axis=axis)

    return jax.tree_util.tree_map_with_path(cut, tree)


def padding_max(tree, vocab: int):
    """The largest magnitude in the rows after the first ``vocab``."""
    import jax
    import jax.numpy as jnp
    out = jnp.float32(0)
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        axis = _vocab_axis(path)
        if axis is not None and x.shape[axis] > vocab:
            pad = jax.lax.slice_in_dim(x, vocab, x.shape[axis], axis=axis)
            out = jnp.maximum(out, jnp.max(jnp.abs(pad)))
    return out


class ShardedSetup(Setup):
    """``Setup`` of a QFT cell on a mesh: the program's sharded step and the
    layouts of both sides' trees."""

    def __init__(self, cell, seed: int):
        super().__init__(cell, seed)
        import jax
        from repro.launch.mesh import make_elastic_mesh
        from repro.launch.train import ShardedQFT
        from repro.models import init_model
        from repro.train.qft_trainer import QFTConfig
        shape = cell.config["mesh"]
        self.mesh = make_elastic_mesh(shape["data"] * shape["model"],
                                      model_parallel=shape["model"])
        if dict(self.mesh.shape) != {a: shape[a] for a in ("data", "model")}:
            raise BenchError(f"mesh {dict(self.mesh.shape)} is not the "
                             f"configuration's {shape}")
        tr = self.tr
        self.sharded = ShardedQFT(
            self.mcfg, self.qcfg, self.mesh,
            {"tokens": jax.ShapeDtypeStruct((self.B, self.S), np.int32)},
            qft=QFTConfig(ce_proportion=tr["ce_proportion"],
                          base_lr=tr["base_lr"]),
            steps_per_epoch=tr["steps_per_epoch"])
        padded = self.sharded.cfg
        if ((padded.n_heads_padded, padded.n_kv_heads_padded)
                != (self.mcfg.n_heads, self.mcfg.n_kv_heads)):
            raise BenchError(f"the mesh pads the heads to "
                             f"{padded.n_heads_padded}/"
                             f"{padded.n_kv_heads_padded}; the reference "
                             f"has {self.mcfg.n_heads}/{self.mcfg.n_kv_heads}")
        self.pad = padded.vocab_padded - self.c["V"]
        log(f"mesh {dict(self.mesh.shape)}; vocabulary {self.c['V']} rows, "
            f"{self.pad} padding rows; collective bytes a step "
            f"{self.sharded.collective_bytes}")
        key = jax.ShapeDtypeStruct((2,), np.uint32)
        self.teacher_like = jax.eval_shape(
            lambda k: init_model(k, padded, None), key)
        self.student_like = jax.eval_shape(
            lambda k: init_model(k, padded, self.qcfg), key)
        c, q, V = self.c, self.q, self.c["V"]
        # the reference's trees, at the real rows, in the program's layout
        self.real_teacher_sharding = self.sharded.shardings(jax.eval_shape(
            lambda k: reference.init_weights(c, k), key))
        self._teacher = jax.jit(lambda k: reference.init_weights(c, k),
                                out_shardings=self.real_teacher_sharding)
        self.real_student_like = jax.eval_shape(
            lambda k: real_rows(init_model(k, padded, self.qcfg), V), key)
        self.real_student_sharding = self.sharded.shardings(
            self.real_student_like)
        self._student = jax.jit(
            lambda t, cal: reference.init_student(t, c, q, cal),
            out_shardings=self.real_student_sharding)
        self._pad = {w: jax.jit(lambda t: pad_rows(t, self.pad),
                                out_shardings=sh)
                     for w, sh in (("teacher", self.sharded.teacher_sharding),
                                   ("student", self.sharded.student_sharding))}
        self._real_norms = jax.jit(lambda t: reference.leaf_norms(
            real_rows(t, V)))
        self._change_norms = jax.jit(lambda a, b: reference.leaf_norms(
            jax.tree.map(lambda x, y: x - y, real_rows(a, V), b)))
        self._pad_max = jax.jit(lambda t: padding_max(t, V))
        self.reference = {lowp: ReferenceSteps(c, q, self.opt,
                                               self.real_student_sharding,
                                               lowp)
                          for lowp in (False, True)}

    def real_state(self):
        """The seed's teacher and pre-QFT student at the real rows, sharded."""
        teacher = self._teacher(reference.seed_key(self.seed))
        return teacher, self._student(teacher, self.calib)

    def program_state(self):
        """The same, padded to the program's rows and placed for its step."""
        teacher, student = self.real_state()
        teacher_p = self.sharded.place_teacher(self._pad["teacher"](teacher))
        del teacher
        student_p = self.sharded.place_student(self._pad["student"](student))
        del student
        same_tree(teacher_p, self.teacher_like, "teacher")
        same_tree(student_p, self.student_like, "student")
        return teacher_p, student_p

    def change_norms(self, params) -> np.ndarray:
        """Per leaf, the norm of ``params`` (real rows) less the seed's
        pre-QFT student."""
        teacher, p0 = self.real_state()
        del teacher
        return np.asarray(self._change_norms(params, p0), np.float64)

    def reference_readings(self, lowp: bool = False):
        """The reference's ``check_steps`` steps from the seed's pre-QFT
        student, on the mesh."""
        teacher, student = self.real_state()
        batches = [self.batch(i) for i in range(self.tr["check_steps"])]
        losses, g1n, params = self.reference[lowp](student, teacher,
                                                   batches)
        del teacher, student
        change = self.change_norms(params)
        del params
        gc.collect()
        return {"loss": losses, "grad_norms": np.asarray(g1n, np.float64),
                "change_norms": change}


class ReferenceSteps:
    """``reference.make_qft_reference``'s steps, with every gradient and
    Adam state kept in the student's ``sharding``: the same sums in the
    same order (a batch's gradient is the mean of its rows', one row at a
    time; Adam as the reference writes it).  Left to XLA, the lm_head's
    gradient, a zero the loss never reaches, comes out whole on every
    device, and three such copies overflow a chip.  Each jitted piece is an
    attribute, so a warm-up can compile it ahead."""

    def __init__(self, c, q, opt, sharding, lowp: bool = False):
        import jax
        import jax.numpy as jnp
        self.opt = opt
        rep = jax.sharding.NamedSharding(jax.tree.leaves(sharding)[0].mesh,
                                         jax.sharding.PartitionSpec())
        self.grad_row = jax.jit(jax.value_and_grad(
            lambda s, t, tok: reference.qft_loss(s, t, tok, c, q, lowp)),
            out_shardings=(rep, sharding))
        self.scale = jax.jit(lambda g, n: jax.tree.map(lambda a: a / n, g),
                             out_shardings=sharding, donate_argnums=0)
        self.add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                           out_shardings=sharding, donate_argnums=0)
        self.zeros = jax.jit(lambda s: jax.tree.map(jnp.zeros_like, s),
                             out_shardings=sharding)

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                           out_shardings=(sharding,) * 3)
        def adam(params, m, v, g, step, lr):
            b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
            m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
            v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
            upd = lambda p, m, v: p - lr * (m / (1 - b1 ** step)) / (
                jnp.sqrt(v / (1 - b2 ** step)) + eps)
            return jax.tree.map(upd, params, m, v), m, v

        self.adam = adam

    def __call__(self, student, teacher, batches):
        """Steps over ``batches`` (each [B, S]); returns the per-step
        losses, each leaf's norm of the first step's gradient, and the final
        parameters."""
        opt = self.opt
        m, v = self.zeros(student), self.zeros(student)
        losses, first = [], None
        for i, batch in enumerate(batches, start=1):
            loss, grad = 0.0, None
            for row in batch:              # the batch mean, one row at a time
                l, g = self.grad_row(student, teacher, row)
                loss = loss + l / len(batch)
                g = self.scale(g, len(batch))
                grad = g if grad is None else self.add(grad, g)
            losses.append(float(loss))
            if first is None:
                first = reference.leaf_norms(grad)
            lr = reference.cosine_reload_lr(i, opt["base_lr"],
                                            opt["steps_per_cycle"])
            student, m, v = self.adam(student, m, v, grad, i, lr)
        return losses, first, student


def first_steps(st: ShardedSetup, step, student, opt_state, teacher):
    """Drive the program's step through the first ``check_steps`` steps.
    Returns the readings, the largest entry of the padding rows after them,
    and the state to hand on to the window."""
    import jax
    losses, g1n = [], None
    for i in range(st.tr["check_steps"]):
        batch = jax.device_put({"tokens": st.batch(i)},
                               st.sharded.batch_sharding)
        student, opt_state, m = step(student, opt_state, teacher, batch)
        losses.append(float(m["loss"]))
        if i == 0:   # the first gradient, from Adam's state: m = (1-b1) g
            g1n = (np.asarray(st._real_norms(opt_state["m"]), np.float64)
                   / (1.0 - st.sharded.trainer.opt.b1))
    readings = {"loss": losses, "grad_norms": g1n,
                "change_norms": st.change_norms(student)}
    return readings, float(st._pad_max(student)), student, opt_state


def run(rt, variant: str | None = None) -> dict:
    """One run of a sharded QFT cell.  ``variant`` (calibration and tests
    only): ``"control"`` puts the low-precision reference in the program's
    place; ``"unchanged"`` and ``"half_batch"`` plant a fault in the
    step."""
    import jax
    st = ShardedSetup(rt.cell, rt.seed)
    with jax.set_mesh(st.mesh):
        return _run(rt, st, variant)


def _run(rt, st: ShardedSetup, variant: str | None) -> dict:
    import jax
    tr, sharded = st.tr, st.sharded
    train_step = sharded.trainer.train_step
    step = sharded.step
    if variant == "unchanged":
        step = sharded.jit(lambda s, o, t, b: (s, o, train_step(s, o, t,
                                                                b)[2]))
    elif variant == "half_batch":
        step = sharded.jit(lambda s, o, t, b: train_step(
            s, o, t, {"tokens": b["tokens"][:st.B // 2]}))
    names = _leaf_names(st.real_student_like)
    if variant == "control":     # no window: the readings are all it needs
        prog = st.reference_readings(lowp=True)
        ref = st.reference_readings()
        return {"checks": compare(prog, ref, tr["limits"], names),
                "attempted": tr["check_steps"], "failed": 0,
                "end_to_end": {}, "memory_peak_bytes": rt.memory_peak(),
                "counts": {}}

    teacher, student = st.program_state()
    opt_state = sharded.init_opt(student)
    jax.block_until_ready(opt_state)
    log(f"set-up: weights and pre-QFT init at "
        f"{time.perf_counter() - rt.t_start!r} s")
    pool = [jax.device_put({"tokens": st.batch(tr["check_steps"] + i)},
                           sharded.batch_sharding)
            for i in range(tr["pool_batches"])]
    prog, pad_max, student, opt_state = first_steps(st, step, student,
                                                    opt_state, teacher)
    jax.block_until_ready(student)
    setup_s = time.perf_counter() - rt.t_start
    log(f"set-up {setup_s!r} s; first steps' losses {prog['loss']!r}")

    # ---- the window, as bench/kinds/qft.py runs it
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
    ref = st.reference_readings()
    log(f"reference: {time.perf_counter() - t_ref!r} s")
    checks = compare(prog, ref, tr["limits"], names)
    # the padding rows are zero and no token draws them: they stay zero
    checks.append(Check("pad_rows_max", pad_max, 0.0))
    ok_losses = all(np.isfinite(losses))
    return {"checks": checks, "attempted": n,
            "failed": 0 if ok_losses else 1,
            "end_to_end": {"qft_tokens_per_s": n * tokens / window,
                           "setup_s": setup_s},
            "memory_peak_bytes": peak,
            "counts": {"steps": n, "window_s": window, "dims": st.c,
                       "seq_len": st.S, "batch": st.B,
                       "ce_proportion": tr["ce_proportion"],
                       "collective_bytes": sharded.collective_bytes}}
