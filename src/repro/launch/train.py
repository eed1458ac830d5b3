"""Production QFT training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b \
        --steps 6000 --ckpt-dir /ckpt/qwen3-8b-w4a8 [--smoke]

Builds the sharded QFT train step (teacher + student + Adam;
``ShardedQFT``) on a mesh of the devices present (16-way model parallel at
most, data parallel over the rest), wires the elastic runner
(checkpoint/restart, straggler timeout) and the seekable calibration
pipeline, and runs the paper's recipe (12 epochs over ~8K sequences,
cosine-reload LR).  ``--smoke`` runs the reduced config
through the staged pipeline — the CI path on a CPU host.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import get_config
from ..core import deployment_oriented, permissive
from ..core.qconfig import QuantConfig
from ..data.calib import CalibConfig, CalibDataset
from ..models import init_model, set_runtime
from ..models.config import ModelConfig
from ..pipeline import PipelineConfig, run_pipeline
from ..pipeline.adapters import resolve_quant_plan
from ..serve.spans import span
from ..sharding.collectives import collective_bytes
from ..sharding.partition import (ShardingPolicy, batch_shardings,
                                  opt_state_shardings, params_shardings)
from ..train.checkpoint import CheckpointManager
from ..train.elastic import ElasticConfig, ElasticRunner
from ..train.qft_trainer import QFTConfig, QFTTrainer
from .compile_cache import enable_compile_cache
from .mesh import make_elastic_mesh, make_production_mesh


class ShardedQFT:
    """Tensor-parallel QFT on ``mesh``: the shardings of teacher, student,
    Adam state and batch, and the jitted train step over them.

    The one place that readies a model for a mesh: ``cfg`` is padded for
    the ``model`` axis (``ModelConfig.with_padding``) and the residual
    stream is pinned to the data-parallel axes (``set_runtime``).  The step
    is compiled here for ``batch_like``, and ``collective_bytes`` counts,
    by kind, the bytes its collectives carry per device and step.  What it
    traces, it traces under ``jax.set_mesh(mesh)``; call ``step`` there
    too.

    The caller brings the state: ``place_teacher`` and ``place_student``
    put a teacher and a pre-QFT student where the step wants them (each a
    jitted init with these ``out_shardings`` lands there already, so no
    device ever holds a whole copy), or ``prepare_student`` runs the
    program's own pre-QFT step; ``init_opt`` makes the Adam state.  Each is
    a profiler span ``repro:qft.<phase>``."""

    def __init__(self, cfg: ModelConfig, qcfg: QuantConfig, mesh, batch_like,
                 pol: ShardingPolicy = ShardingPolicy(),
                 qft: QFTConfig = QFTConfig(), steps_per_epoch: int = 500):
        self.cfg = cfg = cfg.with_padding(tp=mesh.shape[pol.tp])
        set_runtime(act_spec=pol.dp)
        self.mesh, self.pol = mesh, pol
        # one resolved plan for init + finetune forward + (later) export: the
        # production path must train on the grid the artifact ships on
        self.trainer = QFTTrainer(cfg, qcfg, None, qft,
                                  steps_per_epoch=steps_per_epoch,
                                  plan=resolve_quant_plan(cfg, qcfg))
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        teacher = jax.eval_shape(lambda k: init_model(k, cfg, None), key)
        student = jax.eval_shape(lambda k: init_model(k, cfg, qcfg), key)
        self.teacher_sharding = self.shardings(teacher)
        self.student_sharding = self.shardings(student)
        self.opt_sharding = opt_state_shardings(self.student_sharding, mesh)
        self.batch_sharding = batch_shardings(batch_like, mesh, pol)
        self.step = self.jit(self.trainer.train_step)
        args = jax.tree.map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
            (student, jax.eval_shape(self.trainer.opt.init, student), teacher,
             batch_like),
            (self.student_sharding, self.opt_sharding, self.teacher_sharding,
             self.batch_sharding))
        with jax.set_mesh(mesh):
            self.compiled = self.step.lower(*args).compile()
        self.collective_bytes = collective_bytes(self.compiled.as_text())

    def shardings(self, like):
        """The layout of a parameter tree shaped like ``like``."""
        return params_shardings(like, self.cfg, self.mesh, self.pol)

    def jit(self, train_step):
        """``train_step(student, opt_state, teacher, batch) -> (student,
        opt_state, {"loss", "grad_norm"})`` jitted over these shardings,
        student and opt_state donated."""
        rep = NamedSharding(self.mesh, P())
        return jax.jit(train_step,
                       in_shardings=(self.student_sharding, self.opt_sharding,
                                     self.teacher_sharding,
                                     self.batch_sharding),
                       out_shardings=(self.student_sharding,
                                      self.opt_sharding,
                                      {"loss": rep, "grad_norm": rep}),
                       donate_argnums=(0, 1))

    def place_teacher(self, teacher):
        with span("qft.teacher"):
            return jax.device_put(teacher, self.teacher_sharding)

    def place_student(self, student):
        with span("qft.student"):
            return jax.device_put(student, self.student_sharding)

    def prepare_student(self, key, teacher, calib: list[dict]):
        """The program's pre-QFT step (calibration, MMSE scales) on a placed
        ``teacher``, landing in the student's shardings."""
        with span("qft.student"), jax.set_mesh(self.mesh):
            return jax.jit(lambda k, t, c: self.trainer.prepare_student(
                k, c, teacher=t), out_shardings=self.student_sharding)(
                    key, teacher, calib)

    def init_opt(self, student):
        with span("qft.opt_init"), jax.set_mesh(self.mesh):
            return jax.jit(self.trainer.opt.init,
                           out_shardings=self.opt_sharding)(student)


def random_state(qft: ShardedQFT, calib: list[dict]):
    """A ``PRNGKey(0)`` teacher, the program's pre-QFT student over
    ``calib`` (``PRNGKey(1)``) and its Adam state, placed for ``qft.step``.
    Returns ``(student, opt_state, teacher)``."""
    teacher = qft.place_teacher(jax.jit(
        lambda k: init_model(k, qft.cfg, None),
        out_shardings=qft.teacher_sharding)(jax.random.PRNGKey(0)))
    student = qft.prepare_student(jax.random.PRNGKey(1), teacher, calib)
    return student, qft.init_opt(student), teacher


def batch_like(batch: dict) -> dict:
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        batch)


def sharded_qft(cfg: ModelConfig, qcfg: QuantConfig, mesh, calib: list[dict],
                pol: ShardingPolicy = ShardingPolicy(), cle: bool = False,
                steps_per_epoch: int = 500):
    """Sharded QFT state and step on ``mesh`` (call under ``jax.set_mesh``)
    from ``random_state``; ``calib``'s batches have the training batch's
    shape.  Returns ``(student, opt_state, teacher, step)`` with
    ``step(student, opt_state, teacher, batch) -> (student, opt_state,
    {"loss", "grad_norm"})``; student and opt_state are donated."""
    qft = ShardedQFT(cfg, qcfg, mesh, batch_like(calib[0]), pol=pol,
                     qft=QFTConfig(cle_init=cle),
                     steps_per_epoch=steps_per_epoch)
    return (*random_state(qft, calib), qft.step)


def calib_batches(data: CalibDataset, n: int = 4) -> list[dict]:
    it = iter(data)
    return [{k: jnp.asarray(v) for k, v in next(it).items()} for _ in range(n)]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=6000)   # 12 epochs × 500
    ap.add_argument("--mode", choices=["w4a8", "w4chw"], default="w4a8")
    ap.add_argument("--cle", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/qft_ckpt")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        # CI / laptop path: the same staged pipeline as `python -m repro
        # quantize`, per-stage checkpoints under --ckpt-dir
        pcfg = PipelineConfig(
            arch=args.arch, mode=args.mode, smoke=True, cle=args.cle,
            steps=min(args.steps, 50), workdir=args.ckpt_dir,
            calib_samples=512, calib_seq_len=64, calib_batch_size=8)
        result = run_pipeline(pcfg, log=lambda s: print(f"  {s}"))
        ft = result.metrics.get("finetune")
        if ft:
            print(f"smoke done: loss {ft['final_loss']:.4f}")
        return

    qcfg = deployment_oriented() if args.mode == "w4a8" else permissive()
    mesh = (make_production_mesh(multi_pod=True) if args.multi_pod
            else make_elastic_mesh(jax.device_count()))
    cfg = get_config(args.arch)
    pol = ShardingPolicy(
        dp=("pod", "data") if args.multi_pod else ("data",))

    data = CalibDataset(CalibConfig(n_samples=8192, seq_len=512,
                                    batch_size=16, vocab=cfg.vocab))
    ckpt = CheckpointManager(args.ckpt_dir, keep=3)

    # ---- sharded elastic path ----
    with jax.set_mesh(mesh):
        student, opt_state, teacher, jitted = sharded_qft(
            cfg, qcfg, mesh, calib_batches(data), pol=pol, cle=args.cle,
            steps_per_epoch=data.steps_per_epoch)

        def build_step(mesh_):
            def step(state, batch):
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
                st, op, m = jitted(state[0], state[1], teacher, batch)
                return (st, op), m
            return step

        runner = ElasticRunner(build_step, ckpt,
                               ElasticConfig(checkpoint_every=200))
        (student, opt_state), done = runner.run((student, opt_state), data,
                                                steps=args.steps)
        print(f"trained to step {done}; restarts={runner.restarts}")


if __name__ == "__main__":
    main()
