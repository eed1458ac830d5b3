"""Production QFT training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b \
        --steps 6000 --ckpt-dir /ckpt/qwen3-8b-w4a8 [--smoke]

Builds the sharded QFT train step (teacher + student + Adam) on a mesh of
the devices present (16-way model parallel at most, data parallel over the
rest), wires the elastic runner (checkpoint/restart, straggler timeout) and
the seekable calibration pipeline, and runs the paper's recipe (12 epochs
over ~8K sequences, cosine-reload LR).  ``--smoke`` runs the reduced config
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
from ..sharding.partition import (ShardingPolicy, opt_state_shardings,
                                  params_shardings)
from ..train.checkpoint import CheckpointManager
from ..train.elastic import ElasticConfig, ElasticRunner
from ..train.qft_trainer import QFTConfig, QFTTrainer
from .compile_cache import enable_compile_cache
from .mesh import make_elastic_mesh, make_production_mesh


def sharded_qft(cfg: ModelConfig, qcfg: QuantConfig, mesh, calib: list[dict],
                pol: ShardingPolicy = ShardingPolicy(), cle: bool = False,
                steps_per_epoch: int = 500):
    """Sharded QFT state and step on ``mesh`` (call under ``jax.set_mesh``).

    The teacher, the calibrated MMSE-initialised student and the Adam state
    are each computed by a jitted init whose outputs land directly in their
    shardings, so no device ever holds a whole copy.  Returns
    ``(student, opt_state, teacher, step)`` with
    ``step(student, opt_state, teacher, batch) -> (student, opt_state,
    {"loss", "grad_norm"})``; student and opt_state are donated."""
    # one resolved plan for init + finetune forward + (later) export: the
    # production path must train on the grid the artifact ships on
    qplan = resolve_quant_plan(cfg, qcfg)
    k_teacher, k_student = jax.random.PRNGKey(0), jax.random.PRNGKey(1)

    def init_teacher(key):
        return init_model(key, cfg, None)

    t_sh = params_shardings(jax.eval_shape(init_teacher, k_teacher), cfg,
                            mesh, pol)
    teacher = jax.jit(init_teacher, out_shardings=t_sh)(k_teacher)
    trainer = QFTTrainer(cfg, qcfg, teacher, QFTConfig(cle_init=cle),
                         steps_per_epoch=steps_per_epoch, plan=qplan)

    def prepare(t, batches):
        return trainer.prepare_student(k_student, batches, teacher=t)

    s_sh = params_shardings(jax.eval_shape(prepare, teacher, calib), cfg,
                            mesh, pol)
    student = jax.jit(prepare, out_shardings=s_sh)(teacher, calib)
    o_sh = opt_state_shardings(s_sh, mesh)
    opt_state = jax.jit(trainer.opt.init, out_shardings=o_sh)(student)
    rep = NamedSharding(mesh, P())
    step = jax.jit(trainer.train_step, in_shardings=(s_sh, o_sh, t_sh, None),
                   out_shardings=(s_sh, o_sh, {"loss": rep, "grad_norm": rep}),
                   donate_argnums=(0, 1))
    return student, opt_state, teacher, step


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
    cfg = get_config(args.arch).with_padding(tp=mesh.shape["model"])
    pol = ShardingPolicy(
        dp=("pod", "data") if args.multi_pod else ("data",))
    set_runtime(act_spec=pol.dp)

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
