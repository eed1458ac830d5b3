"""QFT trainer: the paper's single-step PTQ pipeline, end to end.

Pipeline (paper §4):
 1. take a pretrained FP network (the teacher);
 2. build the fake-quantized student with the SAME weights;
 3. the sole pre-QFT step: MMSE (PPQ/APQ) weight-scale init + naive max-min
    activation calibration (+ optional 4b-adapted CLE for the layerwise mode,
    + optional bias correction);
 4. finetune ALL DoF jointly — weights, biases, activation scales, rescale
    factors — with backbone-L2 distillation, Adam, cosine-reload schedule;
 5. export the deployment artifact (serve/deploy.py).

Works at smoke scale on CPU (scan_layers=False for tap capture) and sharded
under a mesh (the launcher passes shardings + checkpoint manager).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Iterable

import jax
import jax.numpy as jnp

from ..core import cle, dof
from ..core.calibration import stream_params_from_range
from ..core.mmse import ppq_scale
from ..core.qconfig import Granularity, QuantConfig
from ..models import forward, init_model
from ..models.config import ModelConfig
from ..core.plan import STREAM_OF, QuantPlan, _is_qlinear
from ..optim.adam import paper_recipe
from .steps import make_train_step

Params = dict[str, Any]

# tap name suffix → (module key, stream key) for calibration write-back
_TAP_TO_STREAM = {
    "attn_in": ("attn", "in_stream"),
    "attn.pre_o": ("attn", "out_stream"),
    "mlp_in": ("mlp", "in_stream"),
    "mlp.act": ("mlp", "act_stream"),
    "ssm_in": ("ssm", "in_stream"),
    "ssm.out": ("ssm", "out_stream"),
}


def _init_scales_tree(tree: Params, qcfg: QuantConfig,
                      plan: QuantPlan | None = None) -> Params:
    """MMSE-init every qlinear's log_swr (PPQ; APQ for dchw, folding the left
    scale into the sibling stream).  Handles layer-stacked subtrees via vmap.

    Per-tensor fit bits come from the resolved QuantPlan (path-qualified
    lookups), so exempted / overridden tensors are fitted at the same grid
    they export under; without a plan the pre-plan role defaults apply."""

    def bits_at(path: tuple, default: int | None = None) -> int | None:
        if plan is not None:
            return plan.bits_for(".".join(path))
        return default

    def embed_init(v: Params) -> Params:
        srow = ppq_scale(v["w"], qcfg.embed_bits, axes=(1,),
                         iters=qcfg.mmse_iters)            # [V, 1]
        return {**v, "log_s": jnp.log(jnp.maximum(srow, 1e-12))}

    def walk(node: Params, prefix: tuple) -> Params:
        if not isinstance(node, dict):
            return node
        if "log_s" in node and "w" in node:                # quantized embedding
            return embed_init(node)
        out = dict(node)
        for k, v in node.items():
            if isinstance(v, dict) and "log_s" in v and "w" in v:
                out[k] = embed_init(v)
            elif _is_qlinear(v):
                sname = STREAM_OF.get(k)
                stream = node.get(sname) if sname else None
                bits = bits_at(prefix + (k,))
                if qcfg.granularity is Granularity.DCHW:
                    newlin, log_swl = dof.apq_init_qlinear(v, qcfg, bits=bits)
                    out[k] = newlin
                    if stream is not None:
                        # S_a = 1/S_wL (Eq. 3); fan-out siblings geo-mean in
                        out[sname] = {**out[sname],
                                      "log_sa": out[sname]["log_sa"] * 0.0
                                      - log_swl}
                else:
                    # invert Eq. 2: fit S_wR given the (calibrated) S_a tie
                    log_sa = None if stream is None else stream["log_sa"]
                    out[k] = dof.mmse_init_qlinear(v, qcfg, bits=bits,
                                                   log_sa_in=log_sa)
            elif isinstance(v, dict):
                out[k] = walk(v, prefix + (k,))
        return out

    out = dict(tree)
    for k, v in tree.items():
        if k in ("layers", "enc_layers", "dec_layers", "tail"):
            out[k] = jax.vmap(lambda lp, k=k: walk(lp, (k,)))(v)
        elif isinstance(v, dict):
            if _is_qlinear(v):
                sname = STREAM_OF.get(k)
                stream = tree.get(sname) if sname else None
                log_sa = None if stream is None else stream["log_sa"]
                bits = bits_at((k,), qcfg.embed_bits
                               if k in ("lm_head", "fc") else qcfg.w_bits)
                out[k] = dof.mmse_init_qlinear(v, qcfg, bits=bits,
                                               log_sa_in=log_sa)
            else:
                out[k] = walk(v, (k,))
        else:
            out[k] = v
    return out


def _copy_weights(student: Params, teacher: Params) -> Params:
    """Overwrite student's w/b (master FP weights) with the teacher's.

    Materializes fresh buffers (f32 masters): the student is donated by the
    jitted train step while the teacher stays live — aliased buffers would
    trip XLA's donation check.
    """
    def walk(s, t):
        if isinstance(s, dict):
            out = {}
            for k, v in s.items():
                if k in t:
                    out[k] = walk(v, t[k])
                else:
                    out[k] = v          # quant-only leaves (scales, streams)
            return out
        return jnp.array(t, dtype=s.dtype) if t is not None else s
    return walk(student, teacher)


def calibrate_student(student: Params, cfg: ModelConfig, qcfg: QuantConfig,
                      teacher: Params, batches: Iterable[dict]) -> Params:
    """Naive max-min activation calibration (paper's pre-QFT step) from
    teacher taps; writes per-layer stream (log_sa, zp)."""
    if not qcfg.act_quant:
        return student
    cfg_taps = dataclasses.replace(cfg, scan_layers=False, remat=False)
    acc: dict[str, tuple] = {}
    for batch in batches:
        taps = forward(teacher, cfg_taps, None, batch, collect_taps=True)["taps"]
        for name, st in taps.items():
            lo, hi = st["min"], st["max"]
            if name in acc:
                lo = jnp.minimum(lo, acc[name][0])
                hi = jnp.maximum(hi, acc[name][1])
            acc[name] = (lo, hi)

    new = jax.tree.map(lambda x: x, student)  # shallow functional copy

    def put(layer_idx: int, module: str, stream: str, val: dict,
            container="layers"):
        node = new[container]
        mod = node.get(module) if module else node
        if mod is None or stream not in mod:
            return
        for k2 in ("log_sa", "zp"):
            mod[stream][k2] = mod[stream][k2].at[layer_idx].set(val[k2])

    for name, (lo, hi) in acc.items():
        parts = name.split(".", 1)
        layer_tag, suffix = parts[0], parts[1] if len(parts) > 1 else ""
        if not layer_tag.startswith("L") or not layer_tag[1:].isdigit():
            continue
        i = int(layer_tag[1:])
        if suffix not in _TAP_TO_STREAM:
            continue
        module, stream = _TAP_TO_STREAM[suffix]
        sp = stream_params_from_range(lo, hi, qcfg, per_channel=False)
        put(i, module, stream, sp)
    return new


def cle_init_student(student: Params, cfg: ModelConfig,
                     qcfg: QuantConfig) -> Params:
    """4b-adapted CLE (Appendix D) on the transformer's norm-gain pivot:
    skew each in_stream's S_a by the consumers' MMSE slice/tensor log-ratios
    (β=−1 form: residual producer is lossless ⇒ full benefit to consumers)."""
    def walk(layer: Params) -> Params:
        out = dict(layer)
        for mod_name in ("attn", "mlp", "ssm"):
            mod = layer.get(mod_name)
            if not isinstance(mod, dict) or "in_stream" not in mod:
                continue
            consumers = [v["w"] for k, v in mod.items()
                         if _is_qlinear(v) and STREAM_OF.get(k) == "in_stream"
                         and v["w"].ndim == 2]
            if not consumers:
                continue
            log_c = cle.cle_factors(
                w_prev=jnp.eye(consumers[0].shape[0]),  # residual: lossless
                w_next_list=consumers,
                bits_prev=qcfg.w_bits,
                bits_next_list=[qcfg.w_bits] * len(consumers),
                cfg=qcfg, beta_override=-1.0)
            mod = dict(mod)
            mod["in_stream"] = {**mod["in_stream"],
                                "log_sa": cle.apply_cle_to_stream(
                                    mod["in_stream"]["log_sa"], log_c)}
            out[mod_name] = mod
        return out

    out = dict(student)
    for k in ("layers", "enc_layers", "dec_layers", "tail"):
        if k in student:
            out[k] = jax.vmap(walk)(student[k])
    return out


def build_student(key, cfg: ModelConfig, qcfg: QuantConfig,
                  teacher: Params) -> Params:
    """Stage: fake-quantized student skeleton with the teacher's FP weights."""
    student = init_model(key, cfg, qcfg)
    return _copy_weights(student, teacher)


def init_scales(student: Params, cfg: ModelConfig, qcfg: QuantConfig,
                cle_init: bool = False,
                plan: QuantPlan | None = None) -> Params:
    """Stage: MMSE/APQ weight-scale init (+ optional CLE) — run AFTER
    calibrate_student so the S_a tie of Eq. 2 is inverted against the
    calibrated streams.  ``plan`` supplies per-tensor fit bits."""
    student = _init_scales_tree(student, qcfg, plan=plan)
    if cle_init:
        student = cle_init_student(student, cfg, qcfg)
    return student


# -------------------------------------------------------------------------
# Step-checkpoint convention, shared by QFTTrainer.run and the pipeline's
# CNN finetune loop: checkpoint number == completed steps.
# -------------------------------------------------------------------------

def restore_step_state(ckpt, like: dict, steps: int,
                       resume: bool) -> tuple[dict, int]:
    """(state, start_step) from the newest usable step checkpoint.

    A checkpoint beyond the requested step count can't produce the requested
    state — then (and with resume off / no checkpoint) train from scratch.
    """
    if not resume or ckpt is None:
        return like, 0
    latest = ckpt.latest_step()
    if not latest or latest > steps:
        return like, 0
    return ckpt.restore(latest, like), latest


def step_ckpt_due(completed: int, every: int, steps: int) -> bool:
    """Periodic save points; the final state is saved separately at ``steps``."""
    return completed % every == 0 and completed < steps


@dataclasses.dataclass
class QFTConfig:
    epochs: int = 12                  # paper
    ce_proportion: float = 0.0        # Fig. 6 ablation knob
    cle_init: bool = False            # Fig. 8: CLE+QFT two-step
    base_lr: float = 1e-4             # Fig. 7 robust region
    freeze_scales: bool = False       # Fig. 8/9 ablation: train W&b only
    checkpoint_dir: str | None = None
    checkpoint_every: int = 200


class QFTTrainer:
    """Drives the QFT finetune.  ``plan`` (a resolved core.plan.QuantPlan)
    threads per-tensor bits through BOTH the MMSE scale init and the
    fake-quant training forward, so every stage of the trainer operates on
    the grid the artifact will export under."""

    def __init__(self, cfg: ModelConfig, qcfg: QuantConfig, teacher: Params,
                 qft: QFTConfig = QFTConfig(), steps_per_epoch: int = 500,
                 plan: QuantPlan | None = None):
        self.cfg = cfg
        self.qcfg = qcfg
        self.teacher = teacher
        self.qft = qft
        self.plan = plan
        self.opt = paper_recipe(steps_per_epoch=steps_per_epoch,
                                base_lr=qft.base_lr)
        grad_mask = None
        if qft.freeze_scales:
            def mask_fn(path, g):
                name = str(path[-1].key) if hasattr(path[-1], "key") else ""
                return (jnp.zeros_like(g)
                        if name in ("log_swr", "log_sa", "zp", "log_s") else g)
            grad_mask = mask_fn
        self._grad_mask = grad_mask
        self.train_step = make_train_step(cfg, qcfg, self.opt,
                                          ce_proportion=qft.ce_proportion,
                                          grad_mask=grad_mask, plan=plan)

    # -------------------------------------------------------------- prepare
    def prepare_student(self, key, calib_batches: Iterable[dict],
                        teacher: Params | None = None) -> Params:
        """Build, calibrate and MMSE-init the student.  ``teacher`` defaults
        to the trainer's; pass it explicitly to jit this (a closed-over
        teacher would be baked into the program as a constant)."""
        teacher = self.teacher if teacher is None else teacher
        student = build_student(key, self.cfg, self.qcfg, teacher)
        # order matters: calibrate S_a first, THEN invert Eq. 2 for S_wR
        student = calibrate_student(student, self.cfg, self.qcfg,
                                    teacher, calib_batches)
        return init_scales(student, self.cfg, self.qcfg,
                           cle_init=self.qft.cle_init, plan=self.plan)

    # ------------------------------------------------------------------ run
    def run(self, student: Params, data: Iterable[dict], steps: int,
            log_every: int = 50, ckpt=None,
            resume: bool = False) -> tuple[Params, list[dict]]:
        state, start = restore_step_state(
            ckpt, {"student": student, "opt": self.opt.init(student)},
            steps, resume)
        student, opt_state = state["student"], state["opt"]
        jit_step = jax.jit(self.train_step, donate_argnums=(0, 1))
        history = []
        it = iter(data)
        for _ in range(start):      # fast-forward: deterministic streams
            next(it)                # replay the same batch per step index
        t0 = time.time()
        for s in range(start, steps):
            batch = next(it)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            student, opt_state, metrics = jit_step(student, opt_state,
                                                   self.teacher, batch)
            if s % log_every == 0 or s == steps - 1:
                history.append({"step": s,
                                "loss": float(metrics["loss"]),
                                "t": time.time() - t0})
            if ckpt is not None and step_ckpt_due(
                    s + 1, self.qft.checkpoint_every, steps):
                ckpt.save(s + 1, {"student": student, "opt": opt_state},
                          blocking=False)
        if ckpt is not None and steps > start:
            ckpt.save(steps, {"student": student, "opt": opt_state})
        return student, history
