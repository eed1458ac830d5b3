"""Model-family adapters: one stage vocabulary over every registry entry.

The orchestrator (pipeline/runner.py) is family-agnostic; an adapter maps the
five pipeline stages onto the family's actual machinery — QFTTrainer and
serve/deploy for the transformer zoo, the conv-specific calibration/export
path for the paper CNN.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ..core.calibration import stream_params_from_range
from ..core.distill import backbone_l2
from ..core.plan import QuantPlan, apply_plan, resolve_plan
from ..core.qconfig import Granularity, QuantConfig
from ..data.calib import CalibConfig, CalibDataset
from ..models import forward, init_model
from ..models import cnn as cnn_lib
from ..optim.adam import paper_recipe
from ..serve.deploy import (DeployPlan, deploy_view, effective_view,
                            export_for_layers, kernel_route_check,
                            make_deploy_plan)
from ..train import qft_trainer
from ..train.qft_trainer import QFTConfig, QFTTrainer
from .config import PipelineConfig

Params = dict[str, Any]


def resolve_quant_plan(model_cfg, qcfg: QuantConfig,
                       producers: tuple = ()) -> QuantPlan:
    """Resolve the per-tensor QuantPlan for a registry config.

    The student skeleton is built under ``jax.eval_shape`` — no allocation,
    so this is cheap even for the 100B+ registry entries (what the
    ``python -m repro plan`` CLI relies on)."""
    if getattr(model_cfg, "family", None) == "cnn":
        shapes = jax.eval_shape(
            lambda k: cnn_lib.init_cnn(k, model_cfg, qcfg),
            jax.random.PRNGKey(0))
    else:
        shapes = jax.eval_shape(
            lambda k: init_model(k, model_cfg, qcfg), jax.random.PRNGKey(0))
    return resolve_plan(qcfg, shapes, model_cfg=model_cfg,
                        producers=producers)


def tree_parity_error(deployed: Params, effective: Params) -> float:
    """max |dequantize_export − effective_weight| over every exported leaf —
    the pipeline's export-fidelity acceptance metric."""
    la = jax.tree.leaves(deployed)
    lb = jax.tree.leaves(effective)
    assert len(la) == len(lb), (len(la), len(lb))
    err = 0.0
    for a, b in zip(la, lb):
        err = max(err, float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                             - b.astype(jnp.float32)))))
    return err


# ---------------------------------------------------------------------------
# Transformer zoo (dense / MoE / MLA / SSM / hybrid / enc-dec / VLM)
# ---------------------------------------------------------------------------

class TransformerAdapter:
    """All registry transformer families, via QFTTrainer's stage functions."""

    def __init__(self, pcfg: PipelineConfig, model_cfg, qcfg: QuantConfig):
        if pcfg.smoke:
            model_cfg = dataclasses.replace(model_cfg, scan_layers=False,
                                            remat=False)
        self.pcfg = pcfg
        self.cfg = model_cfg
        self.qcfg = qcfg
        # resolved ONCE; init, the finetune/degradation forwards, export and
        # serving all read this object — the train≡export grid invariant
        self.qplan = resolve_quant_plan(model_cfg, qcfg)
        self.data = CalibDataset(CalibConfig(
            n_samples=pcfg.calib_samples, seq_len=pcfg.calib_seq_len,
            batch_size=pcfg.calib_batch_size, vocab=model_cfg.vocab,
            seed=pcfg.seed))
        self._trainer: QFTTrainer | None = None

    # ------------------------------------------------------------- fixtures
    def _augment(self, batch: dict) -> dict:
        """Stub modality inputs for VLM / enc-dec families (precomputed-
        embedding frontends, as the config modules state)."""
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        fam, d = self.cfg.family, self.cfg.d_model
        B, S = batch["tokens"].shape
        key = jax.random.PRNGKey(self.pcfg.seed + 17)
        if fam == "vlm":
            s_img = 4
            batch["patch_embeds"] = jax.random.normal(
                key, (B, s_img, d), jnp.bfloat16)
            batch["positions"] = jnp.broadcast_to(
                jnp.arange(S + s_img)[None, None],
                (B, 3, S + s_img)).astype(jnp.int32)
        elif fam == "encdec":
            batch["frames"] = jax.random.normal(key, (B, 8, d), jnp.bfloat16)
        return batch

    def batches(self):
        """Endless finetune batch iterator (family inputs attached)."""
        it = iter(self.data)
        while True:
            yield self._augment(next(it))

    def calib_batches(self) -> list[dict]:
        it = iter(CalibDataset(self.data.cfg))
        return [self._augment(next(it)) for _ in range(self.pcfg.calib_batches)]

    def init_teacher(self) -> Params:
        return init_model(jax.random.PRNGKey(self.pcfg.seed), self.cfg, None)

    def trainer(self, teacher: Params) -> QFTTrainer:
        if self._trainer is None:
            self._trainer = QFTTrainer(
                self.cfg, self.qcfg, teacher,
                QFTConfig(cle_init=self.pcfg.cle, base_lr=self.pcfg.base_lr,
                          checkpoint_every=self.pcfg.checkpoint_every),
                steps_per_epoch=self.data.steps_per_epoch, plan=self.qplan)
        return self._trainer

    # --------------------------------------------------------------- stages
    def build_student(self, teacher: Params) -> Params:
        student = qft_trainer.build_student(
            jax.random.PRNGKey(self.pcfg.seed + 1), self.cfg, self.qcfg,
            teacher)
        # reconcile log_swr shapes with the resolved plan (path-glob layout
        # overrides that bare-name init couldn't see)
        return apply_plan(student, self.qplan)

    def calibrate(self, student: Params, teacher: Params) -> Params:
        return qft_trainer.calibrate_student(student, self.cfg, self.qcfg,
                                             teacher, self.calib_batches())

    def init_scales(self, student: Params) -> Params:
        return qft_trainer.init_scales(student, self.cfg, self.qcfg,
                                       cle_init=self.pcfg.cle,
                                       plan=self.qplan)

    def finetune(self, student: Params, teacher: Params,
                 ckpt=None) -> tuple[Params, list[dict]]:
        if self.pcfg.steps <= 0:
            return student, []
        return self.trainer(teacher).run(
            student, self.batches(), steps=self.pcfg.steps,
            log_every=max(self.pcfg.log_every, 1), ckpt=ckpt,
            resume=self.pcfg.resume)

    def make_plan(self) -> DeployPlan:
        return make_deploy_plan(self.qcfg, arch=self.pcfg.arch,
                                family=self.cfg.family,
                                use_pallas=self.pcfg.use_pallas,
                                quant_plan=self.qplan)

    def export(self, student: Params, plan: DeployPlan) -> Params:
        return jax.jit(lambda p: export_for_layers(p, plan))(student)

    # ------------------------------------------------------------- evaluate
    def degradation(self, student: Params, teacher: Params) -> dict:
        losses, agree = [], []
        for batch in self.calib_batches()[: self.pcfg.eval_batches]:
            so = forward(student, self.cfg, self.qcfg, batch, plan=self.qplan)
            to = forward(teacher, self.cfg, None, batch)
            losses.append(float(backbone_l2(so["hidden"], to["hidden"])))
            agree.append(float(jnp.mean(
                jnp.argmax(so["logits"], -1) == jnp.argmax(to["logits"], -1))))
        return {"distill_loss": float(jnp.mean(jnp.asarray(losses))),
                "top1_agree": float(jnp.mean(jnp.asarray(agree)))}

    def evaluate(self, student: Params, teacher: Params, artifact: Params,
                 plan: DeployPlan) -> dict:
        metrics = self.degradation(student, teacher)
        metrics["w_layout"] = str(self.qcfg.layout)
        metrics["exempt"] = sorted(self.qplan.exempt_names)
        dv = deploy_view(artifact, plan, dtype=jnp.float32)
        ev = effective_view(student, plan, dtype=jnp.float32)
        metrics["export_parity_max_err"] = tree_parity_error(dv, ev)
        metrics["artifact_bytes"] = int(sum(
            l.size * l.dtype.itemsize for l in jax.tree.leaves(artifact)))
        if plan.use_pallas:
            check = kernel_route_check(artifact, plan)
            if check is not None:
                metrics["kernel_route"] = check
        if self.pcfg.serve_smoke:
            metrics["serve"] = self.serve_smoke(artifact, plan)
        return metrics

    def serve_smoke(self, artifact: Params, plan: DeployPlan) -> dict:
        from ..serve.engine import Engine, Request, ServeConfig
        cfg = dataclasses.replace(self.cfg, scan_layers=False, remat=False)
        engine = Engine.from_artifact(
            cfg, plan, artifact,
            ServeConfig(max_slots=self.pcfg.serve_max_slots, max_len=64,
                        prefill_chunk=self.pcfg.serve_prefill_chunk))
        pcfg = self.pcfg
        sampling = dict(temperature=pcfg.serve_temperature,
                        top_k=pcfg.serve_top_k, top_p=pcfg.serve_top_p)
        outs = engine.generate(
            [Request(prompt=[1, 2, 3], max_new_tokens=8,
                     seed=pcfg.serve_seed, **sampling),
             Request(prompt=[4, 5], max_new_tokens=4,
                     seed=pcfg.serve_seed + 1, **sampling)])
        assert len(outs) == 2 and len(outs[0]) == 8 and len(outs[1]) == 4
        return {"requests": 2, "tokens": sum(len(o) for o in outs),
                "max_slots": engine.scfg.max_slots,
                "temperature": pcfg.serve_temperature}


# ---------------------------------------------------------------------------
# Paper CNN (the paper's own experimental setting)
# ---------------------------------------------------------------------------

class CNNAdapter:
    """paper-cnn: conv streams chained per Eq. 2, backbone-feature KD."""

    def __init__(self, pcfg: PipelineConfig, model_cfg, qcfg: QuantConfig):
        self.pcfg = pcfg
        self.cfg = model_cfg                    # CNNConfig
        self.qcfg = qcfg
        self.qplan = resolve_quant_plan(model_cfg, qcfg)
        n = max(pcfg.calib_samples, 256)
        self.x_calib, self.y_calib = self._synth(jax.random.PRNGKey(pcfg.seed),
                                                 n)
        self.x_eval, self.y_eval = self._synth(
            jax.random.PRNGKey(pcfg.seed + 99), 512)

    def _synth(self, key, n):
        """Separable synthetic task: smooth class templates + noise (the CNN
        analogue of the LM's self-teaching calibration stream)."""
        cfg = self.cfg
        kx, kn = jax.random.split(key)
        kb = jax.random.PRNGKey(777)            # templates fixed across calls
        hw = cfg.img_hw
        grid = jnp.arange(hw) / hw
        modes = jnp.stack([jnp.cos(jnp.pi * f * grid) for f in (0, 1, 2)])
        spatial = jnp.einsum("ih,jw->ijhw", modes, modes).reshape(9, hw, hw)
        coef = jax.random.normal(kb, (cfg.n_classes, 9, cfg.in_ch))
        basis = jnp.einsum("kfc,fhw->khwc", coef, spatial)
        basis = basis / jnp.linalg.norm(
            basis.reshape(cfg.n_classes, -1), axis=1)[:, None, None, None] * 12.
        y = jax.random.randint(kx, (n,), 0, cfg.n_classes)
        x = basis[y] + jax.random.normal(kn, (n, hw, hw, cfg.in_ch))
        return x.astype(jnp.float32), y

    def accuracy(self, params: Params, qcfg, plan=None) -> float:
        logits = cnn_lib.forward_cnn(params, self.cfg, qcfg,
                                     self.x_eval, plan=plan)["logits"]
        return float(jnp.mean(jnp.argmax(logits, -1) == self.y_eval))

    def init_teacher(self) -> Params:
        teacher = cnn_lib.init_cnn(jax.random.PRNGKey(self.pcfg.seed),
                                   self.cfg, None)
        steps = self.pcfg.teacher_steps
        if steps <= 0:
            return teacher
        from ..optim.adam import Adam
        opt = Adam(lr=3e-3)
        state = opt.init(teacher)
        x, y = self.x_calib, self.y_calib

        def loss_fn(p, xb, yb):
            logits = cnn_lib.forward_cnn(p, self.cfg, None, xb)["logits"]
            lse = jax.nn.log_softmax(logits)
            return -jnp.mean(lse[jnp.arange(len(yb)), yb])

        @jax.jit
        def step(p, s, xb, yb):
            l, g = jax.value_and_grad(loss_fn)(p, xb, yb)
            p, s = opt.update(g, s, p)
            return p, s, l

        bs = min(128, len(x))
        for i in range(steps):
            j = (i * bs) % max(len(x) - bs, 1)
            teacher, state, _ = step(teacher, state, x[j:j + bs], y[j:j + bs])
        return teacher

    # --------------------------------------------------------------- stages
    def build_student(self, teacher: Params) -> Params:
        student = cnn_lib.init_cnn(jax.random.PRNGKey(self.pcfg.seed + 1),
                                   self.cfg, self.qcfg)
        for i, conv in enumerate(teacher["convs"]):
            student["convs"][i].update({"w": conv["w"], "b": conv["b"]})
        student["fc"].update({"w": teacher["fc"]["w"], "b": teacher["fc"]["b"]})
        return apply_plan(student, self.qplan)

    def calibrate(self, student: Params, teacher: Params) -> Params:
        """Naive max-min range calibration from teacher taps (paper §4);
        the fc stream shares PRE-pool feature scales (avg-pool is
        scale-preserving, §3.4)."""
        x = self.x_calib[:256]
        out = cnn_lib.forward_cnn(teacher, self.cfg, None, x,
                                  collect_taps=True)
        taps = out["taps"]
        for i in range(len(student["convs"])):
            t = taps[f"conv{i}.in"]
            student["streams"][i].update(stream_params_from_range(
                t["min"], t["max"], self.qcfg, per_channel=False))
        feats = out["features"].reshape(-1, out["features"].shape[-1])
        student["fc_stream"].update(stream_params_from_range(
            jnp.min(feats, 0), jnp.max(feats, 0), self.qcfg,
            per_channel=False))
        return student

    def init_scales(self, student: Params) -> Params:
        """MMSE (PPQ) / APQ init of every conv's F̂ by inverting Eq. 2 under
        the calibrated stream ties; per-tensor fit bits (exempt convs, the
        fc head) come from the resolved QuantPlan."""
        qcfg, qplan = self.qcfg, self.qplan
        n = len(student["convs"])

        def out_stream(i):
            return (student["streams"][i + 1] if i + 1 < n
                    else student["fc_stream"])

        if qcfg.granularity is Granularity.DCHW:
            apq_t = {}
            for i, conv in enumerate(list(student["convs"])):
                newc, log_swl = cnn_lib.apq_init_qconv(
                    conv, qcfg, bits=qplan.bits_for(f"convs.{i}"))
                apq_t[i] = newc["log_f"]        # total right scale log t
                student["convs"][i] = newc
                student["streams"][i]["log_sa"] = -log_swl
            for i in range(n):                  # Eq. 4: F̂ = t / S_a_out
                student["convs"][i] = {
                    **student["convs"][i],
                    "log_f": apq_t[i] - out_stream(i)["log_sa"]}
        else:
            for i, conv in enumerate(list(student["convs"])):
                student["convs"][i] = cnn_lib.mmse_init_qconv(
                    conv, qcfg,
                    log_sa_in=student["streams"][i]["log_sa"],
                    log_sa_out=out_stream(i)["log_sa"],
                    bits=qplan.bits_for(f"convs.{i}"))
        from ..core.dof import mmse_init_qlinear
        student["fc"] = mmse_init_qlinear(
            student["fc"], qcfg, bits=qplan.bits_for("fc"),
            log_sa_in=student["fc_stream"]["log_sa"])
        if self.pcfg.cle and qcfg.granularity is not Granularity.DCHW:
            student = self._cle(student, out_stream)
        return student

    def _cle(self, student: Params, out_stream) -> Params:
        """4b-adapted CLE on the conv chain (paper App. D) + F̂ refit."""
        from ..core.cle import cle_factors
        qcfg = self.qcfg
        for i in range(1, len(student["convs"])):
            wp = student["convs"][i - 1]["w"]
            w_prev = wp.reshape(-1, wp.shape[-1])
            wn = student["convs"][i]["w"]
            w_next = jnp.transpose(wn, (2, 0, 1, 3)).reshape(wn.shape[2], -1)
            log_c = cle_factors(w_prev, [w_next], qcfg.w_bits, [qcfg.w_bits],
                                qcfg)
            student["streams"][i]["log_sa"] = \
                student["streams"][i]["log_sa"] + log_c
        for i in range(len(student["convs"])):
            student["convs"][i] = cnn_lib.mmse_init_qconv(
                student["convs"][i], qcfg,
                log_sa_in=student["streams"][i]["log_sa"],
                log_sa_out=out_stream(i)["log_sa"])
        return student

    def finetune(self, student: Params, teacher: Params,
                 ckpt=None) -> tuple[Params, list[dict]]:
        steps = self.pcfg.steps
        if steps <= 0:
            return student, []
        opt = paper_recipe(steps_per_epoch=max(steps // 3, 1),
                           base_lr=self.pcfg.base_lr)
        state = opt.init(student)
        cfg, qcfg, qplan = self.cfg, self.qcfg, self.qplan

        def loss_fn(p, x):
            fs = cnn_lib.forward_cnn(p, cfg, qcfg, x, plan=qplan)["features"]
            ft = cnn_lib.forward_cnn(teacher, cfg, None, x)["features"]
            return backbone_l2(fs.reshape(fs.shape[0], -1, fs.shape[-1]),
                               ft.reshape(ft.shape[0], -1, ft.shape[-1]))

        @jax.jit
        def step(p, s, x):
            l, g = jax.value_and_grad(loss_fn)(p, x)
            p, s = opt.update(g, s, p)
            return p, s, l

        restored, start = qft_trainer.restore_step_state(
            ckpt, {"student": student, "opt": state}, steps, self.pcfg.resume)
        student, state = restored["student"], restored["opt"]
        x = self.x_calib
        bs = min(64, len(x))
        history = []
        for i in range(start, steps):
            j = (i * bs) % max(len(x) - bs, 1)
            student, state, loss = step(student, state, x[j:j + bs])
            if i % max(self.pcfg.log_every, 1) == 0 or i == steps - 1:
                history.append({"step": i, "loss": float(loss)})
            if ckpt is not None and qft_trainer.step_ckpt_due(
                    i + 1, self.pcfg.checkpoint_every, steps):
                ckpt.save(i + 1, {"student": student, "opt": state})
        if ckpt is not None and steps > start:
            ckpt.save(steps, {"student": student, "opt": state})
        return student, history

    def make_plan(self) -> DeployPlan:
        return make_deploy_plan(self.qcfg, arch=self.pcfg.arch, family="cnn",
                                use_pallas=self.pcfg.use_pallas,
                                quant_plan=self.qplan)

    def export(self, student: Params, plan: DeployPlan) -> Params:
        return cnn_lib.export_cnn(student, plan)

    # ------------------------------------------------------------- evaluate
    def evaluate(self, student: Params, teacher: Params, artifact: Params,
                 plan: DeployPlan) -> dict:
        dv = cnn_lib.cnn_deploy_view(artifact, plan)
        ev = cnn_lib.cnn_effective_view(student, plan)
        metrics = {
            # convs keep the paper's lw/chw scale shapes; the group layout
            # applies to the fc qlinear only (QLayout falls back per layer)
            "w_layout": str(self.qcfg.layout),
            "exempt": sorted(self.qplan.exempt_names),
            "acc_teacher": self.accuracy(teacher, None),
            "acc_student": self.accuracy(student, self.qcfg,
                                         plan=self.qplan),
            "acc_deployed": self.accuracy(dv, None),
            "export_parity_max_err": tree_parity_error(dv, ev),
            "artifact_bytes": int(sum(
                l.size * l.dtype.itemsize for l in jax.tree.leaves(artifact))),
        }
        if plan.use_pallas:
            check = kernel_route_check(artifact, plan)
            if check is not None:
                metrics["kernel_route"] = check
        return metrics


def get_adapter(pcfg: PipelineConfig):
    model_cfg = pcfg.model_config()
    qcfg = pcfg.quant_config()
    if getattr(model_cfg, "family", None) == "cnn":
        return CNNAdapter(pcfg, model_cfg, qcfg)
    return TransformerAdapter(pcfg, model_cfg, qcfg)
