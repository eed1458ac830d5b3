"""Train≡export grid invariant: the plan-threaded transformer training
forward fake-quants every tensor at its resolved QuantPlan bits, so the
training grid is bit-exactly the deployment grid — under mixed W4/W8 bits,
§4 1%-rule exemptions, and group-layout overrides, across every model
family.

The parity oracle compares the student's fake-quant forward (``plan=``
threaded) against the FP forward over ``effective_view`` /
``deploy_view(export)`` weights.  Activation quant is off (permissive mode):
the invariant is about the *weight* grid — the deployed artifact carries no
activation fake-quant, so only ``a_bits=None`` setups admit exact equality.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.core import deployment_oriented
from repro.core.plan import PlanView, apply_plan, plan_view, resolve_plan
from repro.core.qconfig import Granularity, QuantConfig
from repro.models import ModelConfig, forward, init_model
from repro.models.config import MLAConfig, MoEConfig, SSMConfig
from repro.serve.deploy import (deploy_view, effective_view,
                                export_for_layers, make_deploy_plan)
from repro.train.qft_trainer import init_scales
from repro.train.steps import make_train_step


def _cfg(family, **kw):
    base = dict(name=f"t-{family}", family=family, n_layers=2, d_model=32,
                n_heads=4, n_kv_heads=2, d_ff=64, vocab=64, head_dim=8,
                scan_layers=False, remat=False)
    base.update(kw)
    return ModelConfig(**base)


_MOE = MoEConfig(n_experts=4, top_k=2, n_shared=1, d_ff_expert=16)
_SSM = SSMConfig(d_state=16, head_dim=16, n_groups=1, chunk=8)

# family → (config, mixed-bit/exemption/layout overrides exercising that
# family's distinctive paths)
FAMILIES = {
    "dense": (_cfg("dense"),
              dict(bits_overrides=(("layers.attn.w[qk]", 8),),
                   layout_overrides=(("layers.mlp.*", "group:8"),),
                   exempt_frac=0.2)),
    "moe": (_cfg("moe", moe=_MOE),
            dict(bits_overrides=(("layers.mlp.up", 8),
                                 ("layers.mlp.shared_down", 8)),
                 exempt_frac=0.0)),
    "mla_moe": (_cfg("mla_moe", moe=_MOE, mla=MLAConfig(
                    kv_lora=16, q_lora=16, d_nope=8, d_rope=8, d_v=8)),
                dict(bits_overrides=(("layers.attn.q_up", 8),
                                     ("layers.attn.v_up", 8)),
                     exempt_frac=0.0)),
    "ssm": (_cfg("ssm", ssm=_SSM),
            dict(bits_overrides=(("layers.ssm.in_proj", 8),),
                 exempt_frac=0.0)),
    "hybrid": (_cfg("hybrid", n_layers=3, attn_every=2, ssm=_SSM),
               dict(bits_overrides=(("shared_attn.attn.w[qv]", 8),
                                    ("tail.ssm.out_proj", 8)),
                    exempt_frac=0.0)),
    "encdec": (_cfg("encdec", enc_layers=1),
               dict(bits_overrides=(("dec_layers.cross.w[qk]", 8),
                                    ("frame_proj", 8)),
                    exempt_frac=0.0)),
    "vlm": (_cfg("vlm", mrope_sections=(2, 1, 1)),
            dict(bits_overrides=(("layers.mlp.down", 8),),
                 exempt_frac=0.2)),
}


# W4, FP activations, per-out-channel scales with per-tensor MMSE init: the
# permissive/DCHW setup folds APQ left scales into SHARED streams, which on
# toy nets can zero out whole linears and mask grid differences — CHW keeps
# every tensor's reconstruction well-scaled so the parity test has teeth
_QCFG = QuantConfig(w_bits=4, a_bits=None, granularity=Granularity.CHW)


def _batch(cfg, key, B=2, S=8):
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(key, (B, 4, cfg.d_model),
                                                  jnp.bfloat16)
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(S + 4)[None, None], (B, 3, S + 4)).astype(jnp.int32)
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(key, (B, 4, cfg.d_model),
                                            jnp.bfloat16)
    return batch


def _prepared(cfg, qcfg):
    """(student with plan-reconciled layouts + MMSE-fit scales, plan)."""
    key = jax.random.PRNGKey(0)
    student = init_model(key, cfg, qcfg)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")       # group-fallback notices
        qplan = resolve_plan(qcfg, student, model_cfg=cfg)
    student = apply_plan(student, qplan)      # path-glob layout reshapes
    # MMSE fit at the plan bits — without it the default scales are so
    # coarse nothing clips and W4 ≡ W8 vacuously
    student = init_scales(student, cfg, qcfg, plan=qplan)
    return student, qplan


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_train_forward_matches_export_grid(family):
    cfg, knobs = FAMILIES[family]
    qcfg = dataclasses.replace(_QCFG, **knobs)
    student, qplan = _prepared(cfg, qcfg)
    dplan = make_deploy_plan(qcfg, family=cfg.family, quant_plan=qplan)
    batch = _batch(cfg, jax.random.PRNGKey(1))

    out_train = forward(student, cfg, qcfg, batch, plan=qplan)
    ev = effective_view(student, dplan, dtype=jnp.float32)
    out_eff = forward(ev, cfg, None, batch)
    assert jnp.array_equal(out_train["logits"], out_eff["logits"]), \
        f"{family}: training forward diverges from effective_view grid"
    assert jnp.array_equal(out_train["hidden"], out_eff["hidden"])

    # non-vacuity: the plan assigns non-default bits, so the retired
    # role-ladder forward must land on a DIFFERENT grid
    out_ladder = forward(student, cfg, qcfg, batch)
    assert not jnp.array_equal(out_ladder["logits"], out_train["logits"]), \
        f"{family}: overrides did not change the grid — test is vacuous"


def test_train_forward_matches_deployed_artifact():
    """Full chain: fake-quant train forward ≡ forward over the dequantized
    deployed artifact (int4-packed export included)."""
    cfg, knobs = FAMILIES["dense"]
    qcfg = dataclasses.replace(_QCFG, **knobs)
    student, qplan = _prepared(cfg, qcfg)
    dplan = make_deploy_plan(qcfg, family=cfg.family, quant_plan=qplan)
    artifact = export_for_layers(student, dplan)
    dv = deploy_view(artifact, dplan, dtype=jnp.float32)
    batch = _batch(cfg, jax.random.PRNGKey(1))
    out_train = forward(student, cfg, qcfg, batch, plan=qplan)
    out_dep = forward(dv, cfg, None, batch)
    assert jnp.array_equal(out_train["logits"], out_dep["logits"])


def test_scan_layers_and_jit_accept_plan():
    """Plan lookups are static: the scan-stacked forward jits and a full
    mixed-precision train step produces finite grads for every DoF."""
    cfg = dataclasses.replace(FAMILIES["dense"][0], scan_layers=True)
    qcfg = dataclasses.replace(
        deployment_oriented(),
        bits_overrides=(("layers.attn.w[qk]", 8),), exempt_frac=0.0)
    student, qplan = _prepared(cfg, qcfg)
    teacher = init_model(jax.random.PRNGKey(2), cfg, None)
    from repro.optim.adam import paper_recipe
    opt = paper_recipe(steps_per_epoch=10)
    step = jax.jit(make_train_step(cfg, qcfg, opt, plan=qplan))
    batch = _batch(cfg, jax.random.PRNGKey(3))
    _, _, metrics = step(student, opt.init(student), teacher, batch)
    assert jnp.isfinite(metrics["loss"]) and jnp.isfinite(metrics["grad_norm"])
    assert float(metrics["grad_norm"]) > 0


def test_adapter_offgrid_warning_retired():
    """A plan with non-default transformer bits no longer triggers the
    TransformerAdapter "trains on a different grid" warning — the forward
    honors the plan, so the warning path was deleted, not suppressed."""
    from repro.pipeline import PipelineConfig
    from repro.pipeline.adapters import TransformerAdapter
    pcfg = PipelineConfig(arch="qwen3-8b", smoke=True, steps=0,
                          bits_overrides=(("layers.attn.w[qk]", 8),),
                          exempt_frac=0.1)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        adapter = TransformerAdapter(pcfg, pcfg.model_config(),
                                     pcfg.quant_config())
    assert not [w for w in caught if "role-ladder" in str(w.message)], \
        "the off-grid role-ladder warning should be deleted"
    # the plan the adapter resolved carries the overrides it will train on
    assert adapter.qplan.spec("layers.attn.wq").w_bits == 8


def test_plan_view_scoping():
    qcfg = dataclasses.replace(
        _QCFG, bits_overrides=(("layers.mlp.down", 6),))
    cfg = FAMILIES["dense"][0]
    skel = jax.eval_shape(lambda k: init_model(k, cfg, qcfg),
                          jax.random.PRNGKey(0))
    plan = resolve_plan(qcfg, skel, model_cfg=cfg)
    pv = plan_view(plan).child("layers", "mlp")
    assert pv.bits("down") == 6
    assert pv.bits("up") == qcfg.w_bits
    # unknown paths fall back to the plan default (same rule as export)
    assert pv.child("nope").bits("missing") == plan.default_bits
    # the inert view reproduces pre-plan behavior exactly
    null = plan_view(None)
    assert null.child("anything") is null
    assert null.bits("wq") is None and null.bits("router", 8) == 8
    assert isinstance(plan_view(pv), PlanView) and plan_view(pv) is pv


def test_mesh_context_provides_ambient_mesh():
    """Regression: entering a launch.mesh mesh with jax.set_mesh must
    install an ambient mesh so constrain_act's bare-PartitionSpec sharding
    constraint traces — a missing ambient mesh broke every sharded
    prefill/decode lowering."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh()

    def f(x):
        return jax.lax.with_sharding_constraint(x, P("data", None)) * 2

    with jax.set_mesh(mesh):
        jax.jit(f).lower(jnp.ones((2, 2)))    # raises without an ambient mesh
