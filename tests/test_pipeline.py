"""End-to-end pipeline tests (fast tier): calibrate → init → finetune(2) →
export → evaluate on the paper CNN and a tiny transformer, asserting
export/dequantize_export parity and stage checkpoint resume."""
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dof
from repro.models.cnn import conv_effective_weight
from repro.pipeline import PipelineConfig, STAGES, run_pipeline
from repro.pipeline.cli import main as cli_main

TINY_LM = dict(arch="qwen3_8b", smoke=True, steps=2, calib_samples=64,
               calib_seq_len=16, calib_batch_size=8, calib_batches=2,
               eval_batches=1, log_every=1)


@pytest.fixture(scope="module")
def cnn_run(tmp_path_factory):
    """One full paper-cnn pipeline run, shared by the e2e and resume tests."""
    workdir = tmp_path_factory.mktemp("cnn_pipeline")
    pcfg = PipelineConfig(arch="paper_cnn", mode="w4a8", steps=2,
                          calib_samples=256, log_every=1,
                          workdir=str(workdir))
    return pcfg, run_pipeline(pcfg)


def test_pipeline_e2e_paper_cnn(cnn_run):
    _, result = cnn_run
    assert result.stages_run == list(STAGES)
    ev = result.metrics["evaluate"]
    # acceptance: dequantize_export ≡ effective_weight to fp tolerance
    assert ev["export_parity_max_err"] < 1e-4, ev
    assert 0.0 <= ev["acc_deployed"] <= 1.0
    # direct per-layer round-trip on a conv (int4-packed where cin is even)
    student, art, plan = result.student, result.artifact, result.plan
    from repro.models.cnn import _conv_stream_scales
    i = 1                                     # conv1: cin=16, packs to uint8
    log_in, log_out = _conv_stream_scales(student, i)
    deq = dof.dequantize_export(art["convs"][i], jnp.float32, packed=True)
    w_eff = conv_effective_weight(student["convs"][i], plan.qcfg,
                                  log_in, log_out)
    assert art["convs"][i]["q"].dtype == jnp.uint8    # really int4-packed
    np.testing.assert_allclose(np.asarray(deq), np.asarray(w_eff),
                               rtol=1e-4, atol=1e-5)


def test_pipeline_stage_resume(cnn_run):
    """A rerun over the same workdir skips every completed student stage and
    restores the trained student bit-for-bit (steps=0 → finetune no-op)."""
    pcfg, first = cnn_run
    pcfg2 = PipelineConfig(arch="paper_cnn", mode="w4a8", steps=0,
                           calib_samples=256, workdir=pcfg.workdir)
    second = run_pipeline(pcfg2)
    assert second.stages_skipped == ["calibrate", "init", "finetune"]
    assert second.stages_run == ["export", "evaluate"]
    for a, b in zip((first.student["convs"][0]["w"],
                     first.student["streams"][0]["log_sa"]),
                    (second.student["convs"][0]["w"],
                     second.student["streams"][0]["log_sa"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pipeline_steps_change_reenters_finetune(cnn_run):
    """Raising --steps on an existing workdir must train the extra steps
    (continuing from the within-finetune checkpoint), not silently skip.
    Runs after test_pipeline_stage_resume: it advances the shared workdir."""
    pcfg, _ = cnn_run                        # fixture ran steps=2
    pcfg3 = PipelineConfig(arch="paper_cnn", mode="w4a8", steps=3,
                           calib_samples=256, log_every=1,
                           workdir=pcfg.workdir)
    third = run_pipeline(pcfg3)
    assert third.stages_skipped == ["calibrate", "init"]
    assert "finetune" in third.stages_run
    ft = third.metrics["finetune"]
    assert ft["steps"] == 3
    # continued from step 2, not restarted: only step 2 appears in history
    assert [h["step"] for h in third.history] == [2]


def test_pipeline_e2e_tiny_transformer():
    pcfg = PipelineConfig(mode="w4a8", **TINY_LM)
    result = run_pipeline(pcfg)
    assert result.stages_run == list(STAGES)
    ev = result.metrics["evaluate"]
    assert ev["export_parity_max_err"] < 1e-4, ev
    assert np.isfinite(ev["distill_loss"])
    assert result.metrics["finetune"]["steps"] == 2
    # direct round-trip on a stacked qlinear (mlp.up under the in_stream tie)
    student, art = result.student, result.artifact
    lin = student["layers"]["mlp"]["up"]
    log_sa = student["layers"]["mlp"]["in_stream"]["log_sa"]
    deq = dof.dequantize_export(art["layers"]["mlp"]["up"], jnp.float32)
    w_eff = dof.effective_weight(lin, result.qcfg, log_sa,
                                 compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(deq), np.asarray(w_eff),
                               rtol=1e-4, atol=1e-5)


def test_pipeline_group_layout_transformer():
    """W4 group-wise weight scales end to end on a transformer (QLayout):
    multiple groups per linear at smoke dims (d=64, g=16 → 4 groups), export
    parity exact and the kernel-route oracle through the Pallas path."""
    pcfg = PipelineConfig(mode="w4a8", w_layout="group:16", use_pallas=True,
                          **{**TINY_LM, "steps": 0})
    result = run_pipeline(pcfg)
    ev = result.metrics["evaluate"]
    assert ev["w_layout"] == "group:16"
    assert ev["export_parity_max_err"] < 1e-4, ev
    kr = ev["kernel_route"]
    assert kr["pallas"] and kr["max_err"] < 1e-4, kr
    # the artifact really carries group-resolution scales: [K/g, out]
    up = result.artifact["layers"]["mlp"]["up"]
    assert up["s_wr"].ndim == 3 and up["s_wr"].shape[-2] == 64 // 16
    lin = result.student["layers"]["mlp"]["up"]
    log_sa = result.student["layers"]["mlp"]["in_stream"]["log_sa"]
    deq = dof.dequantize_export(up, jnp.float32)
    w_eff = dof.effective_weight(lin, result.qcfg, log_sa,
                                 compute_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(deq), np.asarray(w_eff))


def test_pipeline_w4chw_mode_cnn():
    """Permissive (doubly-channelwise / APQ) setup through export+evaluate,
    no training.  (The transformer dchw path is covered in the slow tier by
    test_qft_reduces_distillation_loss[W4dchw].)"""
    pcfg = PipelineConfig(arch="paper_cnn", mode="w4chw", steps=0,
                          calib_samples=256)
    result = run_pipeline(pcfg)
    ev = result.metrics["evaluate"]
    assert ev["export_parity_max_err"] < 1e-4, ev
    assert "finetune" not in result.metrics           # steps=0 skips training


def test_cli_quantize_smoke(capsys):
    rc = cli_main(["quantize", "--config", "paper_cnn", "--steps", "0",
                   "--stop-after", "export"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "stage export" in out and "pipeline complete" in out


def test_cli_rejects_unknown_config(capsys):
    rc = cli_main(["quantize", "--config", "nonexistent_model"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "unknown config" in err and "qwen3-8b" in err


def test_canonical_arch_spellings():
    from repro.pipeline import canonical_arch
    assert canonical_arch("qwen3_8b") == "qwen3-8b"
    assert canonical_arch("qwen3-8b") == "qwen3-8b"
    assert canonical_arch("paper_cnn") == "paper-cnn"


def test_reduced_config_is_one_chip_share_at_published_widths():
    from repro.configs import get_config, registry
    full = get_config("qwen3-8b")
    cut = PipelineConfig(arch="qwen3_8b", smoke=False,
                         reduced=True).model_config()
    for f in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff"):
        assert getattr(cut, f) == getattr(full, f), f
    mod = registry.get_module("qwen3-8b")
    assert (cut.n_layers, cut.vocab) == (mod.reduced["n_layers"],
                                         mod.reduced["vocab"])
    assert cut.vocab_padded == cut.vocab           # re-derived, not 151936
    with pytest.raises(ValueError, match="REDUCED"):
        get_config("paper-cnn", reduced=True)
    with pytest.raises(ValueError, match="smoke and reduced"):
        PipelineConfig(arch="qwen3_8b", smoke=True, reduced=True)


def test_compile_cache_env_dir_wins_else_fixed_checkout_dir(monkeypatch,
                                                            tmp_path):
    import jax
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # sets no other
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.DEFAULT_DIR.parent == \
            pathlib.Path(__file__).resolve().parents[1]
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
