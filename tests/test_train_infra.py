"""Training-infrastructure tests: QFT trainer recovery, checkpoint
atomicity/restore, elastic restart, gradient compression, data determinism."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backbone_l2, deployment_oriented, permissive
from repro.data.calib import CalibConfig, CalibDataset
from repro.models import ModelConfig, forward, init_model
from repro.train.checkpoint import CheckpointManager
from repro.train.compression import make_error_feedback_compressor
from repro.train.elastic import ElasticConfig, ElasticRunner
from repro.train.qft_trainer import QFTConfig, QFTTrainer

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                   n_heads=4, n_kv_heads=2, d_ff=64, vocab=128, head_dim=8,
                   scan_layers=False, remat=False)


def _setup(qcfg):
    key = jax.random.PRNGKey(0)
    teacher = init_model(key, TINY, None)
    data = CalibDataset(CalibConfig(n_samples=128, seq_len=16, batch_size=8,
                                    vocab=128))
    calib = [{k: jnp.asarray(v) for k, v in next(iter(data)).items()}
             for _ in range(2)]
    tr = QFTTrainer(TINY, qcfg, teacher, QFTConfig(), steps_per_epoch=16)
    student = tr.prepare_student(key, calib)
    return tr, teacher, student, data, calib


def _deg(student, teacher, qcfg, batch):
    hs = forward(student, TINY, qcfg, batch)["hidden"]
    ht = forward(teacher, TINY, None, batch)["hidden"]
    return float(backbone_l2(hs, ht))


@pytest.mark.slow
@pytest.mark.parametrize("qcfg", [deployment_oriented(), permissive()],
                         ids=["W4A8lw", "W4dchw"])
def test_qft_reduces_distillation_loss(qcfg):
    tr, teacher, student, data, calib = _setup(qcfg)
    d0 = _deg(student, teacher, qcfg, calib[0])
    student, hist = tr.run(student, data, steps=60, log_every=30)
    d1 = _deg(student, teacher, qcfg, calib[0])
    assert d1 < d0 * 0.85, (d0, d1)


@pytest.mark.slow
def test_freeze_scales_trains_weights_only():
    qcfg = permissive()
    key = jax.random.PRNGKey(0)
    teacher = init_model(key, TINY, None)
    data = CalibDataset(CalibConfig(n_samples=64, seq_len=16, batch_size=8,
                                    vocab=128))
    tr = QFTTrainer(TINY, qcfg, teacher, QFTConfig(freeze_scales=True),
                    steps_per_epoch=16)
    student = tr.prepare_student(key, [next(iter(data))])
    swr_before = student["layers"]["mlp"]["up"]["log_swr"].copy()
    w_before = student["layers"]["mlp"]["up"]["w"].copy()
    student, _ = tr.run(student, data, steps=10, log_every=10)
    np.testing.assert_array_equal(
        np.asarray(student["layers"]["mlp"]["up"]["log_swr"]),
        np.asarray(swr_before))
    assert bool(jnp.any(student["layers"]["mlp"]["up"]["w"] != w_before))


def test_checkpoint_roundtrip_and_keep_k(tmp_path):
    ckpt = CheckpointManager(str(tmp_path), keep=2)
    state = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
             "nested": {"b": jnp.ones((4,), jnp.bfloat16)}}
    for s in (10, 20, 30):
        ckpt.save(s, state)
    assert ckpt.all_steps() == [20, 30]              # keep-K GC
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    restored = ckpt.restore(30, state)
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(state["a"]))


def test_checkpoint_async(tmp_path):
    ckpt = CheckpointManager(str(tmp_path), keep=3)
    state = {"w": jnp.ones((128, 128))}
    ckpt.save(1, state, blocking=False)
    ckpt.wait()
    assert ckpt.latest_step() == 1


def test_elastic_restart_with_injected_failure(tmp_path):
    """Failure at step 7 → remesh → restore from last checkpoint → complete."""
    ckpt = CheckpointManager(str(tmp_path), keep=3)

    def build_step(mesh):
        def step(state, batch):
            return {"x": state["x"] + 1.0}, {}
        return step

    runner = ElasticRunner(build_step, ckpt,
                           ElasticConfig(checkpoint_every=5, max_restarts=2,
                                         model_parallel=1))
    data = CalibDataset(CalibConfig(n_samples=64, seq_len=4, batch_size=4,
                                    vocab=16))
    state = {"x": jnp.zeros(())}
    state, s = runner.run(state, data, steps=12, inject_failure_at=7)
    assert s == 12
    assert runner.restarts == 1
    assert runner.events[0]["step"] == 7
    # restored at 5, re-ran 5..12 → x counts total successful steps
    assert float(state["x"]) == 12.0


@pytest.mark.slow
def test_qft_run_resumes_from_step_checkpoint(tmp_path):
    """Crash mid-finetune → rerun with resume=True restores (student, opt) at
    the last step checkpoint and replays only the remaining steps, landing on
    the same state as the uninterrupted run."""
    import shutil
    qcfg = permissive()
    key = jax.random.PRNGKey(0)
    teacher = init_model(key, TINY, None)

    def fresh():
        data = CalibDataset(CalibConfig(n_samples=64, seq_len=16,
                                        batch_size=8, vocab=128))
        tr = QFTTrainer(TINY, qcfg, teacher,
                        QFTConfig(checkpoint_every=2), steps_per_epoch=8)
        return tr, tr.prepare_student(key, [next(iter(data))]), data

    ckpt = CheckpointManager(str(tmp_path), keep=5)
    tr, student, data = fresh()
    s1, _ = tr.run(student, data, steps=4, log_every=1, ckpt=ckpt)
    ckpt.wait()
    assert ckpt.all_steps() == [2, 4]
    shutil.rmtree(tmp_path / "step_0000000004")      # simulate crash after 2
    tr2, student2, data2 = fresh()
    s2, hist = tr2.run(student2, data2, steps=4, log_every=1, ckpt=ckpt,
                       resume=True)
    assert hist[0]["step"] == 2                      # steps 0-1 not replayed
    np.testing.assert_allclose(
        np.asarray(s2["layers"]["mlp"]["up"]["w"]),
        np.asarray(s1["layers"]["mlp"]["up"]["w"]), rtol=1e-6, atol=1e-7)


def test_gradient_compression_error_feedback():
    init, compress = make_error_feedback_compressor(bits=8)
    params = {"w": jnp.zeros((64,))}
    state = init(params)
    rng = np.random.default_rng(0)
    g_total_true = np.zeros(64)
    g_total_comp = np.zeros(64)
    for i in range(50):
        g = {"w": jnp.asarray(rng.normal(size=64) * 0.01, jnp.float32)}
        gq, state = compress(g, state)
        g_total_true += np.asarray(g["w"])
        g_total_comp += np.asarray(gq["w"])
    # error feedback: accumulated compressed grads track the true sum
    rel = np.linalg.norm(g_total_comp - g_total_true) / \
        np.linalg.norm(g_total_true)
    assert rel < 0.05, rel


def test_calib_data_deterministic_and_seekable():
    cfg = CalibConfig(n_samples=64, seq_len=8, batch_size=4, vocab=100)
    a, b = CalibDataset(cfg), CalibDataset(cfg)
    for _ in range(5):
        np.testing.assert_array_equal(next(iter(a))["tokens"],
                                      next(iter(b))["tokens"])
    c = CalibDataset(cfg)
    c.skip_to(5)
    np.testing.assert_array_equal(next(iter(a))["tokens"],
                                  next(iter(c))["tokens"])


def test_sharded_qft_matches_single_device_first_step():
    """launch/train.sharded_qft on a 4-device mesh: the state lands spread
    over the devices, and the first QFT step from one init gives the same
    loss and grad norm sharded as on device 0 alone — chip_smoke.py's
    ``--four-chips`` check, on 4 virtual CPU devices at SMOKE size.

    A subprocess: the device count is fixed when jax starts."""
    import os
    import pathlib
    import subprocess
    import sys
    import textwrap
    root = pathlib.Path(__file__).resolve().parents[1]
    code = textwrap.dedent("""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, ".")
        import jax
        import chip_smoke as cs
        from repro.configs import get_config
        from repro.launch.mesh import make_elastic_mesh
        from repro.models import set_runtime
        set_runtime(act_spec=("data",))
        mesh = make_elastic_mesh(4, model_parallel=4)
        cfg = get_config("qwen3-8b", smoke=True)
        metrics, live = cs.sharded_steps(cfg, mesh, steps=1)
        assert sorted(live) == [0, 1, 2, 3], live
        assert max(live.values()) < sum(live.values()) / 2, live
        sharded, single = cs.first_step_pair(cfg, mesh, jax.devices()[0])
        for k in ("loss", "grad_norm"):
            rel = abs(sharded[k] - single[k]) / abs(single[k])
            assert rel <= cs.SHARDED_STEP_RTOL, (k, sharded, single)
        print("SHARDED_QFT_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, cwd=root,
                         env={**os.environ, "PYTHONPATH": str(root / "src"),
                              "JAX_PLATFORMS": "cpu"})
    assert "SHARDED_QFT_OK" in out.stdout, out.stderr[-2000:]


def test_sharded_qft_setup_phases_are_profiler_spans(tmp_path):
    """``launch/train.ShardedQFT``'s set-up phases (placing the teacher,
    preparing the student, initialising Adam) are ``repro:qft.*`` host
    spans in a profiler session; on a one-device mesh at SMOKE size."""
    import glob
    from jax.profiler import ProfileData
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import ShardedQFT, batch_like, random_state
    from repro.models import set_runtime
    cfg = get_config("qwen3-8b", smoke=True)
    data = CalibDataset(CalibConfig(n_samples=16, seq_len=16, batch_size=2,
                                    vocab=cfg.vocab))
    calib = [{k: jnp.asarray(v) for k, v in next(iter(data)).items()}
             for _ in range(2)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        qft = ShardedQFT(cfg, deployment_oriented(), make_host_mesh(),
                         batch_like(calib[0]))
        jax.block_until_ready(random_state(qft, calib))
    finally:
        jax.profiler.stop_trace()
        set_runtime(act_spec=None)     # the builder pins it process-wide
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"repro:qft.teacher", "repro:qft.student",
            "repro:qft.opt_init"} <= names
