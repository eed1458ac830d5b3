"""Serving path + MoE dispatch correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import deployment_oriented, permissive
from repro.models import (ModelConfig, MoEConfig, forward, init_cache,
                          init_model)
from repro.models.config import SSMConfig
from repro.models.moe import moe_block
from repro.serve.deploy import deploy_view, export_for_layers
from repro.serve.engine import Engine, Request, ServeConfig

QCFG = deployment_oriented()


def test_decode_matches_full_forward_dense():
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab=64, head_dim=8,
                      scan_layers=False, remat=False)
    key = jax.random.PRNGKey(0)
    p = init_model(key, cfg, QCFG)
    toks = jax.random.randint(key, (2, 12), 0, 64)
    full = forward(p, cfg, QCFG, {"tokens": toks})
    cache = init_cache(cfg, 2, 16)
    pre = forward(p, cfg, QCFG, {"tokens": toks[:, :-1]}, cache=cache)
    dec = forward(p, cfg, QCFG, {"tokens": toks[:, -1:]}, cache=pre["cache"])
    np.testing.assert_allclose(
        np.asarray(dec["logits"][:, 0], np.float32),
        np.asarray(full["logits"][:, -1], np.float32), rtol=0.1, atol=0.15)


def test_export_deploy_view_matches_student():
    """Deployed (int4-packed) forward ≈ fake-quant student forward."""
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab=64, head_dim=8,
                      scan_layers=False, remat=False)
    key = jax.random.PRNGKey(0)
    qcfg = permissive()      # weight-only: deployed path has FP activations
    p = init_model(key, cfg, qcfg)
    ex = export_for_layers(p, qcfg)
    dv = deploy_view(ex, qcfg)
    toks = jax.random.randint(key, (2, 8), 0, 64)
    h_student = forward(p, cfg, qcfg, {"tokens": toks})["hidden"]
    h_deploy = forward(dv, cfg, None, {"tokens": toks})["hidden"]
    err = float(jnp.linalg.norm(h_student - h_deploy)
                / jnp.linalg.norm(h_student))
    assert err < 0.05, err
    # and the artifact really is packed: uint8, half the in-dim
    q = ex["layers"]["mlp"]["up"]["q"]
    assert q.dtype == jnp.uint8 and q.shape[-2] == 16  # 32/2


def test_engine_generates_batched():
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab=64, head_dim=8,
                      scan_layers=False, remat=False)
    p = init_model(jax.random.PRNGKey(0), cfg, permissive())
    eng = Engine(cfg, permissive(), p,
                 ServeConfig(max_slots=4, max_len=64, prefill_chunk=8))
    outs = eng.generate([Request(prompt=[1, 2, 3], max_new_tokens=5),
                         Request(prompt=[7, 8], max_new_tokens=3)])
    assert len(outs) == 2 and len(outs[0]) == 5 and len(outs[1]) == 3
    assert all(0 <= t < cfg.vocab_padded for o in outs for t in o)


MOE_CFG = ModelConfig(
    name="m", family="moe", n_layers=1, d_model=32, n_heads=4, n_kv_heads=4,
    d_ff=0, vocab=64, head_dim=8, scan_layers=False, remat=False,
    moe=MoEConfig(n_experts=4, top_k=2, n_shared=1, d_ff_expert=32,
                  capacity_factor=4.0))   # high capacity → no drops

SSM_CFG = ModelConfig(
    name="s", family="ssm", n_layers=2, d_model=32, n_heads=0, n_kv_heads=0,
    d_ff=0, vocab=64, head_dim=8, tie_embeddings=True, scan_layers=False,
    remat=False,
    ssm=SSMConfig(d_state=8, d_conv=4, expand=2, head_dim=8, chunk=8))


@pytest.mark.parametrize("cfg", [MOE_CFG, SSM_CFG], ids=["moe", "ssm"])
def test_engine_from_artifact_parity_moe_ssm(cfg):
    """Serving coverage beyond dense: the artifact path (from_artifact) must
    produce the same tokens as the direct student-export constructor — for
    both previously-untested families, with queueing over a small pool."""
    qcfg = permissive()
    p = init_model(jax.random.PRNGKey(0), cfg, qcfg)
    scfg = ServeConfig(max_slots=2, max_len=48, prefill_chunk=8)
    direct = Engine(cfg, qcfg, p, scfg)
    via = Engine.from_artifact(
        cfg, direct.plan, direct.exported,
        ServeConfig(max_slots=2, max_len=48, prefill_chunk=8))
    reqs = [Request(prompt=[1, 2, 3], max_new_tokens=5),
            Request(prompt=[7, 8], max_new_tokens=3),
            Request(prompt=[4, 5, 6, 7], max_new_tokens=4)]  # 3 reqs, 2 slots
    a, b = direct.generate(reqs), via.generate(reqs)
    assert a == b
    assert [len(o) for o in a] == [5, 3, 4]
    assert all(0 <= t < cfg.vocab_padded for o in a for t in o)


def test_moe_sorted_matches_dense_dispatch():
    from repro.models.moe import init_moe
    key = jax.random.PRNGKey(0)
    p = init_moe(key, MOE_CFG, None)
    x = jax.random.normal(key, (1, 16, 32), jnp.float32)
    y_sorted = moe_block(x, p, MOE_CFG, None, mode="sorted")
    y_dense = moe_block(x, p, MOE_CFG, None, mode="dense")
    np.testing.assert_allclose(np.asarray(y_sorted), np.asarray(y_dense),
                               rtol=2e-3, atol=2e-4)


def test_moe_padding_experts_never_routed():
    import dataclasses
    from repro.models.moe import init_moe, _router_probs
    cfg = dataclasses.replace(
        MOE_CFG, moe=dataclasses.replace(MOE_CFG.moe, n_experts_padded=8))
    key = jax.random.PRNGKey(1)
    p = init_moe(key, cfg, None)
    x = jax.random.normal(key, (32, 32), jnp.float32)
    probs = _router_probs(x, p, cfg, None)
    assert probs.shape[-1] == 8
    assert float(jnp.max(probs[:, 4:])) == 0.0       # padded experts masked


def test_ssm_long_context_decode_is_o1_state():
    """SSM decode cost is independent of context length (long_500k cell)."""
    cfg = ModelConfig(name="s", family="ssm", n_layers=2, d_model=32,
                      n_heads=0, n_kv_heads=0, d_ff=0, vocab=64, head_dim=8,
                      tie_embeddings=True, scan_layers=False, remat=False,
                      ssm=SSMConfig(d_state=8, d_conv=4, expand=2, head_dim=8,
                                    chunk=8))
    cache = init_cache(cfg, 1, 0)
    sizes = [v.size for v in jax.tree.leaves(cache)]
    assert sum(sizes) < 10_000       # no sequence-length dimension anywhere


@pytest.mark.slow
def test_ep_shard_map_matches_sorted_dispatch():
    """sharding/ep.py all-to-all EP dispatch ≡ in-graph sorted dispatch.

    Runs in a subprocess with 8 forced host devices (the test process itself
    must keep the default single-device config for the other tests)."""
    import subprocess, sys, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.models.config import ModelConfig, MoEConfig
        from repro.models.moe import init_moe, moe_sorted
        from repro.sharding.ep import make_ep_moe
        from repro.launch.mesh import make_elastic_mesh
        from repro.core import deployment_oriented
        mesh = make_elastic_mesh(8, model_parallel=4)
        qcfg = deployment_oriented()
        cfg = ModelConfig(name="m", family="moe", n_layers=1, d_model=32,
                          n_heads=4, n_kv_heads=4, d_ff=0, vocab=64,
                          head_dim=8,
                          moe=MoEConfig(n_experts=8, top_k=2, n_shared=0,
                                        d_ff_expert=16, capacity_factor=8.0))
        key = jax.random.PRNGKey(0)
        p = init_moe(key, cfg, qcfg)
        x = jax.random.normal(key, (2, 16, 32), jnp.float32)
        y_ref = moe_sorted(x.reshape(-1, 32), p, cfg, qcfg).reshape(2, 16, 32)
        with jax.set_mesh(mesh):
            moe_fn = make_ep_moe(mesh, cfg, qcfg, dp_axes=("data",))
            y = jax.jit(lambda x, p: moe_fn(x, p))(x, p)
            g = jax.jit(jax.grad(lambda p, x: jnp.sum(moe_fn(x, p)**2)))(p, x)
        err = float(jnp.max(jnp.abs(y - y_ref)))
        assert err < 1e-4, err
        nz = sum(int(jnp.any(gl != 0)) for gl in jax.tree.leaves(g))
        assert nz >= 8, nz
        print("EP_TEST_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"})
    assert "EP_TEST_OK" in out.stdout, out.stderr[-2000:]
