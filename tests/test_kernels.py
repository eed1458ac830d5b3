"""Per-kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fakequant import pack_int4
from repro.kernels import (decode_attention, decode_tiles_ok,
                           fake_quant_kernel, flash_attention, quant_matmul)
from repro.kernels import ref
from repro.kernels.decode_attention import (paged_block_pages,
                                            paged_decode_attention,
                                            paged_decode_tiles_ok)


@pytest.mark.parametrize("M,K,N,bm,bn,bk", [
    (64, 128, 64, 64, 64, 64),
    (128, 256, 128, 64, 128, 128),
    (32, 64, 256, 32, 64, 64),
    (128, 512, 64, 128, 64, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("layout", ["channel", "group"])
@pytest.mark.parametrize("variant", ["int8dot", "dequant"])
def test_quant_matmul_sweep(M, K, N, bm, bn, bk, dtype, layout, variant):
    """Both kernel bodies vs XLA oracle under both scale layouts."""
    key = jax.random.PRNGKey(M + K + N)
    x = jax.random.normal(key, (M, K), dtype)
    q4 = jax.random.randint(key, (K, N), -7, 8).astype(jnp.int8)
    qw = pack_int4(q4, axis=0)
    swl = (jnp.exp(jax.random.normal(key, (K,)) * 0.2) * 0.05).astype(jnp.float32)
    if layout == "group":
        g = min(bk, 64)                  # whole groups per K-tile (bk % g == 0)
        swr = jnp.exp(jax.random.normal(key, (K // g, N)) * 0.2
                      ).astype(jnp.float32)
    else:
        swr = jnp.exp(jax.random.normal(key, (N,)) * 0.2).astype(jnp.float32)
    y = quant_matmul(x, qw, swl, swr, bm=bm, bn=bn, bk=bk, interpret=True,  # qft: noqa[QFT004] parity oracle
                     variant=variant)
    yr = ref.quant_matmul_ref(x, qw, swl, swr)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("layout", ["layerwise", "channel", "group32",
                                    "group64", "group128"])
@pytest.mark.parametrize("variant", ["int8dot", "dequant"])
def test_quant_matmul_group_sizes(layout, variant):
    """Every QLayout against the oracle: layerwise (scalar broadcast to [N]),
    channel [N], group:{32,64,128} [K/g, N] — the CI "Kernel parity" sweep."""
    key = jax.random.PRNGKey(17)
    M, K, N = 64, 256, 128
    x = jax.random.normal(key, (M, K), jnp.float32)
    q4 = jax.random.randint(key, (K, N), -7, 8).astype(jnp.int8)
    qw = pack_int4(q4, axis=0)
    swl = (jnp.exp(jax.random.normal(key, (K,)) * 0.2) * 0.05
           ).astype(jnp.float32)
    if layout == "layerwise":
        swr = jnp.full((N,), 0.013, jnp.float32)      # scalar grid, rank-1 form
    elif layout == "channel":
        swr = jnp.exp(jax.random.normal(key, (N,)) * 0.2).astype(jnp.float32)
    else:
        g = int(layout.removeprefix("group"))
        swr = jnp.exp(jax.random.normal(key, (K // g, N)) * 0.2
                      ).astype(jnp.float32)
    y = quant_matmul(x, qw, swl, swr, bk=128, interpret=True, variant=variant)  # qft: noqa[QFT004] parity oracle
    yr = ref.quant_matmul_ref(x, qw, swl, swr)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("S,T,Hkv,G,hd,bk", [
    (3, 64, 2, 2, 16, 64),          # single KV block
    (5, 128, 2, 2, 8, 32),          # 4 blocks, dead-block skip exercised
    (4, 256, 1, 4, 32, 128),        # MQA-style grouping
    (2, 64, 4, 1, 16, 64),          # no grouping (Hkv == H)
])
def test_decode_attention_parity(S, T, Hkv, G, hd, bk):
    """Flash-decode kernel vs the masked-XLA vector-pos oracle (`_sdpa`) at
    odd per-slot lengths, including a pos=0 slot (length 1: only the token
    written this step is visible)."""
    from repro.models.attention import _sdpa
    assert decode_tiles_ok(T, bk)
    key = jax.random.PRNGKey(S * T + hd)
    H = Hkv * G
    q = jax.random.normal(key, (S, 1, H, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (S, T, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (S, T, Hkv, hd))
    # odd lengths: pos=0 (length 1), mid-block, block-aligned, full cache
    lengths = (jnp.asarray([1, T // 3 + 1, bk, T, T // 2 + 3], jnp.int32)[:S]
               % (T + 1)).clip(1)
    o = decode_attention(q[:, 0].reshape(S, Hkv, G, hd), k, v, lengths,
                         bk=bk, interpret=True)  # qft: noqa[QFT004] parity oracle
    orf = _sdpa(q, k, v, causal=False, q_offset=lengths - 1, kv_len=lengths)
    np.testing.assert_allclose(
        np.asarray(o.reshape(S, 1, H, hd)), np.asarray(orf),
        rtol=2e-5, atol=2e-5)


def test_decode_tiles_ok_gate():
    assert decode_tiles_ok(512) and decode_tiles_ok(64) and decode_tiles_ok(128)
    assert decode_tiles_ok(96)              # bk clamps to max_len: one block
    assert not decode_tiles_ok(0)
    assert not decode_tiles_ok(200, bk=128)  # 200 % 128 != 0: no clean tiling


@pytest.mark.parametrize("P,Hkv,G,hd,n_pg", [
    (8, 2, 1, 64, 5),          # G 1 (Qwen2-MoE's grouping), hd 64
    (16, 8, 4, 128, 40),       # Qwen3-8B's heads: 3 blocks of 16 pages
    (32, 8, 3, 128, 20),       # G 3 (Phi-4-mini): 3 blocks of 8 pages
    (16, 4, 3, 64, 6),
    (32, 2, 4, 128, 3),
])
def test_paged_decode_attention_parity(P, Hkv, G, hd, n_pg):
    """The paged kernel (interpret mode) vs `_paged_sdpa` over the gathered
    view: lengths 1, P-1, P, P+1 and max_len, shuffled non-contiguous page
    tables over a pool with spare pages, a retired slot whose table row all
    points at the trash page, one layer of two-layer stacks.  The math
    holds at any shape; `paged_decode_tiles_ok` decides where it runs on a
    chip."""
    from repro.models.attention import _paged_sdpa
    T = n_pg * P
    lengths = jnp.asarray([1, P - 1, P, P + 1, T, 1], jnp.int32)
    S, N = lengths.shape[0], lengths.shape[0] * n_pg + 7
    ks = jax.random.split(jax.random.PRNGKey(P * 1000 + Hkv * 100 + G), 6)
    pool_k, pool_v = (jax.random.randint(k, (2, N + 1, P, Hkv, hd), -127,
                                         128).astype(jnp.int8)
                      for k in ks[:2])
    pt = np.random.RandomState(P + hd).permutation(N)[:S * n_pg]
    pt = pt.reshape(S, n_pg)
    pt[-1] = N                                    # retired: the trash page
    pt = jnp.asarray(pt, jnp.int32)
    q = jax.random.normal(ks[2], (S, Hkv, G, hd), jnp.float32)
    k_scale = jnp.exp(0.3 * jax.random.normal(ks[3], (2, S, Hkv))) * 0.02
    v_scale = jnp.exp(0.3 * jax.random.normal(ks[4], (2, S, Hkv))) * 0.02
    out = paged_decode_attention(q, pool_k, pool_v, lengths, pt, k_scale,
                                 v_scale, layer=1)
    k8 = pool_k[1][pt].reshape(S, T, Hkv, hd)
    v8 = pool_v[1][pt].reshape(S, T, Hkv, hd)
    want = _paged_sdpa(q.reshape(S, 1, Hkv * G, hd), k8, v8, lengths,
                       k_scale[1], v_scale[1])
    np.testing.assert_allclose(np.asarray(out).reshape(want.shape),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


def test_bf16_split_is_exact():
    """The paged kernel's dots rest on f32 == the sum of its three bf16
    pieces, exactly, from tiny to huge magnitudes."""
    from repro.kernels.decode_attention import _bf16_split
    a = jax.random.normal(jax.random.PRNGKey(3), (64, 128), jnp.float32)
    a = a * jnp.exp2(jnp.arange(64, dtype=jnp.float32)[:, None] * 3 - 90)
    parts = np.asarray(_bf16_split(a).astype(jnp.float32), np.float64)
    np.testing.assert_array_equal(parts[:64] + parts[64:128] + parts[128:],
                                  np.asarray(a, np.float64))


def test_paged_decode_tiles_ok_gate():
    """A page's [P*Hkv, hd] slab is a free view of the int8 pool only with
    whole (8, 128) tiles of heads, and lands on an int8 (32, 128) VMEM tile
    only with P*Hkv a multiple of 32; a block is 256 KiB of K."""
    assert paged_decode_tiles_ok(16, 8, 128)      # Qwen3, Phi-4-mini
    assert paged_decode_tiles_ok(16, 16, 128)     # Qwen2-MoE: 16 KV heads
    assert paged_decode_tiles_ok(8, 8, 128) and paged_decode_tiles_ok(4, 8, 256)
    assert not paged_decode_tiles_ok(16, 4, 128)  # Qwen2-VL: 4 KV heads
    assert not paged_decode_tiles_ok(16, 8, 96)   # lanes not whole tiles
    assert not paged_decode_tiles_ok(16, 8, 64)
    assert not paged_decode_tiles_ok(2, 8, 128)   # 16 rows: half a tile
    assert not paged_decode_tiles_ok(16, 2, 16)   # the smoke configs
    assert not paged_decode_tiles_ok(0, 8, 128)
    assert paged_block_pages(16, 8, 128) == 16    # 256 tokens a block
    assert paged_block_pages(32, 8, 128) == 8
    assert paged_block_pages(16, 64, 256) == 1


@pytest.mark.parametrize("R,C,bits", [(64, 128, 4), (128, 128, 8), (32, 256, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fake_quant_sweep(R, C, bits, dtype):
    key = jax.random.PRNGKey(R * C)
    x = (jax.random.normal(key, (R, C)) * 0.1).astype(dtype)
    s = jnp.full((1, C), 0.01, jnp.float32).astype(dtype)
    y = fake_quant_kernel(x, jnp.broadcast_to(s, x.shape), bits, 32, 64, True)
    yr = ref.fake_quant_ref(x, s, bits)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), rtol=1e-5, atol=1e-6)


def test_fake_quant_ste_gradient():
    x = jnp.array([[0.03, -0.02, 0.5, -0.5]])       # last two clip at 4b,s=.01
    s = jnp.full_like(x, 0.01)
    g = jax.grad(lambda a: jnp.sum(fake_quant_kernel(a, s, 4, 1, 4, True)))(x)
    np.testing.assert_array_equal(np.asarray(g), [[1.0, 1.0, 0.0, 0.0]])


@pytest.mark.parametrize("S,hd,bq,bk", [(128, 64, 64, 64), (256, 32, 64, 128),
                                        (64, 128, 32, 32)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(S, hd, bq, bk, causal):
    key = jax.random.PRNGKey(S + hd)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (2, S, hd))
               for i in range(3))
    o = flash_attention(q, k, v, causal=causal, bq=bq, bk=bk, interpret=True)  # qft: noqa[QFT004] parity oracle
    orf = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_bf16():
    key = jax.random.PRNGKey(7)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (2, 128, 64),
                                 jnp.bfloat16) for i in range(3))
    o = flash_attention(q, k, v, causal=True, bq=64, bk=64, interpret=True)  # qft: noqa[QFT004] parity oracle
    orf = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(orf, np.float32), rtol=3e-2, atol=3e-2)


def test_qlinear_deployed_matches_effective_weight():
    """Deployment kernel path ≡ training-time effective weight (end to end)."""
    from repro.core import dof, permissive
    from repro.kernels.ops import qlinear_deployed
    cfg = permissive()
    key = jax.random.PRNGKey(0)
    p = dof.init_qlinear(key, 64, 32, cfg)
    p = dof.mmse_init_qlinear(p, cfg)
    x = jax.random.normal(key, (8, 64), jnp.float32)
    ex = dof.export_qlinear(p, cfg)
    y_kernel = qlinear_deployed(x, ex, use_pallas=True, interpret=True)  # qft: noqa[QFT004] parity oracle
    w_eff = dof.effective_weight(p, cfg, compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(x @ w_eff),
                               rtol=2e-4, atol=2e-4)


def test_qlinear_deployed_consumes_deploy_plan():
    """The plan object routes the kernel (use_pallas/interpret) — same math."""
    from repro.core import dof, permissive
    from repro.kernels.ops import qlinear_deployed
    from repro.serve.deploy import make_deploy_plan
    cfg = permissive()
    key = jax.random.PRNGKey(1)
    p = dof.mmse_init_qlinear(dof.init_qlinear(key, 64, 32, cfg), cfg)
    x = jax.random.normal(key, (4, 64), jnp.float32)
    ex = dof.export_qlinear(p, cfg)
    plan = make_deploy_plan(cfg, use_pallas=True, interpret=True)  # qft: noqa[QFT004] parity oracle
    y_plan = qlinear_deployed(x, ex, plan=plan)
    w_eff = dof.effective_weight(p, cfg, compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y_plan), np.asarray(x @ w_eff),
                               rtol=2e-4, atol=2e-4)


def test_qlinear_deployed_int8_exempt_layer():
    """Unpacked int8 exports (exempt layers) take the dequant-matmul branch."""
    from repro.core import dof, permissive
    from repro.kernels.ops import qlinear_deployed
    cfg = permissive()
    key = jax.random.PRNGKey(2)
    p = dof.mmse_init_qlinear(dof.init_qlinear(key, 32, 16, cfg), cfg, bits=8)
    x = jax.random.normal(key, (4, 32), jnp.float32)
    ex = dof.export_qlinear(p, cfg, bits=8)
    assert ex["q"].dtype == jnp.int8                   # not nibble-packed
    y = qlinear_deployed(x, ex)
    w_eff = dof.effective_weight(p, cfg, compute_dtype=jnp.float32, bits=8)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w_eff),
                               rtol=2e-4, atol=2e-4)


def test_qlinear_deployed_int8_exempt_group_layout():
    """The int8-exempt branch keeps integer weights in the dot with per-group
    partial sums (mirror of the int8dot kernel restructure) — check it against
    the explicit dequantize-then-matmul math for a group:[K/g, N] s_wr."""
    from repro.core.fakequant import expand_group_scale
    from repro.kernels.ops import qlinear_deployed
    key = jax.random.PRNGKey(5)
    K, N, g = 96, 24, 32                      # odd shapes: XLA path, no tiling
    q = jax.random.randint(key, (K, N), -127, 128).astype(jnp.int8)
    s_wl = jnp.exp(jax.random.normal(key, (K,)) * 0.2) * 0.05
    s_wr = jnp.exp(jax.random.normal(jax.random.fold_in(key, 1),
                                     (K // g, N)) * 0.2)
    x = jax.random.normal(jax.random.fold_in(key, 2), (7, K), jnp.float32)
    y = qlinear_deployed(x, {"q": q, "s_wl": s_wl, "s_wr": s_wr})
    w = q.astype(jnp.float32) * s_wl[:, None] * expand_group_scale(s_wr, K,
                                                                   axis=0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                               rtol=2e-4, atol=2e-4)
