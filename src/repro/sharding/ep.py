"""Expert-parallel MoE dispatch via shard_map (the §Perf collective fix).

Baseline (models/moe.py ``sorted`` mode under plain pjit) runs a GLOBAL
argsort + scatter over all tokens — GSPMD lowers that to distributed-sort
collectives, observed ~100× the useful traffic on deepseek-v2 train_4k
(collective term 1422 s, EXPERIMENTS.md §Perf).

Here tokens enter the block sequence-sharded over the `model` axis, so each
(data, model) device routes a DISTINCT T_loc = B_loc·S/tp token slice with a
purely LOCAL sort, and only expert buffers move — one all-to-all pair on the
model axis per layer (the canonical EP pattern):

  1. local top-k routing + sort-based capacity dispatch → buf [E, C, D]
  2. all_to_all over `model`: [tp, E_loc, C, D] → [E_loc, tp, C, D]
  3. local quantized expert FFN (offline subgraph on the E_loc shard)
  4. all_to_all back; local weighted combine.

Traffic per device per layer ≈ 2·E·C·D·(tp−1)/tp bytes — near the
information-theoretic minimum for token-choice EP.  Differentiable end to
end (all_to_all transposes to itself), so QFT gradients flow through
dispatch to expert weights AND scale DoF.

Decode steps (T_loc < tp tokens) keep the baseline path — dispatch there is
trivially cheap.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core import dof
from ..core.plan import plan_view
from ..core.qconfig import QuantConfig
from ..models.config import ModelConfig
from ..models import moe as moe_lib

Params = dict[str, Any]


def make_ep_moe(mesh: Mesh, cfg: ModelConfig, qcfg: QuantConfig | None,
                dp_axes=("data",), tp_axis: str = "model", plan=None):
    """Returns moe_fn(x[B,S,d], layer_params) -> y[B,S,d]; register with
    models.set_runtime(moe_fn=...) to replace the routed-experts path.

    ``plan``: the resolved QuantPlan — expert/router fake-quant bits are
    looked up once here (the MoE block always lives at ``layers.mlp``), so
    the EP path trains on the same grid as the in-graph path and the export.
    """
    pv = plan_view(plan).child("layers", "mlp")
    e = cfg.moe
    tp = mesh.shape[tp_axis]
    E = e.n_experts_padded
    assert E % tp == 0, (E, tp)
    E_loc = E // tp

    x_spec = P(dp_axes, tp_axis, None)        # sequence-sharded over model

    def pspec(path, leaf):
        keys = [str(k.key) for k in path if hasattr(k, "key")]
        # any expert-stacked leaf (w [E,in,out], b [E,out], log_swr [E,..])
        if keys and keys[0] in ("up", "gate", "down") \
                and leaf.shape and leaf.shape[0] == E:
            return P(tp_axis, *([None] * (leaf.ndim - 1)))   # EP on E axis
        return P()

    def local_moe(x, p, qcfg):
        """Per-device body. x: [B_loc, S_loc, d]; expert leaves E_loc-sized."""
        B, S, d = x.shape
        xt = x.reshape(B * S, d)
        T = B * S
        K = e.top_k
        C = max(int(T * K / max(e.n_experts, 1) * e.capacity_factor), 1)

        probs = moe_lib._router_probs(xt, p, cfg, qcfg,
                                      plan=pv)               # router replicated
        topv, topi = jax.lax.top_k(probs, K)
        gates = topv / jnp.maximum(jnp.sum(topv, -1, keepdims=True), 1e-9)
        flat_e = topi.reshape(-1)
        flat_g = gates.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T), K)
        order = jnp.argsort(flat_e, stable=True)             # LOCAL sort
        e_s, t_s, g_s = flat_e[order], flat_t[order], flat_g[order]
        counts = jnp.bincount(flat_e, length=E)
        offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                                   jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(T * K) - offsets[e_s]
        keep = pos < C
        dest = jnp.where(keep, e_s * C + pos, E * C)
        buf = jnp.zeros((E * C + 1, d), x.dtype).at[dest].set(
            xt[t_s], mode="drop")[:-1]
        buf = buf.reshape(E, C, d)

        # ---- exchange: every expert block to its home model-rank ----------
        # tiled all_to_all: [E, C, d] -> [E_loc, tp·C, d]; symmetric transpose
        h = jax.lax.all_to_all(buf, tp_axis,
                               split_axis=0, concat_axis=1, tiled=True)

        # ---- local quantized expert FFN (offline subgraph, local shard) ---
        ins = p.get("in_stream")
        log_sa = None if ins is None else ins["log_sa"]
        if qcfg is not None:
            h = dof.stream_fake_quant(h, ins, qcfg)
        w_up = dof.effective_weight(p["up"], qcfg, log_sa, h.dtype,
                                    bits=pv.bits("up"))
        w_gate = dof.effective_weight(p["gate"], qcfg, log_sa, h.dtype,
                                      bits=pv.bits("gate"))
        a = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, w_gate)) * \
            jnp.einsum("ecd,edf->ecf", h, w_up)
        acts = p.get("act_stream")
        if qcfg is not None:
            a = dof.stream_fake_quant(a, acts, qcfg)
        w_down = dof.effective_weight(
            p["down"], qcfg, None if acts is None else acts["log_sa"], h.dtype,
            bits=pv.bits("down"))
        y = jnp.einsum("ecf,efd->ecd", a, w_down)            # [E_loc, tp·C, d]

        # ---- return tokens to their owners ---------------------------------
        back = jax.lax.all_to_all(y, tp_axis, split_axis=1, concat_axis=0,
                                  tiled=True)                # [E, C, d]
        y_all = back.reshape(E * C, d)

        y_tok = jnp.where(keep[:, None], y_all[jnp.clip(dest, 0, E * C - 1)],
                          0.0)
        out = jnp.zeros((T, d), y.dtype).at[t_s].add(
            y_tok * g_s[:, None].astype(y.dtype))
        return out.reshape(B, S, d)

    def moe_fn(x, p):
        if x.shape[1] % tp != 0:          # decode: trivial dispatch, baseline
            return None
        # teacher (FP) layers flow through the same override: detect by the
        # presence of quant DoF and drop qcfg for them
        qcfg_eff = qcfg if isinstance(p.get("up"), dict) and \
            "log_swr" in p["up"] else None
        import functools
        body = functools.partial(local_moe, qcfg=qcfg_eff)
        p_specs = jax.tree_util.tree_map_with_path(pspec, p)
        fn = jax.shard_map(body, mesh=mesh, in_specs=(x_spec, p_specs),
                           out_specs=x_spec, check_vma=False)
        return fn(x, p)

    return moe_fn
