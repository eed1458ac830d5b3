"""Sharding policies: PartitionSpec trees per (arch × shape × mesh).

Axes: ``pod``/``data`` = pure DP (+FSDP over ``data``); ``model`` = TP/EP.
Rules are path-based (we control all param names) with a divisibility-aware
helper so head/expert/vocab padding interacts safely with any mesh.

Baseline policy (paper-faithful system, before §Perf hillclimbing):
- Megatron TP: qkv/up col-parallel, o/down row-parallel, vocab-sharded
  embed+head; experts EP-sharded on `model`; FSDP on `data` for weights,
  optimizer state and the (frozen) teacher.
- quant-DoF vectors (log_s*, streams, norms, biases) replicated — they are
  O(channels) and train data-parallel.
"""
from __future__ import annotations

import dataclasses


import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig


def axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, (tuple, list)):
        out = 1
        for n in name:
            out *= axis_size(mesh, n)
        return out
    return mesh.shape[name]


def div_axes(size: int, axes, mesh: Mesh):
    """Longest prefix of ``axes`` whose product divides ``size`` (or None)."""
    if isinstance(axes, str):
        axes = (axes,)
    chosen: list = []
    prod = 1
    for a in axes:
        if size % (prod * axis_size(mesh, a)) == 0:
            chosen.append(a)
            prod *= axis_size(mesh, a)
        else:
            break
    if not chosen:
        return None
    return tuple(chosen) if len(chosen) > 1 else chosen[0]


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """Axis-name knobs; the §Perf pass tunes these per cell."""
    dp: tuple[str, ...] = ("data",)          # ("pod","data") multi-pod
    tp: str = "model"
    fsdp: str | None = "data"                # None → pure DP (no ZeRO)
    fsdp_teacher: bool = True
    remat: bool = True


def _last_keys(path) -> list[str]:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
    return out


# weights whose OUT dim is TP-sharded (col-parallel) / IN dim (row-parallel)
_COL = {"wq", "wk", "wv", "up", "gate", "q_up", "k_up", "v_up", "in_proj",
        "shared_up", "shared_gate"}
_ROW = {"wo", "down", "out_proj", "shared_down"}
_REPL_LIN = {"router", "q_down", "kv_down", "frame_proj"}   # small in+out


def param_spec(path, leaf, cfg: ModelConfig, mesh: Mesh,
               pol: ShardingPolicy) -> P:
    keys = _last_keys(path)
    name = keys[-1]
    parent = keys[-2] if len(keys) > 1 else ""
    shape = leaf.shape
    nd = len(shape)
    tp, fsdp = pol.tp, pol.fsdp

    def spec(*dims):
        # pad leading axes (layer/group stacking) with None
        return P(*([None] * (nd - len(dims)) + list(dims)))

    if name in ("w", "q"):
        # "w": training master weights; "q": exported (possibly int4-packed,
        # in-dim halved) deployment weights — same layout rules apply.
        if fsdp is None or name == "q":
            fs = None                      # serving path: no ZeRO sharding
        else:
            fs = fsdp
        if parent == "embed":
            return P(div_axes(shape[0], tp, mesh),
                     div_axes(shape[1], fs, mesh) if fs else None)
        if parent == "lm_head":
            return P(div_axes(shape[0], fs, mesh) if fs else None,
                     div_axes(shape[1], tp, mesh))
        is_expert = (parent in ("up", "gate", "down") and nd >= 3
                     and "mlp" in keys and cfg.moe is not None)
        if is_expert:
            # [L, E, in, out] (or [E, in, out]): EP on experts
            ein = div_axes(shape[-2], fs, mesh) if fs else None
            return spec(div_axes(shape[-3], tp, mesh), ein, None)
        if parent in _COL:
            return spec(div_axes(shape[-2], fs, mesh) if fs else None,
                        div_axes(shape[-1], tp, mesh))
        if parent in _ROW:
            return spec(div_axes(shape[-2], tp, mesh),
                        div_axes(shape[-1], fs, mesh) if fs else None)
        if parent in _REPL_LIN:
            return spec(div_axes(shape[-2], fs, mesh) if fs else None, None)
        # conv / unknown: replicate
        return P(*([None] * nd))
    # scale vectors (s_wl/s_wr/log_*) are O(channels): replicate
    if name == "conv_w":
        return spec(None, div_axes(shape[-1], tp, mesh))
    if name in ("b", "conv_b", "g", "log_swr", "log_sa", "zp", "log_s",
                "A_log", "D", "dt_bias", "norm_g"):
        return P(*([None] * nd))
    return P(*([None] * nd))


def params_shardings(params_struct, cfg: ModelConfig, mesh: Mesh,
                     pol: ShardingPolicy):
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_struct)
    specs = [NamedSharding(mesh, param_spec(p, l, cfg, mesh, pol))
             for p, l in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def opt_state_shardings(params_shardings_tree, mesh: Mesh):
    """m/v mirror the param shardings (ZeRO: state sharded like weights)."""
    return {"m": params_shardings_tree, "v": params_shardings_tree,
            "step": NamedSharding(mesh, P())}


def batch_shardings(batch_struct, mesh: Mesh, pol: ShardingPolicy):
    dp = pol.dp

    def one(path, leaf):
        b = div_axes(leaf.shape[0], dp, mesh)
        return NamedSharding(mesh, P(*([b] + [None] * (len(leaf.shape) - 1))))

    return jax.tree_util.tree_map_with_path(one, batch_struct)
