"""Deployment export: freeze the offline subgraph into serving constants.

``export_model`` walks the trained student tree, runs each linear's offline
subgraph once (quantize → int4-pack), and drops the FP masters, streams and
DoF — producing the artifact a compiler would burn into the accelerator.
``deploy_view`` reconstructs a forward-compatible params tree whose weights
are dequantized on the fly inside the jitted serving step (unpack+scale fuse
into the matmul's producer; on real TPUs kernels/quant_matmul.py does this in
VMEM tiles).

Per-tensor decisions (bits, layout, stream tie, packing) come from the
resolved :class:`repro.core.plan.QuantPlan` carried by the
:class:`DeployPlan`; every walk here is path-qualified so lookups hit the
same names resolution produced.  Exported artifacts embed the serialized
plan as a uint8 leaf (``core.plan.PLAN_KEY``), so ``deploy_view`` /
``Engine.from_artifact`` can reconstruct the decisions from the artifact
alone.

Weight memory: 4-bit packed → ~0.5 byte/param held in HBM (visible in the
dry-run memory_analysis), vs 2 bytes bf16.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any

import jax
import jax.numpy as jnp

from ..core import dof
from ..core.fakequant import fake_quant, quantize
from ..core.plan import (PLAN_KEY, STREAM_KEYS, STREAM_OF,  # noqa: F401
                         QuantPlan, _is_qlinear, plan_from_array,
                         plan_to_array, resolve_plan)
from ..core.qconfig import QLayout, QuantConfig
from ..models import init_cache
from .kv_cache import PAGED_KV_FAMILIES as _PAGED_FAMILIES

Params = dict[str, Any]

# Deprecation shim only: the bare-name exemption set artifacts exported
# before QuantPlan were frozen under.  New code never reads this — the
# resolved plan is the single source of per-tensor bits.
_LEGACY_EXEMPT_8B = frozenset({"router", "lm_head", "fc"})


def _warn_legacy(what: str) -> None:
    warnings.warn(
        f"DeployPlan has no resolved QuantPlan; falling back to the legacy "
        f"bare-name heuristic for {what}. Re-export the artifact (new "
        f"exports embed the plan) or pass params= to make_deploy_plan.",
        DeprecationWarning, stacklevel=3)


@dataclasses.dataclass(frozen=True)
class DeployPlan:
    """Static deployment decisions, fixed at export time.

    The one object every consumer of an exported artifact reads — the serving
    engine (serve/engine.py), the deploy view, and the Pallas
    kernels/quant_matmul path.  Per-tensor truth lives in ``quant_plan``
    (path-qualified); the remaining fields are run-level routing knobs.
    """
    qcfg: QuantConfig
    arch: str = ""
    family: str = "dense"
    packed: bool = True               # legacy global default (shim path only)
    use_pallas: bool = False          # route matmuls through kernels/quant_matmul
    interpret: bool | None = None     # Pallas interpret mode; None → auto
                                      # (interpret everywhere except real TPU)
    layout: QLayout | None = None     # default weight-scale layout the export
                                      # ran under (None → qcfg.layout); the
                                      # per-tensor truth is quant_plan
    quant_plan: QuantPlan | None = None

    def spec_for(self, path: str):
        return None if self.quant_plan is None else self.quant_plan.get(path)

    def bits_for(self, path: str) -> int:
        if self.quant_plan is not None:
            return self.quant_plan.bits_for(path)
        _warn_legacy(f"bits_for({path!r})")
        name = path.rsplit(".", 1)[-1]
        return (self.qcfg.exempt_bits if name in _LEGACY_EXEMPT_8B
                else self.qcfg.w_bits)

    def is_packed(self, path: str) -> bool:
        if self.quant_plan is not None:
            return self.quant_plan.is_packed(path)
        return self.packed and self.bits_for(path) == 4


def make_deploy_plan(qcfg: QuantConfig, arch: str = "", family: str = "dense",
                     use_pallas: bool = False, interpret: bool | None = None,
                     quant_plan: QuantPlan | None = None, params=None,
                     model_cfg=None) -> DeployPlan:
    """Build the deploy plan; pass either a pre-resolved ``quant_plan`` or the
    (student) ``params`` tree to resolve one — exemptions then come from the
    resolved plan, never from a frozen name set."""
    if quant_plan is None and params is not None:
        quant_plan = resolve_plan(qcfg, params, model_cfg=model_cfg)
    return DeployPlan(qcfg=qcfg, arch=arch, family=family,
                      packed=qcfg.w_bits == 4, use_pallas=use_pallas,
                      interpret=interpret, layout=qcfg.layout,
                      quant_plan=quant_plan)


def plan_from_artifact(exported: Params) -> QuantPlan | None:
    """Recover the QuantPlan embedded in an exported artifact (None if the
    artifact predates plan embedding)."""
    arr = exported.get(PLAN_KEY) if isinstance(exported, dict) else None
    if arr is None:
        return None
    if isinstance(arr, (jax.core.Tracer, jax.ShapeDtypeStruct)):
        # inside jit/eval_shape the leaf is abstract and cannot be decoded —
        # not corruption; callers tracing deploy_view should resolve the
        # DeployPlan eagerly outside the trace
        return None
    try:
        return plan_from_array(arr)
    except Exception as e:                             # noqa: BLE001
        # a PRESENT-but-undecodable plan is corruption (truncated leaf,
        # future schema) — don't silently downgrade to the legacy heuristic
        warnings.warn(
            f"embedded quant plan failed to decode ({type(e).__name__}: {e});"
            f" falling back to legacy bare-name heuristics — the artifact "
            f"may be corrupted", UserWarning, stacklevel=3)
        return None


def _as_plan(plan_or_qcfg, params=None, artifact=None) -> DeployPlan:
    """Normalize to a DeployPlan with a resolved QuantPlan where possible:
    resolve from ``params`` (export side) or recover the plan embedded in
    ``artifact`` (deploy side).  Bare qcfg + neither → legacy shim path."""
    if isinstance(plan_or_qcfg, DeployPlan):
        plan = plan_or_qcfg
    else:
        plan = make_deploy_plan(plan_or_qcfg, params=params)
    if plan.quant_plan is None and artifact is not None:
        qp = plan_from_artifact(artifact)
        if qp is not None:
            plan = dataclasses.replace(plan, quant_plan=qp)
    if plan.quant_plan is None and params is not None:
        plan = dataclasses.replace(
            plan, quant_plan=resolve_plan(plan.qcfg, params))
    return plan


def init_slot_cache(cfg, max_slots: int, max_len: int,
                    dtype=jnp.bfloat16, kv: "KVSpec | None" = None) -> Params:
    """Preallocated slot-indexed serving cache for the continuous-batching
    engine: ``models.init_cache`` with every position leaf vectorized to a
    per-slot offset vector [max_slots].

    A scalar ``pos`` models one wave advancing in lockstep; continuous
    batching admits/evicts per slot, so each slot tracks its own sequence
    offset and the attention mask / K-V write location become per-slot
    (models/attention.py vector-pos path).  The cache shape is fixed at
    engine construction — admission scatters a freshly prefilled batch-1
    cache into one slot row; the decode step never reallocates.

    ``kv`` (a ``serve.kv_cache.KVSpec``) switches the standard-KV families
    to the **paged int8** layout: per-layer int8 page pools replacing the
    monolithic k/v rows, the shared int32 page table ``pt`` (initialized to
    the trash page), and per-layer per-slot per-kv-head MMSE scale leaves.
    ``kv=None`` keeps the monolithic full-precision layout — the
    conformance oracle and the layout for families paging doesn't cover.
    """
    if kv is not None:
        if cfg.family not in _PAGED_FAMILIES:
            raise ValueError(f"paged KV cache is not defined for family "
                             f"{cfg.family!r} (supported: {_PAGED_FAMILIES})")
        L = cfg.n_layers
        Hkv, hd = cfg.n_kv_heads_padded, cfg.head_dim
        pool = (L, kv.n_pages + 1, kv.page_size, Hkv, hd)
        return {
            "k": jnp.zeros(pool, jnp.int8),
            "v": jnp.zeros(pool, jnp.int8),
            # scale of 1.0 until install fits the slot's MMSE scales —
            # a live divide-by-zero can never happen on an empty slot
            "k_scale": jnp.ones((L, max_slots, Hkv), jnp.float32),
            "v_scale": jnp.ones((L, max_slots, Hkv), jnp.float32),
            "pt": jnp.full((max_slots, kv.max_pages_per_slot),
                           kv.trash_page, jnp.int32),
            "pos": jnp.zeros((max_slots,), jnp.int32),
        }
    cache = init_cache(cfg, max_slots, max_len, dtype)

    def fix(path, leaf):
        if (leaf is not None and path
                and getattr(path[-1], "key", None) == "pos"):
            return jnp.zeros((max_slots,), jnp.int32)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, cache)


def init_slot_state(max_slots: int) -> Params:
    """Per-slot decode bookkeeping + sampling state, all device-resident.

    One leaf per slot-vectorized degree of freedom of the slot-decode step
    (train/steps.make_slot_decode_step):

    - ``cur/done/counts/budget/eos`` — the PR 5 decode bookkeeping (current
      token, finished flag, emission count, token budget, stop token);
    - ``key [S, 2]`` — per-slot PRNG key chain (uint32 threefry keys),
      installed from ``PRNGKey(request.seed)`` at admission and split once
      per decode step, so draws are a function of (seed, step) only;
    - ``temp/top_k/top_p [S]`` — per-slot sampling parameters, written at
      admission from the Request.  The zeros/ones defaults are the greedy
      degenerate values, so a freshly reset pool decodes greedily.

    Keeping ALL of this in one device tree is what lets the engine run its
    decode loop with exactly one host transfer per step regardless of slot
    count or sampling configuration.
    """
    S = max_slots
    return {"cur": jnp.zeros((S,), jnp.int32),
            "done": jnp.ones((S,), bool),
            "counts": jnp.zeros((S,), jnp.int32),
            "budget": jnp.zeros((S,), jnp.int32),
            "eos": jnp.full((S,), -1, jnp.int32),
            "key": jnp.zeros((S, 2), jnp.uint32),
            "temp": jnp.zeros((S,), jnp.float32),
            "top_k": jnp.zeros((S,), jnp.int32),
            "top_p": jnp.ones((S,), jnp.float32)}


def _stream_log_sa(name: str, parent: Params):
    sname = STREAM_OF.get(name)
    stream = parent.get(sname) if sname else None
    return None if stream is None else stream["log_sa"]


def _export_node(path: tuple, node: Params, parent: Params,
                 plan: DeployPlan) -> Params:
    dotted = ".".join(path)
    return dof.export_qlinear(node, plan.qcfg,
                              log_sa_in=_stream_log_sa(path[-1], parent),
                              pack=plan.is_packed(dotted),
                              bits=plan.bits_for(dotted))


def _walk(tree, plan: DeployPlan, prefix: tuple = ()):
    qcfg = plan.qcfg
    if isinstance(tree, dict):
        if "w" in tree and "log_s" in tree:          # quantized embedding
            s = jnp.exp(tree["log_s"])
            q = quantize(tree["w"], s, qcfg.embed_bits, signed=True)
            return {"q": q.astype(jnp.int8), "s": s.astype(jnp.float32)}
        out = {}
        for k, v in tree.items():
            if k in STREAM_KEYS:
                continue                             # folded into weights
            if _is_qlinear(v):
                out[k] = _export_node(prefix + (k,), v, tree, plan)
            else:
                out[k] = _walk(v, plan, prefix + (k,))
        return out
    if isinstance(tree, (list, tuple)):
        return type(tree)(_walk(v, plan, prefix + (str(i),))
                          for i, v in enumerate(tree))
    return tree


def export_model(params: Params, plan_or_qcfg) -> Params:
    """Trained student params → deployment artifact (pure function; run under
    jit/eval_shape so 100B+ exports never materialize on the host).  The
    serialized QuantPlan rides along as a uint8 leaf under PLAN_KEY."""
    plan = _as_plan(plan_or_qcfg, params=params)
    out = _walk(params, plan)
    if plan.quant_plan is not None:
        out[PLAN_KEY] = plan_to_array(plan.quant_plan)
    return out


def _deploy_node(path: tuple, ex: Params, plan: DeployPlan,
                 dtype=jnp.bfloat16) -> Params:
    # whether q is nibble-packed is authoritative in the artifact itself
    # (uint8 ⇔ packed) — never second-guess it from plan/legacy lookups,
    # which can disagree for pre-plan artifacts with nonstandard exemptions
    out: Params = {"w": dof.dequantize_export(
        ex, dtype, packed=ex["q"].dtype == jnp.uint8)}
    if "b" in ex:
        out["b"] = ex["b"]
    return out


def deploy_view(exported: Params, plan_or_qcfg,
                dtype=jnp.bfloat16) -> Params:
    """Exported artifact → forward()-compatible tree (weights dequantized in
    the serving graph; use with qcfg=None in forward).  Per-tensor packing /
    bits come from the plan embedded in the artifact when the caller passes a
    bare qcfg."""
    plan = _as_plan(plan_or_qcfg, artifact=exported)

    def walk(tree, prefix: tuple = ()):
        if isinstance(tree, dict):
            if "q" in tree and "s" in tree:          # embedding
                return {"w": tree["q"].astype(jnp.float32) * tree["s"]}
            if "q" in tree and "s_wr" in tree:
                return _deploy_node(prefix, tree, plan, dtype)
            return {k: walk(v, prefix + (k,)) for k, v in tree.items()
                    if k != PLAN_KEY}
        if isinstance(tree, (list, tuple)):
            return type(tree)(walk(v, prefix + (str(i),))
                              for i, v in enumerate(tree))
        return tree
    return walk(exported)


def export_for_layers(params: Params, plan_or_qcfg) -> Params:
    """export_model with layer-stacked subtrees handled under vmap."""
    plan = _as_plan(plan_or_qcfg, params=params)
    out = {}
    for k, v in params.items():
        if k in ("layers", "enc_layers", "dec_layers", "tail"):
            out[k] = jax.vmap(lambda lp: _walk(lp, plan, (k,)))(v)
        elif k in STREAM_KEYS:
            continue
        elif _is_qlinear(v):
            out[k] = _export_node((k,), v, params, plan)
        else:
            out[k] = _walk(v, plan, (k,))
    if plan.quant_plan is not None:
        out[PLAN_KEY] = plan_to_array(plan.quant_plan)
    return out


def abstract_deploy_surfaces(cfg, qcfg: QuantConfig,
                             use_pallas: bool = False,
                             interpret: bool | None = None,
                             dtype=jnp.bfloat16):
    """eval_shape the whole init → export → deploy_view chain (no
    allocation; works at 100B scale) for the static analyzer.

    Returns ``(plan, exported_avals, deployed_avals)`` where ``plan`` is the
    DeployPlan with a QuantPlan resolved over the abstract init tree — the
    same resolution path the Engine constructor takes with real params.
    """
    from ..models import init_model
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: init_model(k, cfg, qcfg), key)
    plan = make_deploy_plan(qcfg, arch=getattr(cfg, "name", ""),
                            family=cfg.family, use_pallas=use_pallas,
                            interpret=interpret, params=params,
                            model_cfg=cfg)

    def build(k):
        p = init_model(k, cfg, qcfg)
        ex = export_for_layers(p, plan)
        return ex, deploy_view(ex, plan, dtype)

    exported, deployed = jax.eval_shape(build, key)
    return plan, exported, deployed


def find_exported_linears(tree, prefix: tuple = ()) -> list[tuple]:
    """Paths of every exported *linear* ({q, s_wr} with a matmul-shaped q —
    convs are 4-D and excluded) in an artifact tree."""
    out: list[tuple] = []
    if isinstance(tree, dict):
        if "q" in tree and "s_wr" in tree:
            # matmul-shaped: s_wr covers all but the [in, out] axes of q.
            # conv kernels ([kh, kw, cin, cout] with per-cout s_wr) fail this.
            if tree["s_wr"].ndim >= tree["q"].ndim - 2:
                out.append(prefix)
            return out
        for k, v in tree.items():
            if k == PLAN_KEY:
                continue
            out.extend(find_exported_linears(v, prefix + (k,)))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.extend(find_exported_linears(v, prefix + (i,)))
    return out


def kernel_route_check(exported: Params, plan: DeployPlan) -> dict | None:
    """Drive ONE exported linear through kernels.ops.qlinear_deployed under
    the plan and compare against the dequantized reference matmul.

    Returns {path, pallas, max_err} — ``pallas`` says whether the traced
    program of that call contains the Pallas quant_matmul kernel (int8 /
    unpacked exports and odd shapes take the XLA reference branch
    regardless of the plan), so the metric can't silently report kernel
    parity that never exercised the kernel.  The reference matmul runs at
    full f32 precision (a TPU's default f32 dot rounds to bf16).  None if
    the artifact has no matmul-shaped linear (e.g. conv-only models with no
    packed fc).
    """
    from ..analysis.jaxpr_checks import has_pallas_call
    from ..kernels.ops import pallas_tiles_ok, qlinear_deployed
    paths = find_exported_linears(exported)
    if not paths:
        return None
    M = 4                                     # probe batch rows

    def leaf(path):
        ex = exported
        for k in path:
            ex = ex[k]
        return ex

    def unstack(ex):
        while ex["q"].ndim > 2:
            ex = jax.tree.map(lambda l: l[0], ex)
        return ex

    def reaches_kernel(ex):
        # packed + evenly-tiling shapes — what routes to the kernel
        if ex["q"].dtype != jnp.uint8:
            return False
        n_groups = ex["s_wr"].shape[0] if ex["s_wr"].ndim == 2 else None
        return pallas_tiles_ok(M, ex["q"].shape[-1], ex["q"].shape[-2] * 2,
                               n_groups=n_groups)

    # prefer a linear that genuinely reaches the Pallas kernel
    chosen = None
    for path in paths:
        ex = unstack(leaf(path))
        if reaches_kernel(ex):
            chosen = (path, ex)
            break
        if chosen is None:
            chosen = (path, ex)
    path, ex = chosen
    dotted = ".".join(str(p) for p in path)
    spec = plan.spec_for(dotted)
    w = dof.dequantize_export(ex, jnp.float32,
                              packed=ex["q"].dtype == jnp.uint8)
    x = jax.random.normal(jax.random.PRNGKey(0), (M, w.shape[0]), jnp.float32)

    def route(x, ex):
        return qlinear_deployed(x, ex, plan=plan)

    y = route(x, ex)
    with jax.default_matmul_precision("highest"):
        y_ref = x @ w
    if "b" in ex:
        y_ref = y_ref + ex["b"]
    return {"path": dotted,
            "layout": (spec.layout if spec is not None
                       else str(plan.layout if plan.layout is not None
                                else plan.qcfg.layout)),
            "pallas": has_pallas_call(jax.make_jaxpr(route)(x, ex)),
            "max_err": float(jnp.max(jnp.abs(y - y_ref)))}


def _effective_node(path: tuple, node: Params, parent: Params,
                    plan: DeployPlan, dtype) -> Params:
    out: Params = {"w": dof.effective_weight(
        node, plan.qcfg, _stream_log_sa(path[-1], parent),
        compute_dtype=dtype, bits=plan.bits_for(".".join(path)))}
    if "b" in node:
        out["b"] = node["b"]
    return out


def effective_view(params: Params, plan_or_qcfg,
                   dtype=jnp.float32) -> Params:
    """Fake-quant (training-time) weights in deploy_view's tree structure.

    The oracle for export fidelity: deploy_view(export_for_layers(p)) must
    match effective_view(p) leaf-for-leaf up to float tolerance.
    """
    plan = _as_plan(plan_or_qcfg, params=params)
    qcfg = plan.qcfg

    def walk(tree, prefix: tuple = ()):
        if isinstance(tree, dict):
            if "w" in tree and "log_s" in tree:      # quantized embedding
                s = jnp.exp(tree["log_s"])
                return {"w": fake_quant(tree["w"], s, qcfg.embed_bits,
                                        signed=True).astype(jnp.float32)}
            out = {}
            for k, v in tree.items():
                if k in STREAM_KEYS:
                    continue
                if _is_qlinear(v):
                    out[k] = _effective_node(prefix + (k,), v, tree, plan,
                                             dtype)
                else:
                    out[k] = walk(v, prefix + (k,))
            return out
        if isinstance(tree, (list, tuple)):
            return type(tree)(walk(v, prefix + (str(i),))
                              for i, v in enumerate(tree))
        return tree

    out = {}
    for k, v in params.items():
        if k in ("layers", "enc_layers", "dec_layers", "tail"):
            out[k] = jax.vmap(lambda lp: walk(lp, (k,)))(v)
        elif k in STREAM_KEYS:
            continue
        elif _is_qlinear(v):
            out[k] = _effective_node((k,), v, params, plan, dtype)
        else:
            out[k] = walk(v, (k,))
    return out
