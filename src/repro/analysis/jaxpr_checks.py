"""Layer 1: trace-time invariant analyzer.

Every check here traces a *real* step constructor (the same
``make_slot_decode_step`` / ``make_prefill_step`` / ``make_train_step`` the
engine and trainer jit) with ``jax.make_jaxpr`` over ShapeDtypeStruct
inputs, then walks the jaxpr.  Nothing is allocated and nothing runs, so
the whole registry — 100B configs included — is provable in seconds on CPU.

The invariants, and why they are structural rather than sampled:

one-transfer     The decode step's jaxpr has exactly ONE host-transfer
                 surface: the output fetch.  Any callback primitive
                 (``pure_callback`` / ``io_callback`` / ``debug_callback``)
                 buried anywhere in the graph is an extra sync the runtime
                 test could only catch if the sampled config happened to hit
                 it.  Counting surfaces in the jaxpr proves it for every
                 config.
int8dot          On the serve path the integer weight operand enters
                 ``dot_general`` directly — no ``convert_element_type``
                 int→float on a weight-shaped (ndim ≥ 2) tensor feeding a
                 dot.  Checked per distinct plan-spec signature through
                 ``kernels.ops.qlinear_deployed`` (XLA int8 branch and the
                 Pallas int4 kernel's inner jaxpr).  The acknowledged
                 odd-shape ``ref.quant_matmul_ref`` fallback is reported as
                 a skip, never silently passed.
prefill-recompile  Attention families bucket prompt chunks to a fixed
                 pad-and-mask menu (serve/kv_cache.prefill_buckets), so the
                 compiled-program surface is ``len(menu)`` — the budget is
                 derived from the exact menu the engine uses and anything
                 above it is an error.  SSM families keep exact-length
                 chunks (a recurrence consumes every frame it sees) and
                 report the documented ``min(prefill_chunk, max_len)``
                 fallback surface as info.
plan-coverage    Every quantized site in the init tree resolves through the
                 QuantPlan path table — a missing path means
                 ``bits_for`` silently falls back to ``default_bits``
                 (the role-ladder fallback this repo spent PR 3/4 removing).
                 The serve-time KV cache is a covered tensor class: a
                 standard-KV family whose plan lacks the ``kv_cache`` entry
                 fails (an f32-KV fallback would otherwise be silent).
kernel-route     ``decode_route`` (for the engine's paged or monolithic
                 cache) × ``_attn_layer_count`` predict whether the decode
                 jaxpr contains a ``pallas_call``; the traced graph must
                 agree in both routed and unrouted modes.
kv-cache         The traced decode cache agrees with the plan's KV entry:
                 int8 page pools + per-slot scale leaves + int32 page table
                 when the plan says int8 KV.  The scales are plain cache
                 leaves of the SAME decode step the one-transfer check
                 traces, so they provably ride the single transfer.
kv-fused         KV quant/dequant stays fused inside the decode jaxpr: no
                 float tensor at page-pool footprint (a materialized
                 dequantized cache), no ``mul`` applying scales at cache
                 extent (scales must fold into q before the dot and into
                 the context after it).
kv-page-table    The decode jaxpr actually indexes through the page table:
                 at least one int8 page gather and one int8 page scatter,
                 with the int32 ``pt`` leaf riding the cache tree.
train-step       ``make_train_step`` traces under the resolved plan with
                 zero callback surfaces (the distillation loop never syncs).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..configs import registry
from ..core.plan import KV_CACHE_FAMILIES, iter_quantized
from ..models import init_cache
from ..core.qconfig import QuantConfig
from ..kernels.ops import pallas_tiles_ok, qlinear_deployed
from ..models.attention import decode_route
from ..optim.adam import Adam
from ..serve.deploy import abstract_deploy_surfaces, find_exported_linears
from ..serve.engine import ServeConfig, _attn_layer_count, serve_trace_surfaces
from ..serve.kv_cache import BUCKETED_PREFILL_FAMILIES, prefill_buckets
from ..train.steps import abstract_train_state, make_train_step
from .report import Diagnostic

# ---------------------------------------------------------------------------
# jaxpr walking primitives (shared with the injection tests)
# ---------------------------------------------------------------------------

#: primitives that open a host-transfer surface inside a jitted graph
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "host_callback_call",
})

#: element-wise / layout primitives a dequantized weight flows through on
#: its way into a dot — the provenance chain the int8dot walker follows
_PASSTHROUGH = frozenset({
    "mul", "add", "sub", "div", "neg", "convert_element_type",
    "broadcast_in_dim", "reshape", "transpose", "squeeze", "expand_dims",
    "slice", "dynamic_slice", "concatenate", "rev", "copy",
})


def _sub_jaxprs(eqn):
    """Inner jaxprs of one equation (scan/cond/pjit/pallas_call/...)."""
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            inner = getattr(x, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield inner                     # ClosedJaxpr
            elif hasattr(x, "eqns"):
                yield x                         # bare Jaxpr


def _as_jaxpr(closed):
    return getattr(closed, "jaxpr", closed)


def iter_jaxprs(closed):
    """The jaxpr and every nested jaxpr, depth-first."""
    stack = [_as_jaxpr(closed)]
    while stack:
        j = stack.pop()
        yield j
        for eqn in j.eqns:
            stack.extend(_sub_jaxprs(eqn))


def iter_eqns(closed):
    for j in iter_jaxprs(closed):
        yield from j.eqns


def callback_count(closed) -> int:
    return sum(1 for e in iter_eqns(closed)
               if e.primitive.name in CALLBACK_PRIMS)


def transfer_surfaces(closed) -> int:
    """Host-transfer surfaces of one jitted step: the single output fetch
    plus every callback primitive anywhere in the graph."""
    return 1 + callback_count(closed)


def has_pallas_call(closed) -> bool:
    return any(e.primitive.name == "pallas_call" for e in iter_eqns(closed))


def integer_dot_count(closed) -> int:
    """dot_general equations with at least one integer-dtyped operand —
    the non-vacuity witness for the int8dot invariant."""
    n = 0
    for e in iter_eqns(closed):
        if e.primitive.name != "dot_general":
            continue
        if any(jnp.issubdtype(getattr(v.aval, "dtype", jnp.float32),
                              jnp.integer) for v in e.invars):
            n += 1
    return n


def _dequant_chain(var, producers, depth: int = 0) -> str | None:
    """Walk one dot operand's provenance back through element-wise/layout
    ops; report the first int→float convert on an ndim>=2 tensor."""
    if depth > 64 or not hasattr(var, "aval"):
        return None
    eqn = producers.get(id(var))
    if eqn is None:
        return None
    name = eqn.primitive.name
    if name == "convert_element_type":
        src = eqn.invars[0]
        src_dt = getattr(src.aval, "dtype", None)
        dst_dt = getattr(eqn.outvars[0].aval, "dtype", None)
        if (src_dt is not None and dst_dt is not None
                and jnp.issubdtype(src_dt, jnp.integer)
                and jnp.issubdtype(dst_dt, jnp.floating)
                and getattr(src.aval, "ndim", 0) >= 2):
            return (f"convert_element_type {src_dt.name}->{dst_dt.name} on "
                    f"shape {tuple(src.aval.shape)} feeds dot_general")
        return _dequant_chain(src, producers, depth + 1)
    if name in _PASSTHROUGH:
        for v in eqn.invars:
            if getattr(getattr(v, "aval", None), "ndim", 0) >= 2:
                hit = _dequant_chain(v, producers, depth + 1)
                if hit:
                    return hit
    return None           # a real compute producer — not a dequant chain


def dequant_dot_violations(closed) -> list[str]:
    """Every dot_general (any nesting depth, incl. Pallas kernel bodies)
    fed by a materialized int→float weight dequant."""
    out: list[str] = []
    for j in iter_jaxprs(closed):
        producers: dict[int, Any] = {}
        for eqn in j.eqns:
            for v in eqn.outvars:
                producers[id(v)] = eqn
        for eqn in j.eqns:
            if eqn.primitive.name != "dot_general":
                continue
            for v in eqn.invars:
                hit = _dequant_chain(v, producers)
                if hit:
                    out.append(hit)
    return out


# ---------------------------------------------------------------------------
# per-config checks
# ---------------------------------------------------------------------------

#: the analyzer's serving geometry: small enough to trace fast, shaped so
#: decode_tiles_ok holds (max_len % 128 == 0) and the prefill surface stays
#: readable in reports
ANALYZER_SCFG = dict(max_slots=4, max_len=256, prefill_chunk=32)


def _trace(fn: Callable, *avals):
    return jax.make_jaxpr(fn)(*avals)


def check_decode_transfers(arch: str, surfaces: dict,
                           deployed) -> list[Diagnostic]:
    closed = _trace(surfaces["decode_fn"], deployed, surfaces["cache"],
                    surfaces["state"])
    n = transfer_surfaces(closed)
    if n != 1:
        return [Diagnostic(
            check="trace.one-transfer", config=arch, value=n,
            message=f"decode step has {n} host-transfer surfaces "
                    f"({n - 1} callback(s) beyond the output fetch); "
                    "the serve loop budget is exactly one")]
    return [Diagnostic(check="trace.one-transfer", config=arch,
                       severity="info", value=1,
                       message="decode step: one host-transfer surface")]


def _route(cfg, scfg: ServeConfig, kv) -> bool:
    """``decode_route`` for the engine's cache: paged (``kv``, a KVSpec)
    or monolithic (None)."""
    return decode_route(cfg, scfg.max_len, True,
                        page_size=None if kv is None else kv.page_size)


def check_kernel_route(arch: str, cfg, scfg: ServeConfig, deployed,
                       plan) -> list[Diagnostic]:
    diags = []
    for routed in (False, True):
        p = dataclasses.replace(plan, use_pallas=routed)
        s = serve_trace_surfaces(cfg, plan=p, scfg=scfg)
        closed = _trace(s["decode_fn"], deployed, s["cache"], s["state"])
        actual = has_pallas_call(closed)
        expected = routed and _route(cfg, scfg, s["kv"]) \
            and _attn_layer_count(cfg) > 0
        if actual != expected:
            diags.append(Diagnostic(
                check="trace.kernel-route", config=arch,
                value={"use_pallas": routed, "expected": expected,
                       "actual": actual},
                message=f"decode_route predicts pallas_call={expected} "
                        f"(use_pallas={routed}) but the traced decode jaxpr "
                        f"has pallas_call={actual}"))
    if not diags:
        diags.append(Diagnostic(
            check="trace.kernel-route", config=arch, severity="info",
            value=_route(cfg, scfg, s["kv"]),
            message="decode_route prediction matches traced graph "
                    "(routed and unrouted)"))
    return diags


def check_prefill_recompile(arch: str, cfg, surfaces: dict,
                            budget: int | None = None) -> list[Diagnostic]:
    scfg = surfaces["scfg"]
    bucketed = cfg.family in BUCKETED_PREFILL_FAMILIES
    if bucketed:
        menu = prefill_buckets(scfg.prefill_chunk)
        count = len(menu)
        trace_lens = sorted({menu[0], menu[-1]})
    else:
        # SSM fallback: a recurrence consumes pad frames, so chunks stay
        # exact-length — one program per distinct remainder (documented)
        count = min(scfg.prefill_chunk, scfg.max_len)
        trace_lens = sorted({scfg.prefill_chunk, 1})
    diags = []
    # prove the scheme actually compiles at the menu extremes (bucketed)
    # or the steady-state chunk + a remainder length (exact-length)
    for L in trace_lens:
        batch = {"tokens": jax.ShapeDtypeStruct((1, L), jnp.int32)}
        cache = jax.eval_shape(lambda: init_cache(cfg, 1, scfg.max_len))
        if bucketed:
            closed = _trace(surfaces["prefill_bucketed_fn"],
                            surfaces["deployed"], cache, batch,
                            jax.ShapeDtypeStruct((), jnp.int32))
        else:
            closed = _trace(surfaces["prefill_fn"], surfaces["deployed"],
                            cache, batch)
        cb = callback_count(closed)
        if cb:
            diags.append(Diagnostic(
                check="trace.prefill-recompile", config=arch, value=cb,
                message=f"prefill step (chunk len {L}) has {cb} callback "
                        "surface(s) — prefill must be sync-free"))
    # the bucketed budget is the menu itself — any extra program is a bug;
    # the exact-length fallback keeps the lenient documented cap
    cap = budget if budget is not None else \
        (count if bucketed else scfg.prefill_chunk)
    sev = "error" if count > cap else "info"
    if bucketed:
        msg = (f"prefill pads to a fixed {count}-bucket menu {menu} "
               f"(prefill_chunk={scfg.prefill_chunk}; real_len is traced)")
    else:
        msg = (f"prefill compiles ≤ {count} distinct chunk-length "
               f"programs (exact-length SSM fallback; "
               f"prefill_chunk={scfg.prefill_chunk}, "
               f"max_len={scfg.max_len})")
    diags.append(Diagnostic(
        check="trace.prefill-recompile", config=arch, severity=sev,
        value=count,
        message=msg + (f" — exceeds budget {cap}" if sev == "error" else "")))
    return diags


def check_plan_coverage(arch: str, cfg, qcfg, plan) -> list[Diagnostic]:
    qplan = plan.quant_plan
    if qplan is None:
        return [Diagnostic(check="trace.plan-coverage", config=arch,
                           message="DeployPlan carries no resolved "
                                   "QuantPlan — legacy shim path")]
    from ..models import init_model
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: init_model(k, cfg, qcfg), key)
    tree_paths = {".".join(p) for p, _kind, _n in iter_quantized(params)}
    plan_paths = set(qplan.paths)
    diags = []
    # the KV cache is a serve-time tensor class, not an init-tree site —
    # expected exactly for the standard-KV families (never "stale")
    expects_kv = bool(getattr(qcfg, "kv_bits", 0)) \
        and cfg.family in KV_CACHE_FAMILIES
    has_kv = "kv_cache" in plan_paths
    plan_paths.discard("kv_cache")
    if expects_kv and not has_kv:
        diags.append(Diagnostic(
            check="trace.plan-coverage", config=arch, value="kv_cache",
            message="standard-KV family with kv_bits set, but the resolved "
                    "plan has no `kv_cache` entry — the serve cache would "
                    "silently stay in the activation dtype"))
    elif has_kv and not expects_kv:
        diags.append(Diagnostic(
            check="trace.plan-coverage", config=arch, severity="warning",
            value="kv_cache",
            message=f"plan entry `kv_cache` but family {cfg.family} has no "
                    "standard slot-KV cache to quantize"))
    for missing in sorted(tree_paths - plan_paths):
        diags.append(Diagnostic(
            check="trace.plan-coverage", config=arch, value=missing,
            message=f"quantized site `{missing}` is absent from the "
                    f"resolved plan — bits_for would silently fall back "
                    f"to default_bits={qplan.default_bits}"))
    for stale in sorted(plan_paths - tree_paths):
        diags.append(Diagnostic(
            check="trace.plan-coverage", config=arch, severity="warning",
            value=stale,
            message=f"plan entry `{stale}` matches no site in the init "
                    "tree (stale override?)"))
    if not diags:
        diags.append(Diagnostic(
            check="trace.plan-coverage", config=arch, severity="info",
            value=len(tree_paths),
            message=f"all {len(tree_paths)} quantized sites resolve "
                    "through the plan path table"
                    + (" (+ kv_cache tensor class)" if has_kv else "")))
    return diags


#: the KV-cache rule family — skipped together for non-standard-KV configs
_KV_CHECKS = ("trace.kv-cache", "trace.kv-fused", "trace.kv-page-table")


def check_kv_cache(arch: str, cfg, surfaces: dict, plan) -> list[Diagnostic]:
    """The three KV rules over ONE decode trace (the same step the
    one-transfer check proves, so the scale leaves demonstrably ride the
    single host transfer):

    kv-cache      plan `kv_cache` entry ↔ traced cache layout agree (int8
                  pools + f32 scale leaves + int32 page table iff the plan
                  says 8-bit KV).
    kv-fused      no float tensor at page-pool footprint ``(*, P, Hkv, hd)``
                  (a materialized dequantized pool) and no ``mul`` at cache
                  extent (scales fold into q pre-dot / context post-dot,
                  never into the gathered KV) — witnessed non-vacuously by
                  at least one page read: an int8 page gather, or the
                  paged kernel's ``pallas_call`` on the int8 pool.
    kv-page-table the decode graph actually indexes pages: ≥1 page read
                  and ≥1 int8 scatter (the token write).
    """
    if cfg.family not in KV_CACHE_FAMILIES:
        return [Diagnostic(
            check=c, config=arch, severity="skip",
            message=f"{cfg.family} keeps the monolithic slot cache (no "
                    "standard KV layout to page/quantize)")
            for c in _KV_CHECKS]
    kv, cache = surfaces["kv"], surfaces["cache"]
    qplan = plan.quant_plan
    entry = qplan.get("kv_cache") if qplan is not None else None
    paged = (kv is not None
             and getattr(cache.get("k"), "dtype", None) == jnp.int8
             and {"k_scale", "v_scale", "pt"} <= set(cache))
    wants_int8 = entry is not None and entry.w_bits == 8
    if wants_int8 != paged:
        return [Diagnostic(
            check="trace.kv-cache", config=arch,
            value={"plan_kv_bits": None if entry is None else entry.w_bits,
                   "cache_paged_int8": paged},
            message="plan and traced cache disagree: plan says "
                    f"{'int8' if wants_int8 else 'no'} KV quantization but "
                    f"the decode cache is "
                    f"{'paged int8' if paged else 'monolithic float'} — "
                    "a silent precision fallback")] + [
            Diagnostic(check=c, config=arch, severity="skip",
                       message="skipped: kv-cache plan/trace mismatch")
            for c in _KV_CHECKS[1:]]
    if not paged:
        return [Diagnostic(
            check=c, config=arch, severity="skip",
            message="KV quantization disabled (kv_bits=0 or monolithic "
                    "mode) — plan and cache agree")
            for c in _KV_CHECKS]
    diags = [Diagnostic(
        check="trace.kv-cache", config=arch, severity="info",
        value={"kv_bits": entry.w_bits, "page_size": kv.page_size,
               "n_pages": kv.n_pages},
        message="plan kv_cache entry matches traced cache: int8 page pools"
                " + per-slot scales + int32 page table, all leaves of the "
                "one-transfer decode step")]
    closed = _trace(surfaces["decode_fn"], surfaces["deployed"], cache,
                    surfaces["state"])
    P = kv.page_size
    Hkv, hd = int(cache["k"].shape[-2]), int(cache["k"].shape[-1])
    fused_viol: list[str] = []
    int8_gathers = int8_scatters = 0
    for eqn in iter_eqns(closed):
        name = eqn.primitive.name
        out_aval = getattr(eqn.outvars[0], "aval", None) if eqn.outvars \
            else None
        out_dt = getattr(out_aval, "dtype", None)
        if name == "gather" and out_dt == jnp.int8 \
                and getattr(out_aval, "ndim", 0) >= 4:
            int8_gathers += 1
        elif name == "pallas_call" and any(
                getattr(v.aval, "dtype", None) == jnp.int8
                and v.aval.ndim >= 4 for v in eqn.invars):
            int8_gathers += 1         # the paged kernel reads pages itself
        elif name.startswith("scatter") and out_dt == jnp.int8:
            int8_scatters += 1
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is None or not jnp.issubdtype(dt, jnp.floating):
                continue
            shp = tuple(aval.shape)
            if len(shp) >= 4 and shp[-3:] == (P, Hkv, hd):
                fused_viol.append(
                    f"{name} produces float {shp} at page-pool footprint "
                    "— a materialized dequantized KV pool")
            elif (name == "mul" and len(shp) >= 4
                  and shp[-2:] == (Hkv, hd) and shp[-3] >= P):
                fused_viol.append(
                    f"mul produces float {shp} at cache extent — scales "
                    "must fold into q (pre-dot) and context (post-dot), "
                    "never into the gathered KV")
    if fused_viol:
        diags.extend(Diagnostic(check="trace.kv-fused", config=arch,
                                value=m.split(" ")[0], message=m)
                     for m in fused_viol[:4])
    elif int8_gathers == 0:
        diags.append(Diagnostic(
            check="trace.kv-fused", config=arch, value=0,
            message="no int8 page read in the decode jaxpr — the fused "
                    "quant/dequant check would be vacuous"))
    else:
        diags.append(Diagnostic(
            check="trace.kv-fused", config=arch, severity="info",
            value=int8_gathers,
            message="KV dequant stays fused: int8 feeds the attention "
                    "dots via bare converts, scales hoisted out of the "
                    "cache extent"))
    pt_ok = getattr(cache.get("pt"), "dtype", None) == jnp.int32
    if int8_gathers >= 1 and int8_scatters >= 1 and pt_ok:
        diags.append(Diagnostic(
            check="trace.kv-page-table", config=arch, severity="info",
            value={"gathers": int8_gathers, "scatters": int8_scatters},
            message="decode indexes through the page table: "
                    f"{int8_gathers} int8 page read(s), "
                    f"{int8_scatters} int8 token scatter(s)"))
    else:
        diags.append(Diagnostic(
            check="trace.kv-page-table", config=arch,
            value={"gathers": int8_gathers, "scatters": int8_scatters,
                   "pt_int32": pt_ok},
            message="paged decode must read int8 pages, scatter the new "
                    "token int8, and carry an int32 page table — traced "
                    f"graph has gathers={int8_gathers}, "
                    f"scatters={int8_scatters}, pt_int32={pt_ok}"))
    return diags


def _linear_signatures(exported) -> dict[tuple, tuple]:
    """Distinct (packed, K_stored, N, n_groups) weight signatures across an
    abstract exported artifact (stacked layer axes collapsed)."""
    sigs: dict[tuple, tuple] = {}
    for path in find_exported_linears(exported):
        node = exported
        for k in path:
            node = node[k]
        q, s_wr = node["q"], node["s_wr"]
        packed = q.dtype == jnp.uint8
        k_st, n = int(q.shape[-2]), int(q.shape[-1])
        lead = q.ndim - 2
        rel = s_wr.ndim - lead
        n_groups = int(s_wr.shape[-2]) if rel == 2 else None
        sigs.setdefault((packed, k_st, n, n_groups),
                        tuple(str(p) for p in path))
    return sigs


def check_int8dot(arch: str, exported, plan) -> list[Diagnostic]:
    """Trace qlinear_deployed per distinct plan-spec signature and prove no
    f32 weight materialization feeds a dot (the PR 7 invariant)."""
    diags = []
    checked = 0
    for (packed, k_st, n, n_groups), path in \
            sorted(_linear_signatures(exported).items(), key=str):
        K = k_st * 2 if packed else k_st
        sig = (f"{'.'.join(path)} [{'int4-packed' if packed else 'int8'} "
               f"K={K} N={n}"
               + (f" groups={n_groups}" if n_groups else "") + "]")
        qdt = jnp.uint8 if packed else jnp.int8
        s_wr_aval = (jax.ShapeDtypeStruct((n_groups, n), jnp.float32)
                     if n_groups else
                     jax.ShapeDtypeStruct((n,), jnp.float32))
        ex = {"q": jax.ShapeDtypeStruct((k_st, n), qdt),
              "s_wl": jax.ShapeDtypeStruct((K,), jnp.float32),
              "s_wr": s_wr_aval}
        if packed:
            M = 128
            if not (plan.use_pallas
                    and pallas_tiles_ok(M, n, K, n_groups=n_groups)):
                diags.append(Diagnostic(
                    check="trace.int8dot", config=arch, severity="skip",
                    value=sig,
                    message=f"{sig}: odd-shape/unrouted int4 falls back to "
                            "ref.quant_matmul_ref (documented f32 "
                            "materialization; not on the kernel path)"))
                continue
            x = jax.ShapeDtypeStruct((M, K), jnp.float32)
            closed = _trace(lambda xx, ee: qlinear_deployed(
                xx, ee, use_pallas=True, interpret=None), x, ex)
        else:
            x = jax.ShapeDtypeStruct((8, K), jnp.float32)
            closed = _trace(lambda xx, ee: qlinear_deployed(
                xx, ee, use_pallas=False), x, ex)
        bad = dequant_dot_violations(closed)
        if bad:
            diags.append(Diagnostic(
                check="trace.int8dot", config=arch, value=sig,
                message=f"{sig}: {bad[0]} — integer weights must be the "
                        "dot operand (scales hoisted), never a "
                        "materialized float [K,N]"))
        elif integer_dot_count(closed) == 0:
            diags.append(Diagnostic(
                check="trace.int8dot", config=arch, value=sig,
                message=f"{sig}: no integer-operand dot_general found — "
                        "the invariant check would be vacuous"))
        else:
            checked += 1
    if checked and not any(d.severity == "error" for d in diags):
        diags.append(Diagnostic(
            check="trace.int8dot", config=arch, severity="info",
            value=checked,
            message=f"{checked} weight signature(s): integer operand "
                    "enters dot_general directly, no f32 dequant "
                    "materialization"))
    return diags


def check_train_step(arch: str, cfg, qcfg, plan) -> list[Diagnostic]:
    qplan = plan.quant_plan
    opt = Adam(lr=1e-4)
    student, opt_state = abstract_train_state(cfg, qcfg, opt)
    step = make_train_step(cfg, qcfg, opt, plan=qplan)
    batch = _small_train_batch(cfg)
    closed = _trace(step, student, opt_state, student, batch)
    cb = callback_count(closed)
    if cb:
        return [Diagnostic(
            check="trace.train-step", config=arch, value=cb,
            message=f"train step has {cb} callback surface(s) — the "
                    "distillation loop must never sync mid-step")]
    return [Diagnostic(check="trace.train-step", config=arch,
                       severity="info", value=0,
                       message="train step traces under the resolved plan "
                               "with zero callback surfaces")]


def _small_train_batch(cfg, B: int = 2, S: int = 32) -> dict:
    """A train batch of every model input at trace-friendly size."""
    i32 = jnp.int32
    tok = lambda b, s: jax.ShapeDtypeStruct((b, s), i32)  # noqa: E731
    if cfg.family == "vlm":
        s_img = S // 4
        return {"tokens": tok(B, S - s_img),
                "patch_embeds": jax.ShapeDtypeStruct((B, s_img, cfg.d_model),
                                                     jnp.bfloat16),
                "positions": jax.ShapeDtypeStruct((B, 3, S), i32)}
    if cfg.family == "encdec":
        return {"frames": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                               jnp.bfloat16),
                "tokens": tok(B, max(S // 8, 16))}
    return {"tokens": tok(B, S)}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

#: checks that need a serving path; encdec has none (forward needs frames;
#: the Engine builds token-only batches — see ROADMAP)
_SERVE_CHECKS = ("trace.one-transfer", "trace.kernel-route",
                 "trace.prefill-recompile") + _KV_CHECKS


def analyze_config(arch: str, qcfg: QuantConfig | None = None,
                   use_pallas: bool = True,
                   prefill_budget: int | None = None) -> list[Diagnostic]:
    """Run every Layer-1 check for one registry config (SMOKE geometry —
    the invariants are structural, so config scale is irrelevant)."""
    cfg = registry.get_config(arch, smoke=True)
    qcfg = qcfg if qcfg is not None else QuantConfig()
    diags: list[Diagnostic] = []
    try:
        plan, exported, deployed = abstract_deploy_surfaces(
            cfg, qcfg, use_pallas=use_pallas, interpret=None)
    except Exception as e:  # noqa: BLE001 — a config that cannot even
        # resolve abstractly is one diagnostic, not a crashed run
        return [Diagnostic(check="trace.resolve", config=arch,
                           message=f"abstract init/export/deploy failed: "
                                   f"{type(e).__name__}: {e}")]
    diags.extend(check_plan_coverage(arch, cfg, qcfg, plan))
    diags.extend(check_int8dot(arch, exported, plan))
    diags.extend(check_train_step(arch, cfg, qcfg, plan))

    if cfg.family == "encdec":
        diags.extend(Diagnostic(
            check=c, config=arch, severity="skip",
            message="encdec has no serving path (forward needs frames; "
                    "Engine builds token-only batches) — ROADMAP item")
            for c in _SERVE_CHECKS)
        return diags

    scfg = ServeConfig(**ANALYZER_SCFG)
    surfaces = serve_trace_surfaces(cfg, plan=plan, scfg=scfg)
    surfaces["deployed"] = deployed
    diags.extend(check_decode_transfers(arch, surfaces, deployed))
    diags.extend(check_kernel_route(arch, cfg, scfg, deployed, plan))
    diags.extend(check_prefill_recompile(arch, cfg, surfaces,
                                         budget=prefill_budget))
    diags.extend(check_kv_cache(arch, cfg, surfaces, plan))
    return diags


def analyze(configs: list[str] | None = None,
            qcfg: QuantConfig | None = None,
            prefill_budget: int | None = None) -> list[Diagnostic]:
    diags: list[Diagnostic] = []
    for arch in (configs if configs is not None else registry.ARCH_IDS):
        diags.extend(analyze_config(arch, qcfg=qcfg,
                                    prefill_budget=prefill_budget))
    return diags
