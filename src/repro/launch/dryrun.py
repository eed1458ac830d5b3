"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

MUST set XLA_FLAGS before any other import (jax locks device count at init).

Per cell:
  - full-depth compile (scan over layers) → proves shardability, gives
    memory_analysis + exact collective traffic (known_trip_count-corrected);
  - L=1 / L=2 compiles under identical shardings → per-layer FLOPs/bytes by
    differencing (cost_analysis counts while bodies once; DESIGN.md §7);
  - roofline terms vs TPU v5e (197 TF bf16, 819 GB/s HBM, 50 GB/s ICI).

Results are cached as JSON under benchmarks/results/dryrun/.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod]
  python -m repro.launch.dryrun --all --both-meshes
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import functools         # noqa: E402
import json              # noqa: E402
import pathlib           # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from ..configs import SHAPES, get_config, input_specs, skip_reason, ARCH_IDS  # noqa: E402
from ..core import deployment_oriented  # noqa: E402
from ..core.plan import resolve_plan  # noqa: E402
from ..models import init_model, init_cache, set_runtime  # noqa: E402
from ..optim.adam import paper_recipe  # noqa: E402
from ..serve.deploy import (export_for_layers, deploy_view,  # noqa: E402
                            make_deploy_plan)
from ..sharding.partition import (ShardingPolicy, batch_shardings,
                                  cache_shardings, opt_state_shardings,
                                  params_shardings)  # noqa: E402
from ..train.steps import (make_decode_step, make_prefill_step,
                           make_train_step)  # noqa: E402
from . import hlo_analysis as H  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / \
    "benchmarks" / "results" / "dryrun"

# big models: bf16 optimizer state + bf16 master-adjacent memory savings
_BF16_OPT = {"deepseek-v2-236b", "command-r-plus-104b", "qwen3-32b"}


def _cfg_for(arch: str, n_layer_units: int | None = None):
    cfg = get_config(arch).with_padding(tp=16)
    cfg = dataclasses.replace(cfg, scan_layers=True, remat=True)
    if n_layer_units is not None:
        # cost-probe configs are UNROLLED: cost_analysis counts a while body
        # once regardless of trip count, so only unrolled builds difference
        # correctly (total(L) = base + L·layer exactly).
        cfg = dataclasses.replace(cfg, scan_layers=False)
        if cfg.family == "hybrid":
            k = cfg.attn_every
            r = cfg.n_layers % k
            cfg = dataclasses.replace(cfg, n_layers=k * n_layer_units + r)
        elif cfg.family == "encdec":
            cfg = dataclasses.replace(cfg, n_layers=n_layer_units,
                                      enc_layers=n_layer_units)
        else:
            cfg = dataclasses.replace(cfg, n_layers=n_layer_units)
    return cfg


def _layer_units(cfg) -> int:
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.attn_every
    return cfg.n_layers


def _struct(f, *a, **k):
    return jax.eval_shape(functools.partial(f, **k), *a)


def _cast_tree(tree, dtype):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype),
        tree)


def build_cell(arch: str, shape: str, mesh, pol: ShardingPolicy,
               n_layer_units: int | None = None, qcfg=None,
               variant: str = ""):
    """Returns (jitted_fn, arg_structs) ready to .lower(*arg_structs).

    ``variant``: '+'-separated §Perf knobs — ep (shard_map expert parallel),
    mb<k> (k-way microbatching), save_dots (remat policy).
    """
    qcfg = qcfg or deployment_oriented()
    cfg = _cfg_for(arch, n_layer_units)
    opts = set(variant.split("+")) if variant else set()
    if "save_dots" in opts:
        cfg = dataclasses.replace(cfg, remat_policy="save_dots")
    if "absorb" in opts and cfg.mla is not None:
        # beyond-paper: MLA decode with k_up/v_up absorbed — attention runs
        # in the compressed latent space (no per-step K/V expansion)
        cfg = dataclasses.replace(cfg, mla_absorb=True)
    microbatches = 1
    for o in opts:
        if o.startswith("mb"):
            microbatches = int(o[2:])
    sp = SHAPES[shape]
    batch = input_specs(arch, shape, cfg)
    key = jax.random.PRNGKey(0)
    # abstract student skeleton + resolved QuantPlan, shared by every cell
    # kind.  Resolved EAGERLY (outside any trace): plan lookups are then
    # static Python ints in the lowered graphs, and the train cells compile
    # the exact grid the inference cells deploy.
    student = _struct(init_model, key, cfg=cfg, qcfg=qcfg)
    qplan = resolve_plan(qcfg, student, model_cfg=cfg)
    if "ep" in opts and cfg.moe is not None:
        from ..sharding.ep import make_ep_moe
        set_runtime(moe_fn=make_ep_moe(mesh, cfg, qcfg, dp_axes=pol.dp,
                                       tp_axis=pol.tp, plan=qplan))
    else:
        set_runtime(moe_fn=None)

    if sp.kind == "train":
        opt = paper_recipe(
            steps_per_epoch=500,
            state_dtype=jnp.bfloat16 if arch in _BF16_OPT else jnp.float32)
        step = make_train_step(cfg, qcfg, opt, microbatches=microbatches,
                               plan=qplan)
        teacher = _cast_tree(_struct(init_model, key, cfg=cfg, qcfg=None),
                             jnp.bfloat16)
        opt_state = _struct(opt.init, student)
        s_sh = params_shardings(student, cfg, mesh, pol)
        t_sh = params_shardings(teacher, cfg, mesh, pol)
        o_sh = opt_state_shardings(s_sh, mesh)
        b_sh = batch_shardings(batch, mesh, pol)
        rep = NamedSharding(mesh, P())
        fn = jax.jit(step,
                     in_shardings=(s_sh, o_sh, t_sh, b_sh),
                     out_shardings=(s_sh, o_sh, {"loss": rep, "grad_norm": rep}),
                     donate_argnums=(0, 1))
        return fn, (student, opt_state, teacher, batch), cfg

    # inference cells run the DEPLOYED artifact (int4-packed weights) under
    # the same resolved plan the train cells fake-quant against.  The
    # DeployPlan is built eagerly: inside the traced step the embedded plan
    # leaf is abstract and could not be decoded.
    dplan = make_deploy_plan(qcfg, arch=arch, family=cfg.family,
                             quant_plan=qplan)
    exported = _struct(export_for_layers, student, plan_or_qcfg=dplan)
    ex_sh = params_shardings(exported, cfg, mesh, pol)

    if sp.kind == "prefill":
        cache = _struct(init_cache, cfg=cfg, batch=sp.global_batch,
                        max_len=sp.seq_len + 8)

        def step(ex, cache, batch):
            params = deploy_view(ex, dplan)
            return make_prefill_step(cfg, None)(params, cache, batch)
    else:  # decode
        cache = _struct(init_cache, cfg=cfg, batch=sp.global_batch,
                        max_len=sp.seq_len,
                        enc_len=sp.seq_len if cfg.family == "encdec" else None)

        def step(ex, cache, batch):
            params = deploy_view(ex, dplan)
            return make_decode_step(cfg, None)(params, cache, batch)

    c_sh = cache_shardings(cache, cfg, mesh, pol)
    b_sh = batch_shardings(batch, mesh, pol)
    rep = NamedSharding(mesh, P())
    logits_sh = NamedSharding(mesh, P(pol.dp if sp.global_batch > 1 else None,
                                      pol.tp))
    fn = jax.jit(step, in_shardings=(ex_sh, c_sh, b_sh),
                 out_shardings=(logits_sh, c_sh), donate_argnums=(1,))
    return fn, (exported, cache, batch), cfg


def _model_flops(arch: str, shape: str) -> float:
    cfg = get_config(arch)
    sp = SHAPES[shape]
    pc = cfg.param_count()
    if sp.kind == "train":
        # QFT backbone params only: the lm_head is DCE'd (loss on hidden) and
        # embed is a lookup.  6ND student (fwd+bwd) + 2ND frozen teacher fwd.
        n = cfg.n_params_active() - pc["embed"] - pc["head"]
        tokens = sp.global_batch * sp.seq_len
        return 8.0 * n * tokens
    n = cfg.n_params_active() - pc["embed"]   # serving computes logits
    tokens = sp.global_batch * (sp.seq_len if sp.kind == "prefill" else 1)
    return 2.0 * n * tokens


def run_cell(arch: str, shape: str, multi_pod: bool,
             pol: ShardingPolicy | None = None, tag: str = "baseline",
             save: bool = True, variant: str = "") -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    out: dict = {"arch": arch, "shape": shape, "mesh": mesh_name, "tag": tag,
                 "variant": variant}
    reason = skip_reason(arch, shape)
    if reason:
        out["status"] = "SKIP"
        out["reason"] = reason
        if save:
            _save(out)
        return out

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    pol = pol or ShardingPolicy(dp=("pod", "data") if multi_pod else ("data",))
    set_runtime(act_spec=pol.dp)
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            # --- full-depth compile: shardability + memory + exact collectives
            fn, args, cfg = build_cell(arch, shape, mesh, pol, variant=variant)
            lowered = fn.lower(*args)
            compiled = lowered.compile()
            out["compile_s"] = round(time.time() - t0, 1)
            out["memory"] = H.memory_summary(compiled)
            cost_full = H.cost_summary(compiled)
            out["collectives"] = H.collective_stats(compiled.as_text(), n_chips)

            # --- L-differencing for FLOPs/bytes (scan bodies counted once;
            # L=2/3 because XLA fully unrolls trip-count-1 loops, which would
            # bias the diff — observed on the first dry-run)
            units = _layer_units(_cfg_for(arch))
            cost_l = {}
            for n in (1, 2):
                fn_n, args_n, _ = build_cell(arch, shape, mesh, pol,
                                             n_layer_units=n, variant=variant)
                cost_l[n] = H.cost_summary(fn_n.lower(*args_n).compile())
            layer = {k: cost_l[2][k] - cost_l[1][k] for k in ("flops", "bytes")}
            total = {k: cost_l[1][k] + (units - 1) * layer[k]
                     for k in ("flops", "bytes")}
            # microbatched variants wrap fwd/bwd in a lax.scan whose body the
            # cost probes count ONCE — scale to the full batch (collectives
            # are already exact via known_trip_count)
            mb = 1
            for o in (variant.split("+") if variant else []):
                if o.startswith("mb"):
                    mb = int(o[2:])
            if mb > 1:
                total = {k: v * mb for k, v in total.items()}
                out["microbatches"] = mb
            out["cost"] = {"full_scan_raw": cost_full, "per_layer_unit": layer,
                           "corrected_total": total, "layer_units": units}

        mf = _model_flops(arch, shape)
        out["roofline"] = H.roofline_terms(
            total["flops"], total["bytes"],
            out["collectives"]["collective_bytes"], mf, n_chips)
        out["status"] = "OK"
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        out["status"] = "FAIL"
        out["error"] = f"{type(e).__name__}: {e}"
        out["traceback"] = traceback.format_exc()[-2000:]
    out["total_s"] = round(time.time() - t0, 1)
    if save:
        _save(out)
    return out


def _save(out: dict) -> None:
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    name = f"{out['arch']}__{out['shape']}__{out['mesh']}__{out['tag']}.json"
    (RESULTS_DIR / name).write_text(json.dumps(out, indent=1))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--variant", default="")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    cells = ([(a, s) for a in ARCH_IDS for s in SHAPES]
             if args.all else [(args.arch, args.shape)])
    for mp in meshes:
        for arch, shape in cells:
            mesh_name = "pod2x16x16" if mp else "pod16x16"
            fname = RESULTS_DIR / f"{arch}__{shape}__{mesh_name}__{args.tag}.json"
            if args.skip_existing and fname.exists():
                prev = json.loads(fname.read_text())
                if prev.get("status") in ("OK", "SKIP"):
                    print(f"[skip-existing] {arch} {shape} {mesh_name}")
                    continue
            r = run_cell(arch, shape, mp, tag=args.tag, variant=args.variant)
            line = {k: r.get(k) for k in
                    ("arch", "shape", "mesh", "status", "compile_s", "error")}
            if r.get("roofline"):
                line["dominant"] = r["roofline"]["dominant"]
                line["frac"] = round(r["roofline"]["roofline_fraction"], 3)
            print(json.dumps(line))


if __name__ == "__main__":
    main()
