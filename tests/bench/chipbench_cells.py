"""A benchmark root at SMOKE size for the CPU tests: BENCHMARK.json and the
cell files, written as files only into a temporary directory.  The code
that runs them is the benchmark's own (bench/)."""
import contextlib
import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

QUANT = {"mode": "deployment_oriented", "w_bits": 4, "a_bits": 8,
         "embed_bits": 8}
SMOKE_MODEL = {"head_dim": 16, "hidden_size": 64, "intermediate_size": 128,
               "num_attention_heads": 4, "num_hidden_layers": 2,
               "num_key_value_heads": 2, "rope_theta": 1000000,
               "tie_word_embeddings": False, "vocab_size": 512,
               "qk_norm": True}
CONFIG = {"name": "smoke", "model": SMOKE_MODEL, "program": {
    "registry": "qwen3-8b",
    "replace": {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                "d_ff": 128, "vocab": 512, "head_dim": 16}}}
# limits between the CPU readings of the program and of the control
QFT = {"kind": "qft", "batch": 2, "seq_len": 32, "quant": QUANT,
       "ce_proportion": 0.0, "base_lr": 1e-4, "steps_per_epoch": 500,
       "log_every": 50, "calib_batches": 2, "check_steps": 3,
       "pool_batches": 4,
       "limits": {"loss_rel_gap": 0.07, "grad_norm_gap": 0.08,
                  "change_norm_gap": 0.06}}
CHAT = {"kind": "serve", "loop": "open", "rate_per_s": 8.0,
        "prompt": {"median": 24, "sigma": 0.8, "min": 4, "max": 60},
        "output": {"median": 6, "sigma": 0.8, "min": 2, "max": 12},
        "quant": QUANT, "max_slots": 4, "max_len": 128, "prefill_chunk": 32,
        "kv_mode": "paged", "check_requests": 3,
        "limits": {"served_logit_gap_mean": 0.005}}
CPU_PEAKS = {"devices": {"cpu": {"bf16_flops": 1e12, "int8_ops": 2e12,
                                 "hbm_bytes_per_s": 1e11,
                                 "hbm_bytes": 1e10}}}


def metric(name, moves, cell, unit="%"):
    return {"name": name, "unit": unit, "better": "higher",
            "source": "device_trace", "layer": "x", "moves": moves,
            "workloads": [cell]}


def write_root(tmp: pathlib.Path, metric_files=()) -> pathlib.Path:
    """The smoke cells ``smoke.qft`` and ``smoke.chat``; ``metric_files``:
    (metric name, moves, cell, source, file stem) of per-layer readers to
    drop in beside the real ones this root lists."""
    from bench.harness import metric_reader_path
    b = tmp / "bench"
    for d in ("configs", "traffic", "metrics"):
        (b / d).mkdir(parents=True, exist_ok=True)
    (b / "configs" / "smoke.json").write_text(json.dumps(CONFIG))
    (b / "traffic" / "qft-smoke.json").write_text(json.dumps(QFT))
    (b / "traffic" / "chat-smoke.json").write_text(json.dumps(CHAT))
    (b / "peaks.json").write_text(json.dumps(CPU_PEAKS))
    for name in ("mfu.qft", "engine_step_ms.chat"):
        src = metric_reader_path(ROOT, name)
        shutil.copy(src, b / "metrics" / src.name)
    per_layer = [metric("mfu.qft", "qft_tokens_per_s", "smoke.qft"),
                 metric("engine_step_ms.chat", "itl_p95_ms", "smoke.chat",
                        "ms")]
    for name, moves, cell, src, stem in metric_files:
        (b / "metrics" / f"{stem}.py").write_text(src)
        if name not in {m["name"] for m in per_layer}:
            per_layer.append(metric(name, moves, cell))
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "smoke", "source": "test",
                     "file": "bench/configs/smoke.json", "reduced": [],
                     "why": "test"}],
        "workloads": [
            {"name": "smoke.qft", "config": "smoke", "traffic": "qft-smoke",
             "chips": 1, "why": "test"},
            {"name": "smoke.chat", "config": "smoke",
             "traffic": "chat-smoke", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "qft_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.1, "source": "host_clock",
             "workloads": ["smoke.qft"]},
            {"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock",
             "workloads": ["smoke.chat"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": per_layer}
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@contextlib.contextmanager
def jax_config_kept():
    """Undo what a run sets process-wide (the persistent compile cache)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    try:
        yield
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def last_json_line(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])
