"""The sharded QFT kind (bench/kinds/qft_sharded.py) on 4 virtual CPU
devices at SMOKE size, through the benchmark's test-only entry: the cell
reads ``correct`` true, with the vocabulary padded for the mesh (500 rows,
512 in the program); the fp8 control and each planted fault read false.
And the cell's per-layer readers on a recorded 4-device trace.  CPU only.

The bench runs go in one subprocess: the device count is fixed when jax
starts."""
import json
import math
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import pytest

import chipbench_cells as cells

from bench import flops, harness  # noqa: E402
from bench.trace import Trace  # noqa: E402

ROOT = cells.ROOT
CELL = "smoke-tp4.qft"
MODEL = {**cells.SMOKE_MODEL, "num_attention_heads": 8,
         "num_key_value_heads": 4, "vocab_size": 500}
CONFIG = {"name": "smoke-tp4", "model": MODEL,
          "mesh": {"data": 1, "model": 4}, "program": {
              "registry": "qwen3-8b",
              "replace": {"n_layers": 2, "d_model": 64, "n_heads": 8,
                          "n_kv_heads": 4, "d_ff": 128, "vocab": 500,
                          "head_dim": 16}}}
# limits between the CPU readings of the program and of the control
TRAFFIC = {**cells.QFT, "kind": "qft_sharded",
           "limits": {"loss_rel_gap": 0.07, "grad_norm_gap": 0.08,
                      "change_norm_gap": 0.06}}
METRICS = ("mfu.qft-tp4", "qft_step_device_ms.qft-tp4",
           "device_idle_share.qft-tp4", "collective_ms.qft-tp4",
           "collective_share.qft-tp4", "collective_gb_per_step.qft-tp4")
VARIANTS = ("control", "unchanged", "half_batch")


def write_root(tmp: pathlib.Path) -> pathlib.Path:
    """A benchmark root holding the one cell ``smoke-tp4.qft`` on 4 chips,
    with the six per-layer metrics of ``qwen3-8b-tp4.qft`` and their
    readers."""
    b = tmp / "bench"
    for d in ("configs", "traffic", "metrics"):
        (b / d).mkdir(parents=True, exist_ok=True)
    (b / "configs" / "smoke-tp4.json").write_text(json.dumps(CONFIG))
    (b / "traffic" / "qft-smoke-tp4.json").write_text(json.dumps(TRAFFIC))
    (b / "peaks.json").write_text(json.dumps(cells.CPU_PEAKS))
    for name in METRICS:
        src = harness.metric_reader_path(ROOT, name)
        shutil.copy(src, b / "metrics" / src.name)
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "smoke-tp4", "source": "test",
                     "file": "bench/configs/smoke-tp4.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": CELL, "config": "smoke-tp4",
                       "traffic": "qft-smoke-tp4", "chips": 4,
                       "why": "test"}],
        "end_to_end": [
            {"name": "qft_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.1, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [cells.metric(name, "qft_tokens_per_s", CELL)
                      for name in METRICS]}
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture(scope="module")
def lines(tmp_path_factory):
    """The result line of a traced run, then of each variant, by name."""
    root = write_root(tmp_path_factory.mktemp("tp4"))
    code = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {str(ROOT)!r})
        import pathlib
        from bench import run
        root = pathlib.Path({str(root)!r})
        for variant, trace in ((None, 1), ("control", 0), ("unchanged", 0),
                               ("half_batch", 0)):
            run.main(["--workload", {CELL!r}, "--seed", "3000000051",
                      "--seconds", "1", "--trace", str(trace)],
                     root=root, require_chip=False, variant=variant)
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600,
                         cwd=tmp_path_factory.getbasetemp(),
                         env={**os.environ, "PYTHONPATH": str(ROOT / "src"),
                              "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-4000:]
    results = [json.loads(s) for s in out.stdout.splitlines()
               if s.startswith("{")]
    assert len(results) == 1 + len(VARIANTS), out.stderr[-4000:]
    return dict(zip(("program",) + VARIANTS, results)), out.stderr


def test_the_sharded_cell_reads_correct(lines):
    line = lines[0]["program"]
    assert line["correct"] is True and line["failed"] == 0, lines[1][-3000:]
    assert line["attempted"] > 0
    assert line["device"]["count"] == 4
    assert set(line["checks"]) == {"loss_rel_gap", "grad_norm_gap",
                                   "change_norm_gap", "pad_rows_max"}
    assert line["checks"]["pad_rows_max"]["value"] == 0.0
    # no TPU plane in a CPU trace: the device readers find nothing and
    # their metrics are left out; the program's counter and mfu are read
    assert set(line["metrics"]) == {"mfu.qft-tp4",
                                    "collective_gb_per_step.qft-tp4"}
    assert line["metrics"]["collective_gb_per_step.qft-tp4"]["value"] > 0
    assert "vocabulary 500 rows, 12 padding rows" in lines[1]


@pytest.mark.parametrize("variant", VARIANTS)
def test_the_control_and_the_faults_read_not_correct(lines, variant):
    line = lines[0][variant]
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


# ------------------------------------------------------ readers on 4 devices

#: four devices, a 10 us window; two train steps on each, collectives
#: among other ops, one straddling the window's end and one before it;
#: device 3 the idlest
TRACE = {
    "devices": {
        str(d): {"ops": [["fusion.1", 1000, 2000],
                         ["all-reduce.3", 3000, 500],
                         ["all-reduce-start.4", 3500, 100],
                         ["all-reduce-done.4", 4000, 400],
                         ["all-gather.5", 200, 300],           # before it
                         ["fusion.all-reduce.6", 5000, 1000],  # not one
                         ["all-reduce-scatter-fusion.7", 6000, 500],
                         ["reduce-scatter.8", 7000, 1000],
                         ["collective-permute-done.9", 10500, 1000],
                         ["fusion.10", 8000, 1000 - 250 * d]],
                 "modules": [["jit_train_step(1)", 1000, 4000],
                             ["jit_train_step(2)", 5000 + 500 * d, 4000]]}
        for d in range(4)},
    "spans": [["bench:window", 1000, 10000]]}
COUNTS = {"steps": 2, "window_s": 1e-5, "batch": 2, "seq_len": 32,
          "ce_proportion": 0.0,
          "dims": {"L": 2, "d": 64, "H": 8, "Hkv": 4, "hd": 16, "ff": 128,
                   "V": 500},
          "collective_bytes": {"all-reduce": 3 * 10 ** 9,
                               "all-gather": 5 * 10 ** 8}}


def _ctx(trace):
    return {"trace": trace, "spans": {}, "counts": COUNTS, "chips": 4,
            "flops": flops,
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


@pytest.mark.parametrize("name", METRICS)
def test_each_reader_reads_a_number_or_nothing_on_four_devices(name):
    read = harness.load_metric_reader(ROOT, name)
    for trace in (Trace.from_json(json.dumps(TRACE)),
                  Trace({}, [["bench:window", 0, 1000]])):
        value = read(_ctx(trace))
        assert value is None or math.isfinite(value)


def test_collective_time_sums_only_collectives_inside_the_window():
    tr = Trace.from_json(json.dumps(TRACE))
    read = lambda n: harness.load_metric_reader(ROOT, n)(_ctx(tr))
    # all-reduce.3 500 + start 100 + done 400 + reduce-scatter 1000 +
    # the permute's 500 inside the window; two steps
    assert read("collective_ms.qft-tp4") == pytest.approx(2500e-9 * 1e3 / 2)
    # first device's steps: 4000 + 4000 ns
    assert read("collective_share.qft-tp4") == pytest.approx(
        100.0 * 2500 / 8000)
    assert read("qft_step_device_ms.qft-tp4") == pytest.approx(4e-3)
    assert read("collective_gb_per_step.qft-tp4") == pytest.approx(3.5)
    ops = flops.qft_step(COUNTS["dims"], 2, 32, 0.0) * 2
    assert read("mfu.qft-tp4") == pytest.approx(
        100.0 * ops / 1e-5 / (4 * 197e12))
    # the idlest device, device 3: busy 6,000 ns the devices share and
    # 250 ns of its own in the 10,000 ns window
    assert read("device_idle_share.qft-tp4") == pytest.approx(37.5)
