"""A configuration, a traffic and a per-layer metric dropped into a
directory as files only are found by name and run, at SMOKE size on the
CPU, through the benchmark's test-only entry; the chip path refuses the
CPU.  CPU only."""
import pytest

import chipbench_cells as cells

from bench import run  # noqa: E402
from bench.harness import BenchError  # noqa: E402

STEPS_READER = '''
def read(ctx):
    n = ctx["counts"].get("steps")
    return float(n) if n else None
'''
SILENT_READER = '''
def read(ctx):
    return None
'''
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(root, workload, trace, capsys):
    with cells.jax_config_kept():
        rc = run.main(["--workload", workload, "--seed", "3000000017",
                       "--seconds", "1", "--trace", str(trace)],
                      root=root, require_chip=False)
    assert rc == 0
    return cells.last_json_line(capsys.readouterr().out)


def test_files_only_cell_and_metric_are_found_and_run(tmp_path, capsys):
    root = cells.write_root(tmp_path, metric_files=[
        ("steps_seen.qft", "qft_tokens_per_s", "smoke.qft", STEPS_READER,
         "steps_seen.qft"),
        ("nothing_to_read.qft", "qft_tokens_per_s", "smoke.qft",
         SILENT_READER, "nothing_to_read.qft")])
    line = _run(root, "smoke.qft", 1, capsys)
    assert KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["steps_seen.qft"]["value"] == line["attempted"] > 0
    assert "mfu.qft" in line["metrics"]
    assert "nothing_to_read.qft" not in line["metrics"]   # left out, not 0
    assert {"platform", "kind", "count", "memory_peak_bytes", "busy_s",
            "window_s"} <= set(line["device"])
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == {"loss_rel_gap", "grad_norm_gap",
                                   "change_norm_gap"}


def test_a_metric_without_a_file_of_its_own_is_read_by_the_shared_one(
        tmp_path, capsys):
    # ``steps_seen.qft`` has no ``steps_seen.qft.py``: ``steps_seen.py``
    # reads it; ``mfu.qft.py`` is found before ``mfu.py``
    root = cells.write_root(tmp_path, metric_files=[
        ("steps_seen.qft", "qft_tokens_per_s", "smoke.qft", STEPS_READER,
         "steps_seen"),
        ("mfu.qft", "qft_tokens_per_s", "smoke.qft", SILENT_READER, "mfu")])
    line = _run(root, "smoke.qft", 1, capsys)
    assert line["metrics"]["steps_seen.qft"]["value"] == line["attempted"] > 0
    assert line["metrics"]["mfu.qft"]["value"] > 0


def test_serving_cell_end_to_end_metrics(tmp_path, capsys):
    root = cells.write_root(tmp_path)
    line = _run(root, "smoke.chat", 0, capsys)
    assert KEYS <= set(line)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"ttft_p95_ms", "setup_s"}
    assert line["device"]["platform"] == "cpu"


def test_chip_path_refuses_the_cpu(tmp_path, capsys):
    root = cells.write_root(tmp_path)
    with pytest.raises(BenchError):
        run.main(["--workload", "smoke.qft", "--seed", "1", "--seconds", "1",
                  "--trace", "0"], root=root)
    assert capsys.readouterr().out == ""


def test_unknown_device_kind_has_no_peaks(tmp_path):
    root = cells.write_root(tmp_path)
    from bench import harness
    with pytest.raises(BenchError):
        harness.peaks(root, "TPU v99")
