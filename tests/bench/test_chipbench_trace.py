"""The benchmark's trace reduction (bench/trace.py) on a small recorded
trace: two devices, a window span, overlapping ops and host spans.  CPU
only."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace as T  # noqa: E402


@pytest.fixture(scope="module")
def tr():
    return T.Trace.from_json(
        (pathlib.Path(__file__).parent / "trace_fixture.json").read_text())


def test_window_comes_from_the_window_span(tr):
    assert tr.window == (1000, 11000)
    assert tr.window_s == pytest.approx(1e-5)


def test_busy_union_and_idle_share(tr):
    # [1000, 4000) + [5000, 7000) + [9000, 11000): overlaps merged, the op
    # before the window and the tail past its end clipped
    assert T.busy_s(tr, 0) == pytest.approx(7000e-9)
    assert T.idle_share(tr, 0) == pytest.approx(30.0)
    assert T.idle_share(tr, 1) == pytest.approx(0.0)
    assert T.idle_share(tr) == pytest.approx(30.0)      # the idlest device
    assert T.mean_busy_s(tr) == pytest.approx(8500e-9)


def test_kernel_time_is_summed_inside_the_window(tr):
    t, n = T.op_time_s(tr, 0, T.KERNEL["decode_attention"])
    assert (n, t) == (2, pytest.approx(3500e-9))


def test_module_time(tr):
    t, n = T.module_time_s(tr, 0, T.MODULE["train_step"])
    assert (n, t) == (2, pytest.approx(5000e-9))
    t, n = T.module_time_s(tr, 0, T.MODULE["decode"])
    assert (n, t) == (1, pytest.approx(2000e-9))


def test_idle_gaps_are_labelled_by_the_open_harness_span(tr):
    assert T.idle_gaps(tr, 0) == [["qft.loss_read", pytest.approx(2000e-9)],
                                  ["engine.step", pytest.approx(1000e-9)]]
    assert T.idle_gaps(tr, 1) == []


def test_top_ops(tr):
    top = T.top_ops(tr, 0, n=10)
    assert dict(top) == pytest.approx({
        "fusion.1": 2000e-9, "decode_attention.2": 1500e-9, "all-reduce.3": 2000e-9,
        "fusion.4": 500e-9, "decode_attention.5": 2000e-9})
    assert [v for _, v in top] == sorted((v for _, v in top), reverse=True)
    assert T.top_ops(tr, 1) == [["fusion.9", pytest.approx(1e-5)]]


def test_idle_before_the_first_op_is_labelled_window_start():
    tr = T.Trace({0: {"ops": [["fusion.1", 400, 100]], "modules": []}},
                 [["bench:window", 0, 1000], ["bench:qft.step", 700, 100]])
    assert T.idle_gaps(tr, 0) == [
        ["qft.step", pytest.approx(500e-9)],
        ["window start, before the first device op", pytest.approx(400e-9)]]


METRICS = sorted(m["name"] for m in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["per_layer"])


@pytest.mark.parametrize("name", METRICS)
def test_every_reader_reads_a_number_or_nothing(tr, name):
    """Each per-layer metric of BENCHMARK.json has a reader; on the
    recorded trace, and on a trace with no device in it, it returns a
    finite number or None, never raises."""
    import math
    from bench import flops, harness
    dims = {"L": 2, "d": 64, "H": 4, "Hkv": 2, "hd": 16, "ff": 128,
            "V": 512}
    counts = {"steps": 2, "window_s": 1e-5, "dims": dims, "batch": 2,
              "seq_len": 32, "ce_proportion": 0.0, "model_ops": 10 ** 6,
              "decode_lengths": [3, 5]}
    read = harness.load_metric_reader(ROOT, name)
    empty = T.Trace({}, [["bench:window", 0, 1000]])
    for trace in (tr, empty):
        ctx = {"trace": trace, "spans": {"engine.step": [0.01, 0.02]},
               "counts": counts, "chips": 1, "flops": flops,
               "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
        value = read(ctx)
        assert value is None or math.isfinite(value)
