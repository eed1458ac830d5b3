"""The comparison that decides ``correct`` fails what it must: with the
harness's chip check skipped, a whole run at SMOKE size on the CPU with the
timed path broken underneath reads ``correct`` false, once for each fault
the cell can have; and the control (the fp8 reference put in the program's
place) exceeds the limits.  CPU only."""
import json

import pytest

import chipbench_cells as cells

from bench import calibrate, run  # noqa: E402


@pytest.mark.parametrize("workload,fault", [
    ("smoke.qft", "unchanged"),      # a step that returns its state as is
    ("smoke.qft", "half_batch"),     # half the batch left out
    ("smoke.chat", "altered"),       # every emitted token shifted by one
])
def test_a_broken_timed_path_reads_not_correct(tmp_path, capsys, workload,
                                               fault):
    root = cells.write_root(tmp_path)
    with cells.jax_config_kept():
        rc = run.main(["--workload", workload, "--seed", "3000000029",
                       "--seconds", "1", "--trace", "0"],
                      root=root, require_chip=False, variant=fault)
    assert rc == 0
    line = cells.last_json_line(capsys.readouterr().out)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


@pytest.mark.parametrize("workload", ["smoke.qft", "smoke.chat"])
def test_the_control_in_the_programs_place_reads_not_correct(tmp_path, capsys,
                                                             workload):
    root = cells.write_root(tmp_path)
    with cells.jax_config_kept():
        rc = run.main(["--workload", workload, "--seed", "3000000037",
                       "--seconds", "1", "--trace", "0"],
                      root=root, require_chip=False, variant="control")
    assert rc == 0
    line = cells.last_json_line(capsys.readouterr().out)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


@pytest.mark.parametrize("workload", ["smoke.qft", "smoke.chat"])
def test_the_control_exceeds_the_limits(tmp_path, capsys, workload):
    root = cells.write_root(tmp_path)
    with cells.jax_config_kept():
        calibrate.main(["--workload", workload, "--seed", "3000000041",
                        "--seeds", "1", "--controls", "1", "--faults", "0",
                        "--seconds", "1"], root=root, require_chip=False)
    lines = [json.loads(s) for s in capsys.readouterr().out.splitlines()]
    program = next(x for x in lines if x["variant"] == "program")
    control = next(x for x in lines if x["variant"] == "control")
    limits = (cells.QFT if workload == "smoke.qft" else cells.CHAT)["limits"]
    assert all(program[k] <= v for k, v in limits.items())
    assert any(control[k] > v for k, v in limits.items())
