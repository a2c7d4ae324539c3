"""End to end on the CPU at a tiny size: ``chipbench.run`` in a temporary
copy to which tiny cells and a dummy metric were ADDED (no file edited),
through the real plugin daemon's ``Allocate`` on a made-up host tree.  The
chip path's look for a chip is skipped (``--rehearse cpu``); everything else
is a run.  Then the timed path broken underneath: ``correct`` comes out false.
"""

import json

import pytest

import chipbench_helpers as helpers
from chipbench import check_line
from chipbench.cells import load_cell


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return helpers.make_copy(str(tmp_path_factory.mktemp("bench")), train=True)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    assert len(lines) == 1, "nothing but the result line on stdout"
    return lines[0], json.loads(lines[0])


@pytest.mark.parametrize("cell,trace", [
    ("tiny-llm.tiny-chat", 0), ("tiny-llm.tiny-chat", 1),
    ("tiny-llm.tiny-batch", 0), ("tiny-llm.tiny-batch", 1),
    ("tiny-resnet.train", 0), ("tiny-resnet.train", 1),
])
def test_rehearsal_ends_in_a_valid_line(copy, cell, trace):
    text, line = last_line(helpers.run_cell(copy, cell, "--rehearse", "cpu", trace=trace))
    units = load_cell(cell, copy).units(bool(trace))
    assert check_line.problems(text, units, 1, bool(trace), "cpu") == []
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == set(units)
    assert list(line)[-1] == "compared" and line["attempted"] > 0
    if cell == "tiny-llm.tiny-chat" and trace:
        assert line["metrics"]["dummy_count"]["value"] == line["attempted"]
    if not cell.startswith("tiny-resnet"):
        # The toy ResNet's bf16 gap is far over the real cell's limits.
        assert line["correct"] is True, line["compared"]


@pytest.mark.parametrize("cell,fault,number", [
    ("tiny-llm.tiny-batch", "alter_token", "gap_max"),
    ("tiny-resnet.train", "frozen", "change_norm_gap"),
    ("tiny-resnet.train", "half_batch", "grad_norm_gap"),
])
def test_broken_timed_path_is_not_correct(copy, cell, fault, number):
    _, line = last_line(helpers.run_cell(copy, cell, "--rehearse", "cpu", "--fault", fault))
    assert line["correct"] is False
    got = line["compared"][number]
    assert got["value"] > got["limit"]
    if fault == "frozen":
        assert got["value"] == pytest.approx(1.0)  # a leaf that has not moved reads 1


@pytest.mark.parametrize("cell,names", [
    ("tiny-resnet.train", {"control_int8", "fault_half_batch"}),
    ("tiny-llm.tiny-batch", {"control_int8"}),
])
def test_control_is_judged_by_the_cells_limits(copy, cell, names):
    """``--control 1``: the control's numbers beside the cell's own limits,
    the verdict in the line, exit code 4 exactly where one passed (the toy
    decoder's limit of 0.5 is no limit set from readings, so its control may)."""
    proc = helpers.run_cell(copy, cell, "--rehearse", "cpu", "--control", "1")
    assert proc.returncode in (0, 4), proc.stderr[-3000:]
    line = json.loads(proc.stdout.splitlines()[-1])
    assert set(line["control_correct"]) == set(line["controls"]) == names and list(line)[-1] == "compared"
    limits = {k: v["limit"] for k, v in line["compared"].items()}
    for name, numbers in line["controls"].items():
        assert all(c["limit"] == limits[k] for k, c in numbers.items())
        passed = all(c["limit"] is None or c["value"] <= c["limit"] for c in numbers.values())
        assert line["control_correct"][name] is passed
        assert f"{name}: correct {passed}" in proc.stderr
    assert proc.returncode == (4 if any(line["control_correct"].values()) else 0)
    if cell == "tiny-resnet.train":
        assert not any(line["control_correct"].values())


def test_same_seed_same_served_tokens(copy):
    a = last_line(helpers.run_cell(copy, "tiny-llm.tiny-batch", "--rehearse", "cpu", seed=2**31 + 5, seconds=2))[1]
    assert a["correct"] is True and a["failed"] == 0


def test_chip_path_refuses_without_a_tpu(copy):
    proc = helpers.run_cell(copy, "tiny-llm.tiny-chat", seconds=1)
    assert proc.returncode != 0 and proc.stdout == ""


def test_unknown_workload_gives_no_line(copy):
    proc = helpers.run_cell(copy, "no-such.cell", "--rehearse", "cpu", seconds=1)
    assert proc.returncode != 0 and proc.stdout == ""
