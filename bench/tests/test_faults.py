"""A run with the timed path broken underneath must come out not correct.

Drives whole runs of the test-size cells on the CPU past the harness's look
for a chip, once sound and once with each fault planted (bench/faults.py),
and once with the control, the reference in the configuration's next lower
precision, in the program's place. The limits are the test cells' own, set
from readings of these sizes (fixtures/limits).

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_faults.py
"""
import argparse
import contextlib
import json
import pathlib
import sys
import time

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import check  # noqa: E402
import faults  # noqa: E402
import harness  # noqa: E402
import run as bench_run  # noqa: E402

FIX = BENCH / "tests" / "fixtures"
LM, IMAGE = "tiny-mamba2.train-lm", "tiny-resnet.train-image"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _cell(name):
    spec = json.loads((FIX / "benchmark.json").read_text())
    return harness.Cell(name, spec=spec, files=FIX)


def _drive(name, fault=None, seed=2 ** 33 + 5, control=False, capsys=None):
    cell = _cell(name)
    args = argparse.Namespace(workload=name, seed=seed, seconds=0.5, trace=0,
                              control=control)
    with fault if fault is not None else contextlib.nullcontext():
        correct = bench_run.drive(cell, args, CPU, time.perf_counter())
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] == correct
    return line


@pytest.mark.parametrize("name", [LM, IMAGE])
def test_sound_run_is_correct(name, capsys):
    line = _drive(name, capsys=capsys)
    assert line["correct"], line["checks"]
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device", "checks"}
    assert list(line)[-1] == "checks"
    assert "setup_s" in line["metrics"]


@pytest.mark.parametrize("name", [LM, IMAGE])
@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_training_fault_is_caught(name, fault, capsys):
    line = _drive(name, getattr(faults, fault)(), capsys=capsys)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("name", [LM, IMAGE])
def test_control_fails_the_limits(name, capsys):
    cell = _cell(name)
    runner = harness.runner(cell)
    args = argparse.Namespace(workload=name, seed=2 ** 33 + 9, seconds=0.5,
                              trace=0, control=True)
    res = runner.run(cell, args, time.perf_counter(), None)
    ok, _ = check.verdict(res.numbers, cell.limits)
    assert ok
    numbers = dict(res.numbers, **res.control)
    bad, rows = check.verdict(numbers, cell.limits)
    assert not bad, rows


def test_no_tpu_exits_nonzero_without_a_result(capsys):
    with pytest.raises(SystemExit) as e:
        harness.device_info(1)
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""
