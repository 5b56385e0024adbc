"""The benchmark's own files: BENCHMARK.json against the files it names,
the work each seed gets, and the result line.

    python -m pytest -q bench/tests/test_harness.py
"""
import json
import pathlib
import re
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402
import train_cell  # noqa: E402

SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_name_finds_its_files():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"])
        assert (BENCH / "metrics" / f"{m['name']}.py").exists(), m["name"]
    for c in SPEC["configs"]:
        assert (BENCH.parent / c["file"]).exists()
    for w in SPEC["workloads"]:
        cell = harness.Cell(w["name"], SPEC)
        assert cell.limits
        assert [m["name"] for m in cell.end_to_end][0] == "setup_s"
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        for f in ("ref_cfg", "program_want", "make_params",
                  "train_round_flops", "loss_and_grads"):
            assert callable(getattr(cell.family, f)), (w["config"], f)


def test_bounds_and_lengths():
    assert 1 <= SPEC["run_seconds"] <= 51
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_a_name_outside_benchmark_json_is_refused():
    with pytest.raises(SystemExit):
        harness.Cell("mamba2-130m.train.not-listed", SPEC)


@pytest.mark.parametrize("seed", [1, 2 ** 33 + 1])
@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_every_seed_gets_the_same_work(name, seed):
    cell = harness.Cell(name, SPEC)
    a = next(iter(train_cell.source(cell, 7)))
    b = next(iter(train_cell.source(cell, seed)))
    assert ({k: (v.shape, v.dtype) for k, v in a.items()}
            == {k: (v.shape, v.dtype) for k, v in b.items()})
    assert not all(np.array_equal(a[k], b[k]) for k in a)


def test_result_line_is_json_with_checks_last(capsys):
    harness.emit(False, 3, 1, {"setup_s": {"value": 1.5, "unit": "s"}},
                 {"platform": "tpu"}, [["loss", float("inf"), 0.01]])
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert line["checks"]["loss"] == {"value": 1e30, "limit": 0.01}
    assert out.err.strip().splitlines()[-1] == "correct: False"
