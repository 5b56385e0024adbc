"""Benchmark smoke tests (slow): the participation sweep and the new
sync-vs-pipelined throughput benchmark run end-to-end on tiny configs and
emit well-formed JSON.

These guard the benchmark ENTRY POINTS (arg parsing, JSON schema, claim
wiring) — the numeric claims themselves are exercised at full scale by the
benchmarks and pinned structurally here (types/ranges, not values, since
CI wall-clock is noisy).
"""
import json
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from benchmarks import (async_rounds, fig5_participation, serving_load,
                        throughput, time_to_accuracy)


@pytest.mark.slow
def test_fig5_participation_quick_end_to_end(tmp_path):
    path = tmp_path / "fig5.json"
    rows = fig5_participation.run(quick=True, json_path=str(path))
    assert rows and all(len(r) == 3 for r in rows)
    claims = [r for r in rows if "claim" in r[0]]
    assert claims and all(r[2] == "PASS" for r in claims)

    d = json.loads(path.read_text())
    assert d["benchmark"] == "fig5_participation"
    assert d["quick"] is True
    # 7 algorithms x 2 rates x 2 fracs in quick mode
    assert len(d["cells"]) == 28
    for cell in d["cells"]:
        assert set(cell) == {"algorithm", "participation_rate",
                             "straggler_frac", "acc_mtl", "total_bytes",
                             "mean_participants"}
        assert 0.0 <= cell["acc_mtl"] <= 1.0
        assert cell["total_bytes"] > 0
        assert cell["mean_participants"] > 0
    assert d["claims"]["bytes_scale_with_participation"] is True
    assert d["claims"]["mtsl_trains_under_straggle"] is True


@pytest.mark.slow
def test_throughput_benchmark_quick_end_to_end(tmp_path):
    path = tmp_path / "throughput.json"
    out = throughput.run(quick=True, json_path=str(path))
    d = json.loads(path.read_text())
    assert d == json.loads(json.dumps(out))  # what we returned is what we wrote
    assert d["benchmark"] == "throughput"
    assert len(d["results"]) == 3
    for r in d["results"]:
        assert r["algorithm"] in ("mtsl", "fedavg")
        # steady-state per-round times must be positive and sane
        assert 0 < r["sync_ms_per_round"] < 10_000
        assert 0 < r["pipelined_ms_per_round"] < 10_000
        assert np.isfinite(r["speedup"]) and r["speedup"] > 0
    # at least one straggler-heavy cell exists and the claim reflects it
    straggle = [r for r in d["results"] if r["straggler_frac"] > 0]
    assert straggle
    assert d["claims"]["prefetch_wins"] == any(
        r["speedup"] > 1.02 for r in straggle)
    # the cached-vs-synthesized data-path cell (data/shards.py) at M>=256
    dp = d["data_path"]
    assert dp["num_clients"] >= 256
    assert 0 < dp["synthesized_ms_per_round"] < 10_000
    assert 0 < dp["cached_ms_per_round"] < 10_000
    assert np.isfinite(dp["speedup"]) and dp["speedup"] > 0
    assert d["claims"]["cached_data_wins"] == (dp["speedup"] > 1.02)


@pytest.mark.slow
def test_time_to_accuracy_quick_end_to_end(tmp_path):
    """The acceptance-criterion artifact: simulated wall-clock-to-target for
    mtsl vs fedavg vs parallelsfl under an asymmetric-link cell."""
    path = tmp_path / "tta.json"
    rows = time_to_accuracy.run(quick=True, json_path=str(path))
    assert rows and all(len(r) == 3 for r in rows)
    d = json.loads(path.read_text())
    assert d["benchmark"] == "time_to_accuracy"
    cells = d["cells"]
    # quick mode: 2 cells (slow_uplink, stragglers) x 3 algorithms
    assert {c["cell"] for c in cells} == {"slow_uplink", "stragglers"}
    assert {c["algorithm"] for c in cells} == {"mtsl", "fedavg",
                                               "parallelsfl"}
    for c in cells:
        assert c["total_sim_s"] > 0
        assert 0.0 <= c["acc_mtl"] <= 1.0
        # sim-to-target is either unreached (None) or within the run's total
        if c["sim_s_to_target"] is not None:
            assert 0 < c["sim_s_to_target"] <= c["total_sim_s"] + 1e-9
    assert d["claims"]["sim_clock_emitted"] is True


@pytest.mark.slow
def test_async_rounds_quick_end_to_end(tmp_path):
    """The PR's acceptance-criterion artifact: under a heavy-tail
    capability profile the event engine reaches the target accuracy in
    less SIMULATED wall-clock than the synchronous barrier."""
    path = tmp_path / "async.json"
    rows = async_rounds.run(quick=True, json_path=str(path))
    assert rows and all(len(r) == 3 for r in rows)
    d = json.loads(path.read_text())
    assert d["benchmark"] == "async_rounds"
    assert set(d["arms"]) == {"sync", "async"}
    for arm in d["arms"].values():
        assert arm["total_sim_s"] > 0
        assert arm["applies"] > 0
        if arm["sim_s_to_target"] is not None:
            assert 0 < arm["sim_s_to_target"] <= arm["total_sim_s"] + 1e-9
    # the sim clock is deterministic, so the headline claim is exact
    assert d["claims"]["async_beats_sync_heavy_tail"] is True
    s = d["arms"]["sync"]["sim_s_to_target"]
    a = d["arms"]["async"]["sim_s_to_target"]
    assert a is not None and (s is None or a < s)


@pytest.mark.slow
def test_serving_load_quick_end_to_end(tmp_path):
    """PR acceptance artifact: under a saturating heavy-tailed open-loop
    stream over the star Topology, continuous batching must sustain higher
    tokens/s AND lower p99 TTFT than the sequential FCFS-batch engine, and
    the real continuous engine must be greedy-parity with the real
    sequential one."""
    path = tmp_path / "serving.json"
    rows = serving_load.run(quick=True, json_path=str(path))
    assert rows and all(len(r) == 3 for r in rows)
    claims = [r for r in rows if "claim" in r[0]]
    assert len(claims) == 3 and all(r[2] == "PASS" for r in claims)

    d = json.loads(path.read_text())
    assert d["benchmark"] == "serving_load"
    assert set(d["arms"]) == {"sequential", "continuous"}
    for arm in d["arms"].values():
        assert arm["tokens_per_s"] > 0
        assert 0 < arm["busy_s"] <= arm["makespan_s"] + 1e-9
        assert arm["ttft_p50_s"] <= arm["ttft_p99_s"]
        assert arm["uplink_bytes"] > 0 and arm["downlink_bytes"] > 0
    seq, cont = d["arms"]["sequential"], d["arms"]["continuous"]
    # both arms replayed the identical seeded workload + link bills
    assert seq["total_tokens"] == cont["total_tokens"]
    assert seq["uplink_bytes"] == cont["uplink_bytes"]
    # the sim is deterministic, so the headline claims are exact
    assert d["claims"]["continuous_higher_tokens_per_s"] is True
    assert cont["tokens_per_s"] > seq["tokens_per_s"]
    assert d["claims"]["continuous_lower_p99_ttft"] is True
    assert cont["ttft_p99_s"] < seq["ttft_p99_s"]
    assert d["claims"]["greedy_parity_smoke"] is True


@pytest.mark.parametrize("outcome,rc", [
    ("pass", 0), ("fail_claim", 1), ("raise", 1)])
def test_benchmark_runner_exit_code(monkeypatch, capsys, outcome, rc):
    """benchmarks/run.py exits nonzero when a suite raises or a claim
    FAILs, so a broken suite cannot pass as a clean run."""
    from benchmarks import run as runner
    from benchmarks import table2_accuracy

    def suite(quick, json_path):
        if outcome == "raise":
            raise RuntimeError("suite broke")
        return [("table2/claim", 0, "PASS" if outcome == "pass" else "FAIL")]

    monkeypatch.setattr(table2_accuracy, "run", suite)
    assert runner.main(["--only", "table2"]) == rc
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last == f"claims_failed,{rc},{'OK' if rc == 0 else 'CHECK'}"
