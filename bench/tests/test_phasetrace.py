"""The reduction of a profile by the program's own scopes and spans.

    python -m pytest -q bench/tests/test_phasetrace.py
"""
import pathlib
import shutil
import sys
import types

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests"), str(BENCH.parent / "src")]

import cut_trace  # noqa: E402
import phasetrace  # noqa: E402

SCOPED = BENCH / "tests" / "fixtures" / "v5e_phases.xplane.pb"
UNSCOPED = BENCH / "tests" / "fixtures" / "v5e.xplane.pb"
MS = 1_000_000  # ns


def test_phases_count_self_time_once():
    """A `while` op enclosing its body's ops, as the "XLA Ops" line nests
    them; an async copy that overlaps ops without nesting; program spans
    on the host."""
    root = "jit(round_fn)/jit(main)"
    server = root + "/transpose(jvp(mtsl.server))/while"
    ops = [("fusion.1", 0 * MS, 2 * MS, root + "/jvp(vmap(mtsl.tower))/dot"),
           ("while.2", 2 * MS, 10 * MS, server),
           ("fusion.3", 3 * MS, 5 * MS,
            server + "/body/closed_call/mamba.ssd/dot_general"),
           ("fusion.4", 6 * MS, 8 * MS, server + "/body/closed_call/add"),
           ("copy-start.5", 9 * MS, 11 * MS, ""),
           ("fusion.6", 10 * MS, 12 * MS,
            root + "/jvp(mtsl.loss)/reduce_sum"),
           ("fusion.7", 14 * MS, 15 * MS, root + "/mtsl.update/mul"),
           ("fusion.8", 18 * MS, 19 * MS,
            root + "/transpose(jvp(mtsl.towers))/dot")]
    spans = [("bench.window", 0, 20 * MS, {}),
             ("repro.round", 11 * MS, 20 * MS, {"round": 4, "step_num": 4}),
             ("repro.input_wait", 12 * MS, 14 * MS,
              {"round": 4, "queued": 0}),
             ("repro.dispatch", 15 * MS, 16 * MS, {"round": 4}),
             ("bench.data", 15 * MS, 18 * MS, {}),
             ("repro.draw", 21 * MS, 22 * MS, {"round": 6})]
    r = phasetrace.reduce_ops(ops, spans)
    assert r["busy_s"] == pytest.approx(0.014)
    # the while's own 3 of its 8 ms, and its body's 4; the copy takes
    # [9, 10] from the while (it started later) and loses [10, 11] to the
    # fusion that started after it; `mtsl.towers` is no phase's element
    assert r["phases"] == {"tower": pytest.approx(0.002),
                           "server": pytest.approx(0.007),
                           "loss": pytest.approx(0.002),
                           "update": pytest.approx(0.001),
                           "unscoped": pytest.approx(0.002)}
    assert sum(r["phases"].values()) == pytest.approx(r["busy_s"])
    assert r["ssd_s"] == pytest.approx(0.002)
    # [15, 18]: the round, as dispatch covers only a third of it (and the
    # harness's bench.data comes after any program span); [12, 14]: the
    # input wait, the innermost of the two that cover it; [19, 20]: round
    assert r["idle_gaps"] == [["repro.round", pytest.approx(0.003)],
                              ["repro.input_wait", pytest.approx(0.002)],
                              ["repro.round", pytest.approx(0.001)]]
    assert r["idle_s"] == {"repro.round": pytest.approx(0.004),
                           "repro.input_wait": pytest.approx(0.002)}
    hs = r["host_spans"]
    assert set(hs) == {"repro.round", "repro.input_wait", "repro.dispatch"}
    assert hs["repro.input_wait"] == {
        "count": 1, "total_s": pytest.approx(0.002),
        "mean_s": pytest.approx(0.002),
        "counters": {"round": 4, "queued": 0}}
    assert hs["repro.round"]["counters"]["step_num"] == 4


def test_gap_without_spans_is_other():
    spans = [("bench.window", 0, 10 * MS, {}),
             ("bench.data", 2 * MS, 3 * MS, {})]
    ops = [("fusion.1", 0, 2 * MS, ""), ("fusion.2", 4 * MS, 10 * MS, "")]
    r = phasetrace.reduce_ops(ops, spans)
    # [2, 4]: bench.data covers half of it, and no program span does
    assert r["idle_gaps"] == [["bench.data", pytest.approx(0.002)]]
    r = phasetrace.reduce_ops(ops, spans[:1])
    assert r["idle_gaps"] == [["other", pytest.approx(0.002)]]
    assert r["phases"]["unscoped"] == pytest.approx(r["busy_s"])
    assert r["host_spans"] == {}


def test_scope_names_match_whole_elements():
    assert phasetrace.scope_names(
        "jit(f)/transpose(jvp(vmap(mtsl.tower)))/dot") == [
            "f", "mtsl.tower", "dot"]
    assert phasetrace.phase_of("jit(f)/mtsl.server/mtsl.loss/x") == "loss"
    assert phasetrace.phase_of("jit(f)/mtsl.towers/mtsl.tower_x") == (
        "unscoped")
    assert phasetrace.phase_of("") == "unscoped"


def test_ops_take_op_names_from_their_own_program():
    programs = {"jit_a(1)": {"fusion.1": "jit(a)/mtsl.tower/dot"},
                "jit_b(2)": {"fusion.1": "jit(b)/mtsl.update/mul"}}
    modules = [("jit_b(2)", 10, 20), ("jit_a(1)", 0, 10)]
    ops = [("fusion.1", 1, 2), ("fusion.1", 12, 13), ("fusion.1", 25, 26)]
    named = phasetrace.name_ops(ops, modules, programs)
    assert [op[3] for op in named] == [
        "jit(a)/mtsl.tower/dot", "jit(b)/mtsl.update/mul", ""]


def test_hlo_op_names_read_the_wire_format():
    """An HloProto as cut_trace writes it: names and op_names survive the
    round trip through the protobuf reader."""
    full = {"fusion.1": "jit(f)/jvp(mtsl.server)/dot", "copy.2": "state",
            "while.3": "jit(f)/while" + "/x" * 100}
    inner = b"".join(
        cut_trace._field(2, cut_trace._field(1, n.encode())
                         + cut_trace._field(7, cut_trace._field(
                             2, on.encode())))
        for n, on in full.items())
    proto = cut_trace._field(1, cut_trace._field(3, inner))
    assert phasetrace.hlo_op_names(proto) == full
    kept = cut_trace._cut_hlo(proto, {"fusion.1", "while.3"})
    assert phasetrace.hlo_op_names(kept) == {
        n: full[n] for n in ("fusion.1", "while.3")}


def test_recorded_v5e_trace_with_phase_scopes():
    """A traced run of mamba2-130m.train.m8-s512 on a TPU v5 lite whose
    program carries the phase scopes, cut to the first 0.42 s of its window
    (one round and the start of the next) by `bench/tests/cut_trace.py`;
    the `op_name`s come from the HLO the profile holds."""
    r = phasetrace.reduce_file(SCOPED)
    ph = r["phases"]
    assert sum(ph.values()) == pytest.approx(r["busy_s"])
    # one round's server (281 ms) and the towers of one round and a half
    assert ph["server"] > ph["tower"] > ph["update"] > ph["loss"] > 0
    assert ph["unscoped"] < 0.1 * r["busy_s"]
    assert 0 < r["ssd_s"] < ph["server"] + ph["tower"]
    spans = r["host_spans"]
    assert {"repro.round", "repro.input_wait", "repro.dispatch",
            "repro.draw"} <= set(spans)
    assert "queued" in spans["repro.input_wait"]["counters"]
    assert all("round" in s["counters"] for s in spans.values())
    # the longest gap is the window's first, the loop waiting for its input
    assert r["idle_gaps"][0][0] == "repro.input_wait"
    assert "other" not in r["idle_s"]


def test_recorded_trace_without_scopes_reads_nothing(tmp_path, monkeypatch):
    """The benchmark's earlier fixture, a program without scopes or spans:
    every op is unscoped, and the readers return None rather than 0."""
    r = phasetrace.reduce_file(UNSCOPED)
    assert r["phases"]["unscoped"] == pytest.approx(r["busy_s"])
    assert r["ssd_s"] == 0 and r["host_spans"] == {}
    cell = tmp_path / "mamba2-130m.train.m8-s512" / "plugins"
    cell.mkdir(parents=True)
    shutil.copy(UNSCOPED, cell / "t.xplane.pb")
    monkeypatch.setattr(phasetrace, "TRACES", tmp_path)

    def run_record():
        return types.SimpleNamespace(
            kind="train", data="lm", trace={}, traced_rounds=2,
            cell=types.SimpleNamespace(name="mamba2-130m.train.m8-s512"))

    run = run_record()
    assert phasetrace.phase_ms(run, "lm", "server") is None
    assert phasetrace.ssd_ms(run, "lm") is None
    assert phasetrace.span_ms(run, "lm", "repro.dispatch") is None
    # the same readers on a run of the scoped fixture
    shutil.copy(SCOPED, cell / "t.xplane.pb")
    run = run_record()
    server = phasetrace.phase_ms(run, "lm", "server")
    assert server == pytest.approx(
        phasetrace.reduce_file(SCOPED)["phases"]["server"] / 2 * 1e3)
    assert phasetrace.span_ms(run, "lm", "repro.dispatch") > 0
    # another cell's data, or an untraced run, reads nothing
    assert phasetrace.phase_ms(run, "image", "server") is None
    run.trace = None
    assert phasetrace.phase_ms(run, "lm", "server") is None
