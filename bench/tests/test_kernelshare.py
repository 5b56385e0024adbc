"""The share of the SSD's device time that runs in Pallas kernels
(bench/kernelshare.py, read by ssd_kernel_share.train_tokens).

    python -m pytest -q bench/tests/test_kernelshare.py
"""
import pathlib
import shutil
import sys
import types

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests"), str(BENCH.parent / "src")]

import cut_trace  # noqa: E402
import kernelshare  # noqa: E402
import phasetrace  # noqa: E402

SCOPED = BENCH / "tests" / "fixtures" / "v5e_phases.xplane.pb"
CELL = "mamba2-130m.train.m8-s512"
MS = 1_000_000  # ns


def test_share_counts_custom_calls_under_the_scope():
    """Custom calls and fusions under `mamba.ssd` (forward and, with the
    transforms round it, backward), a custom call outside it, a `while`
    that encloses a kernel, and ops outside the window."""
    root = "jit(round_fn)/jit(main)"
    ssd = root + "/jvp(mtsl.server)/while/body/mamba.ssd"
    bwd = root + "/transpose(jvp(mtsl.server))/while/body/" \
        "transpose(jvp(mamba.ssd))"
    cc, fu = "custom-call", "fusion"
    ops = [("ssd_fwd.1", 1 * MS, 5 * MS, ssd + "/ssd_fwd/pallas_call", cc),
           ("fusion.2", 5 * MS, 6 * MS, ssd + "/reshape", fu),
           ("while.3", 6 * MS, 16 * MS, bwd, "while"),
           ("ssd_bwd.4", 7 * MS, 15 * MS, bwd + "/ssd_bwd/pallas_call", cc),
           ("attn.5", 16 * MS, 18 * MS, root + "/mtsl.server/attn", cc),
           ("fusion.6", 18 * MS, 19 * MS, root + "/mtsl.loss/exp", fu),
           ("ssd_fwd.1", 30 * MS, 34 * MS, ssd + "/ssd_fwd/pallas_call", cc)]
    spans = [("bench.window", 0, 20 * MS, {})]
    r = kernelshare.reduce_ops(ops, spans)
    # scope: 4 + 1 + the while's own 2 of its 10 + 8; kernels: 4 + 8
    assert r["scope_s"] == pytest.approx(0.015)
    assert r["kernel_s"] == pytest.approx(0.012)
    assert r["share_pct"] == pytest.approx(80.0)
    # no op under the scope: no share
    assert kernelshare.reduce_ops(ops[4:6], spans)["share_pct"] is None


def test_hlo_opcodes_read_the_wire_format():
    """An HloProto with names, opcodes and op_names (field numbers of
    xla/service/hlo.proto) as the profile's metadata plane holds it."""
    instrs = {"ssd_fwd.1": "custom-call", "fusion.2": "fusion",
              "while.3": "while"}
    inner = b"".join(
        cut_trace._field(2, cut_trace._field(1, n.encode())
                         + cut_trace._field(2, code.encode())
                         + cut_trace._field(7, cut_trace._field(
                             2, b"jit(f)/mamba.ssd/x")))
        for n, code in instrs.items())
    proto = cut_trace._field(1, cut_trace._field(3, inner))
    assert kernelshare.hlo_opcodes(proto) == instrs
    assert set(phasetrace.hlo_op_names(proto)) == set(instrs)


def test_recorded_reference_path_reads_zero(tmp_path, monkeypatch):
    """The recorded mamba2 run (a program whose SSD runs the chunked
    reference, XLA's ops alone) reads 0 %, over the same SSD time
    the phase reduction finds; through the metric's reader too, and
    another cell's data or an untraced run reads nothing."""
    r = kernelshare.reduce_file(SCOPED)
    assert r["scope_s"] == pytest.approx(
        phasetrace.reduce_file(SCOPED)["ssd_s"])
    assert r["scope_s"] > 0.1 and r["kernel_s"] == 0
    assert r["share_pct"] == 0
    cell = tmp_path / CELL / "plugins"
    cell.mkdir(parents=True)
    shutil.copy(SCOPED, cell / "t.xplane.pb")
    monkeypatch.setattr(phasetrace, "TRACES", tmp_path)
    run = types.SimpleNamespace(kind="train", data="lm", trace={},
                                traced_rounds=2,
                                cell=types.SimpleNamespace(name=CELL))
    assert kernelshare.share_pct(run, "lm") == 0
    assert kernelshare.share_pct(run, "image") is None
    run.trace = None
    assert kernelshare.share_pct(run, "lm") is None
