"""The share of a scope's device time that Pallas kernels take: of the self
time of chip 0's ops under `mamba.ssd` (models/ssm.py) in a traced run's
window, the part spent in ops whose HLO instruction is a `custom-call`
(a Pallas kernel lowers to one; XLA's own fusions, copies and loops do
not).

    python3 bench/kernelshare.py .bench/trace/<cell>

prints {"scope_s", "kernel_s", "share_pct"} as JSON. Ops are named and
timed as bench/phasetrace.py does (`op_name` and opcode from the HLO the
profile's `/host:metadata` plane holds, self time so that each instant
counts once). The reader `bench/metrics/ssd_kernel_share.train_tokens.py`
calls `share_pct`.
"""
from __future__ import annotations

import json
import sys

import phasetrace
import tracereduce

CUSTOM_CALL = "custom-call"


def hlo_opcodes(proto):
    """HLO instruction name -> opcode, of one HloProto."""
    out = {}
    for f, module in phasetrace.fields(proto):
        if f != 1:  # HloProto.hlo_module
            continue
        for k, comp in phasetrace.fields(module):
            if k != 3:  # HloModuleProto.computations
                continue
            for c, instr in phasetrace.fields(comp):
                if c != 2:  # HloComputationProto.instructions
                    continue
                name = opcode = None
                for i, v in phasetrace.fields(instr):
                    if i == 1:  # HloInstructionProto.name
                        name = phasetrace._text(v)
                    elif i == 2:  # HloInstructionProto.opcode
                        opcode = phasetrace._text(v)
                if name and opcode:
                    out[name] = opcode
    return out


def reduce_ops(ops, spans, scope=phasetrace.SSD_SCOPE):
    """ops: chip 0's (name, start, end, op_name, opcode); spans: host
    (name, start, end, counters), in one clock (ns). Self seconds of the
    ops under `scope` inside the `bench.window` span, and of those that
    are custom calls."""
    windows = [sp[1:3] for sp in spans if sp[0] == "bench.window"]
    if not windows:
        raise ValueError("no bench.window span in the trace")
    lo, hi = windows[0]
    ops = [(n, max(s, lo), min(e, hi), on, code)
           for n, s, e, on, code in ops if e > lo and s < hi]
    scope_t = kernel_t = 0
    for op, t in zip(ops, phasetrace.self_times(ops)):
        if scope in phasetrace.scope_names(op[3]):
            scope_t += t
            if op[4] == CUSTOM_CALL:
                kernel_t += t
    return {"scope_s": scope_t * 1e-9, "kernel_s": kernel_t * 1e-9,
            "share_pct": 100.0 * kernel_t / scope_t if scope_t else None}


def reduce_file(path, scope=phasetrace.SSD_SCOPE):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes = tracereduce._device_planes(pd)
    if not planes:
        raise ValueError(f"no TPU device plane in {path}")
    ops = tracereduce._events(tracereduce._line(planes[0], "XLA Ops"))
    modules = tracereduce._events(tracereduce._line(planes[0],
                                                    "XLA Modules"))
    data = open(path, "rb").read()
    protos = list(phasetrace.hlo_protos(data))
    names = phasetrace.name_ops(
        ops, modules, {p: phasetrace.hlo_op_names(b) for p, b in protos})
    codes = phasetrace.name_ops(
        ops, modules, {p: hlo_opcodes(b) for p, b in protos})
    return reduce_ops([op + (c[3],) for op, c in zip(names, codes)],
                      phasetrace._host_spans(pd), scope)


def share_pct(run, data):
    """% of the `mamba.ssd` self time in custom calls, over a traced run's
    window; None where the run is of another data, was not traced, or its
    program has no op under the scope."""
    if not (run.kind == "train" and run.data == data
            and run.trace is not None and run.traced_rounds):
        return None
    if getattr(run, "kernel_trace", None) is None:
        run.kernel_trace = reduce_file(phasetrace.trace_file(
            phasetrace.TRACES / run.cell.name))
    return run.kernel_trace["share_pct"]


if __name__ == "__main__":
    target = sys.argv[1] if len(sys.argv) > 1 else "."
    path = (target if target.endswith(".xplane.pb")
            else phasetrace.trace_file(target))
    print(json.dumps(reduce_file(path), indent=1))
