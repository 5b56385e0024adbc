"""The program's own names in a traced run's profile: device time by the
MTSL round's phase scopes, and the training loop's host spans.

    python3 bench/phasetrace.py .bench/trace/<cell>

prints the reduction below as JSON. The metric readers of bench/metrics/
that read scopes or program spans call `phase_ms`, `ssd_ms` and `span_ms`,
which reduce the profile a `--trace 1` run of bench/run.py leaves under
`.bench/trace/<cell>/` once per run. On a program without the scopes or
spans they return None.

  window_s      the harness's `bench.window` span
  busy_s        union of chip 0's op intervals inside the window
  phases        self time of chip 0's ops by phase scope (`mtsl.tower`,
                `mtsl.server`, `mtsl.loss`, `mtsl.update`; core/mtsl.py),
                or `unscoped`: they sum to busy_s
  ssd_s         self time of chip 0's ops under `mamba.ssd` (models/ssm.py)
  host_spans    per program span (`repro.*`; train/loop.py,
                data/pipeline.py) starting inside the window: count, total
                and mean seconds, and the mean of each counter
  idle_gaps     chip 0's longest idle gaps, each named by the innermost
                program span covering more than half of it, else by the
                harness span (`bench.*`) covering most of it, else "other"
  idle_s        chip 0's idle time inside the window by those names

An op's scope comes from its `op_name` metadata, the name stack JAX writes
(`jit(round_fn)/transpose(jvp(mtsl.server))/while/body/...`): a scope is a
whole element of it, with the transforms wrapped round it taken off. TPU op
events carry no metadata; the profile's `/host:metadata` plane holds each
program's HLO (`Hlo Proto`), which maps an op's instruction name to its
`op_name`, within the program (`XLA Modules` event) the op ran in. An op
the compiler made from nothing (copies of the state) has no `op_name`.
An op's self time is its interval less the part of it in which an op that
started later (one nested in it, as a `while` body's ops are in the
`while`) runs, so each instant counts once.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import json
import os
import pathlib
import re
import sys

import tracereduce

# where bench/run.py writes a traced run's profile: <TRACES>/<cell>/
TRACES = pathlib.Path(__file__).resolve().parents[1] / ".bench" / "trace"

PHASES = ("tower", "server", "loss", "update")
PHASE_SCOPES = {f"mtsl.{p}": p for p in PHASES}
SSD_SCOPE = "mamba.ssd"
_ELEMENT = re.compile(r"(?:[\w.-]+\()*([^()]*?)\)*")


def scope_names(op_name):
    """The elements of a name stack, each without the transforms that wrap
    it: `a/transpose(jvp(mtsl.server))/b` -> [a, mtsl.server, b]."""
    out = []
    for el in op_name.split("/"):
        m = _ELEMENT.fullmatch(el)
        out.append(m.group(1) if m else el)
    return out


def phase_of(op_name):
    """The innermost phase scope of an op, or "unscoped"."""
    phase = "unscoped"
    for el in scope_names(op_name or ""):
        phase = PHASE_SCOPES.get(el, phase)
    return phase


def self_times(ops):
    """Per op (name, start, end, ...): the part of its interval in which no
    op that started after it is running. Ops that start together go
    innermost first (the shorter)."""
    order = sorted(range(len(ops)), key=lambda i: ops[i][1])
    points = sorted({t for op in ops for t in op[1:3]})
    out = [0] * len(ops)
    heap, k = [], 0
    for t0, t1 in zip(points, points[1:]):
        while k < len(order) and ops[order[k]][1] <= t0:
            i = order[k]
            heapq.heappush(heap, (-ops[i][1], ops[i][2], i))
            k += 1
        while heap and heap[0][1] <= t0:
            heapq.heappop(heap)
        if heap:
            out[heap[0][2]] += t1 - t0
    return out


# -- the profile's HLO, read from the protobuf wire format ------------------

def _varint(buf, i):
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return v, i


def fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    memoryviews for length-delimited and fixed-width fields."""
    buf, i = memoryview(buf), 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(v):
    return bytes(v).decode("utf-8", "replace")


def hlo_protos(space):
    """(program name, HloProto bytes) of each program in an XSpace's
    `/host:metadata` plane."""
    for f, plane in fields(space):
        if f != 1:  # XSpace.planes
            continue
        name, metas, stat_names = None, [], {}
        for k, v in fields(plane):
            if k == 2:  # XPlane.name
                name = _text(v)
            elif k == 4:  # event_metadata: map entry, value XEventMetadata
                metas.append(dict(fields(v)).get(2, b""))
            elif k == 5:  # stat_metadata: map entry, value XStatMetadata
                sm = dict(fields(dict(fields(v)).get(2, b"")))
                stat_names[sm.get(1, 0)] = _text(sm.get(2, b""))
        if name != "/host:metadata":
            continue
        for md in metas:
            prog, proto = None, None
            for k, v in fields(md):
                if k == 2:  # XEventMetadata.name
                    prog = _text(v)
                elif k == 5:  # XEventMetadata.stats: XStat
                    st = dict(fields(v))
                    if stat_names.get(st.get(1)) == "Hlo Proto":
                        proto = st.get(6)  # XStat.bytes_value
            if prog and proto is not None:
                yield prog, proto


def hlo_op_names(proto):
    """HLO instruction name -> `op_name`, of one HloProto."""
    out = {}
    for f, module in fields(proto):
        if f != 1:  # HloProto.hlo_module
            continue
        for k, comp in fields(module):
            if k != 3:  # HloModuleProto.computations
                continue
            for c, instr in fields(comp):
                if c != 2:  # HloComputationProto.instructions
                    continue
                name = op_name = None
                for i, v in fields(instr):
                    if i == 1:  # HloInstructionProto.name
                        name = _text(v)
                    elif i == 7:  # HloInstructionProto.metadata
                        for m, mv in fields(v):
                            if m == 2:  # OpMetadata.op_name
                                op_name = _text(mv)
                if name and op_name:
                    out[name] = op_name
    return out


def program_op_names(path):
    """Program name (`jit_round_fn(<id>)`) -> instruction -> `op_name`."""
    data = pathlib.Path(path).read_bytes()
    return {prog: hlo_op_names(proto) for prog, proto in hlo_protos(data)}


# -- the reduction ------------------------------------------------------------

def name_ops(ops, modules, programs):
    """(name, start, end, op_name) of each op, its `op_name` looked up in
    the program whose `XLA Modules` event holds the op's start."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for n, s, e in ops:
        j = bisect.bisect_right(starts, s) - 1
        prog = modules[j][0] if j >= 0 and s < modules[j][2] else None
        out.append((n, s, e, programs.get(prog, {}).get(n, "")))
    return out


def span_stats(spans, lo, hi):
    """count, total_s, mean_s and the mean of each counter, per program
    span name, over the spans that start inside [lo, hi)."""
    acc = {}
    for n, s, e, counters in spans:
        if n.startswith("repro.") and lo <= s < hi:
            a = acc.setdefault(n, {"n": 0, "t": 0, "c": {}})
            a["n"] += 1
            a["t"] += e - s
            for k, v in counters.items():
                a["c"][k] = a["c"].get(k, 0) + v
    return {n: {"count": a["n"], "total_s": a["t"] * 1e-9,
                "mean_s": a["t"] * 1e-9 / a["n"],
                "counters": {k: v / a["n"] for k, v in a["c"].items()}}
            for n, a in acc.items()}


def gap_label(spans, g0, g1):
    """The innermost program span covering more than half of [g0, g1],
    else the harness span covering most of it, else "other"."""
    cover, last = {}, {}
    for n, s, e, _ in spans:
        o = min(e, g1) - max(s, g0)
        if o > 0 and n != "bench.window":
            cover[n] = cover.get(n, 0) + o
            last[n] = max(last.get(n, s), s)
    # of the program's span names covering more than half the gap, the one
    # whose span started last (the innermost, where they nest)
    inner = [(last[n], n) for n in cover
             if n.startswith("repro.") and 2 * cover[n] > g1 - g0]
    if inner:
        return max(inner)[1]
    bench = {n: c for n, c in cover.items() if n.startswith("bench.")}
    return max(bench, key=bench.get) if bench else "other"


def reduce_ops(ops, spans, top=10):
    """ops: chip 0's (name, start, end, op_name); spans: host (name,
    start, end, counters), in the same clock (ns)."""
    windows = [sp[1:3] for sp in spans if sp[0] == "bench.window"]
    if not windows:
        raise ValueError("no bench.window span in the trace")
    lo, hi = windows[0]
    ops = [(n, max(s, lo), min(e, hi), on) for n, s, e, on in ops
           if e > lo and s < hi]
    phases = dict.fromkeys(PHASES + ("unscoped",), 0.0)
    ssd = 0.0
    for op, t in zip(ops, self_times(ops)):
        phases[phase_of(op[3])] += t * 1e-9
        if SSD_SCOPE in scope_names(op[3]):
            ssd += t * 1e-9
    union = tracereduce._union([op[1:3] for op in ops])
    gaps, prev = [], lo
    for s, e in union + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    labelled = [[gap_label(spans, a, b), (b - a) * 1e-9] for a, b in gaps]
    idle = {}
    for name, t in labelled:
        idle[name] = idle.get(name, 0.0) + t
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(e - s for s, e in union) * 1e-9,
        "phases": phases,
        "ssd_s": ssd,
        "host_spans": span_stats(spans, lo, hi),
        "idle_gaps": sorted(labelled, key=lambda g: -g[1])[:top],
        "idle_s": idle,
    }


def _host_spans(pd):
    """(name, start, end, numeric counters) of the harness's and the
    program's spans, in ns."""
    spans = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name.startswith(("bench.", "repro.")):
                    counters = {k: v for k, v in ev.stats
                                if not k.startswith("_")
                                and isinstance(v, (int, float))}
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns, counters))
    return spans


def reduce_file(path):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes = tracereduce._device_planes(pd)
    if not planes:
        raise ValueError(f"no TPU device plane in {path}")
    ops = name_ops(tracereduce._events(tracereduce._line(planes[0],
                                                         "XLA Ops")),
                   tracereduce._events(tracereduce._line(planes[0],
                                                         "XLA Modules")),
                   program_op_names(path))
    return reduce_ops(ops, _host_spans(pd))


def trace_file(directory):
    """The profile the harness reduces: the last `.xplane.pb` under it."""
    files = sorted(glob.glob(os.path.join(str(directory), "**",
                                          "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no trace written under {directory}")
    return files[-1]


# -- readers for bench/metrics/ ----------------------------------------------
# Each takes a run record of bench/train_cell.py and returns None where the
# run is of another data, was not traced, or its program carries no such
# scope or span. The first to read a run reduces its profile and keeps the
# reduction on the record as `program_trace`.


def _trace(run, data):
    if not (run.kind == "train" and run.data == data
            and run.trace is not None and run.traced_rounds):
        return None
    if getattr(run, "program_trace", None) is None:
        run.program_trace = reduce_file(trace_file(TRACES / run.cell.name))
    return run.program_trace


def phase_ms(run, data, phase):
    """Device ms of one phase per traced round."""
    tr = _trace(run, data)
    if tr is None or not any(tr["phases"][p] for p in PHASES):
        return None
    return tr["phases"][phase] / run.traced_rounds * 1e3


def ssd_ms(run, data):
    """Device ms under `mamba.ssd` per traced round."""
    tr = _trace(run, data)
    if tr is None or not tr["ssd_s"]:
        return None
    return tr["ssd_s"] / run.traced_rounds * 1e3


def span_ms(run, data, name):
    """Mean ms of one program span, over those starting in the window."""
    tr = _trace(run, data)
    st = None if tr is None else tr["host_spans"].get(name)
    return None if st is None else st["mean_s"] * 1e3


if __name__ == "__main__":
    target = sys.argv[1] if len(sys.argv) > 1 else "."
    path = target if target.endswith(".xplane.pb") else trace_file(target)
    print(json.dumps(reduce_file(path), indent=1))
