"""Reduction of a profiler trace (`.xplane.pb`) to the numbers the per-layer
metrics read.

The traced window is the host span `bench.window` that the harness opens
and closes around the traced seconds. On each device plane
(`/device:TPU:<n>`) the line "XLA Ops" holds one event per executed
operation and "XLA Modules" one per executed program (`jit_<name>(<id>)`).

  busy_s        union of the device's op intervals inside the window,
                averaged over the chips used
  programs      per program name: device seconds inside the window and
                executions that overlap it
  collective_s  device seconds of all-reduce / all-gather / reduce-scatter /
                all-to-all / collective-permute ops, averaged over chips
  top_ops       the ops that took most device time (chip 0)
  idle_gaps     the longest idle gaps of chip 0, each named by the harness
                span (`bench.*`) that covers most of it, or "other"
"""
from __future__ import annotations

import re

COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute|allreduce|allgather", re.I)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _device_planes(pd):
    planes = [p for p in pd.planes if p.name.startswith("/device:TPU:")
              and not p.name.endswith("SparseCore")]
    return sorted(planes, key=lambda p: int(re.findall(r"\d+", p.name)[0]))


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return list(line.events)
    return []


def _events(evs):
    """(name, start, end) in ns; an op's name is its HLO instruction name
    (`fusion.12`), without the instruction text that follows " = "."""
    return [(e.name.split(" = ", 1)[0].lstrip("%"), e.start_ns,
             e.start_ns + e.duration_ns) for e in evs]


def _host_spans(pd):
    spans = []
    for p in pd.planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                spans += [(n, s, e) for n, s, e in _events(line.events)
                          if n.startswith("bench.")]
    return spans


def reduce_spans(devices, spans, chips, top=10):
    """devices: per chip {"ops": [(name, s, e)], "modules": [...]};
    spans: host [(name, s, e)] in the same clock (ns)."""
    windows = [(s, e) for n, s, e in spans if n == "bench.window"]
    if not windows:
        raise ValueError("no bench.window span in the trace")
    lo, hi = windows[0]
    busy, coll, programs = [], [], {}
    for i, dev in enumerate(devices[:chips]):
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in dev["ops"]
               if e > lo and s < hi]
        if not ops:
            # the device and host clocks disagree, or nothing ran: a
            # silent busy 0 would read as an idle chip
            first = min((s for _, s, _ in dev["ops"]), default=None)
            raise ValueError(f"chip {i}: none of its {len(dev['ops'])} ops "
                             f"(first at {first} ns) lies inside the "
                             f"bench.window span [{lo}, {hi}] ns")
        u = _union([(s, e) for _, s, e in ops])
        busy.append(sum(e - s for s, e in u))
        coll.append(sum(e - s for n, s, e in ops if COLLECTIVE.search(n)))
        if i == 0:
            union0, ops0 = u, ops
            for n, s, e in dev["modules"]:
                if e > lo and s < hi:
                    name = re.sub(r"\(\d+\)$", "", n)
                    t, c = programs.get(name, (0.0, 0))
                    programs[name] = (t + (min(e, hi) - max(s, lo)) * 1e-9,
                                      c + 1)
    per_op = {}
    for n, s, e in ops0:
        per_op[n] = per_op.get(n, 0.0) + (e - s) * 1e-9
    gaps, prev = [], lo
    for s, e in union0 + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    host = [(n, s, e) for n, s, e in spans if n != "bench.window"]

    def label(g0, g1):
        cover = {}
        for n, s, e in host:
            o = min(e, g1) - max(s, g0)
            if o > 0:
                cover[n] = cover.get(n, 0) + o
        return max(cover, key=cover.get) if cover else "other"

    gaps.sort(key=lambda g: g[0] - g[1])
    n = len(devices[:chips])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "collective_s": sum(coll) / n * 1e-9,
        "programs": programs,
        "top_ops": sorted(per_op.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[label(a, b), (b - a) * 1e-9] for a, b in gaps[:top]],
        "ops": len(ops0),
    }


def reduce_file(path, chips):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices = [{"ops": _events(_line(p, "XLA Ops")),
                "modules": _events(_line(p, "XLA Modules"))}
               for p in _device_planes(pd)]
    if len(devices) < chips or not devices[0]["ops"]:
        layout = {p.name: [(ln.name, len(list(ln.events))) for ln in p.lines]
                  for p in pd.planes}
        raise ValueError(f"trace has {len(devices)} device planes with ops; "
                         f"the cell uses {chips}; planes and lines: {layout}")
    return reduce_spans(devices, _host_spans(pd), chips)
