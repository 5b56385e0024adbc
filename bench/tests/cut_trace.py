"""Cut a traced benchmark run to a small test fixture.

    python bench/tests/cut_trace.py TRACE.xplane.pb OUT.xplane.pb \
        --seconds 0.42

Keeps the first --seconds of the `bench.window` span: on the host planes
the window span (cut to match) and the spans of the program (`repro.*`)
and of the harness (`bench.*`) that start inside it, with their counters;
on the first device plane the "XLA Ops" events that start inside it and
the "XLA Modules" events that overlap it, without stats, their names (HLO
instruction text) cut to --name-chars characters; on the `/host:metadata`
plane each program's HLO (`Hlo Proto`) cut to the instructions the kept
ops name, each with its name and `op_name` alone. Needs the XPlane
protobuf module that TensorFlow ships.
"""
import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import phasetrace  # noqa: E402

HLO_STAT = "Hlo Proto"


def _start_ps(line, ev):
    return line.timestamp_ns * 1000 + ev.offset_ps


def _window(space):
    for plane in space.planes:
        for line in plane.lines:
            for ev in line.events:
                if plane.event_metadata[ev.metadata_id].name == "bench.window":
                    t0 = _start_ps(line, ev)
                    return t0, t0 + ev.duration_ps
    raise SystemExit("no bench.window span in the trace")


def _copy_plane(src, dst, keep, name_chars, with_stats):
    """The lines and events of `src` that `keep(line, ev, name)` admits
    (returning the event's duration in ps, or None), and their metadata.
    Returns the instruction names of the events kept."""
    dst.id, dst.name = src.id, src.name
    used = set()
    for line in src.lines:
        kept = [(ev, d) for ev in line.events if (d := keep(
            line, ev, src.event_metadata[ev.metadata_id].name)) is not None]
        if not kept:
            continue
        out = dst.lines.add()
        out.id, out.display_id, out.name = line.id, line.display_id, line.name
        out.timestamp_ns = line.timestamp_ns
        for ev, d in kept:
            e = out.events.add()
            e.metadata_id, e.offset_ps, e.duration_ps = (
                ev.metadata_id, ev.offset_ps, d)
            if with_stats:
                e.stats.extend(ev.stats)
            used.add(ev.metadata_id)
    for k in used:
        md = dst.event_metadata[k]
        md.id, md.name = k, src.event_metadata[k].name[:name_chars]
    if with_stats:
        for k, v in src.stat_metadata.items():
            dst.stat_metadata[k].CopyFrom(v)
    return {src.event_metadata[k].name.split(" = ", 1)[0].lstrip("%")
            for k in used}


def _field(number, payload):
    """One length-delimited protobuf field."""
    key, n, out = number << 3 | 2, len(payload), bytearray()
    for v in (key, n):
        while v >= 0x80:
            out.append(v & 0x7F | 0x80)
            v >>= 7
        out.append(v)
    return bytes(out) + payload


def _cut_hlo(proto, keep):
    """An HloProto holding, of `proto`, the instructions named in `keep`,
    each with its name and `op_name` alone (field numbers: xla/service/
    hlo.proto)."""
    names = phasetrace.hlo_op_names(proto)
    instrs = b"".join(
        _field(2, _field(1, n.encode()) + _field(7, _field(2, on.encode())))
        for n, on in sorted(names.items()) if n in keep)
    return _field(1, _field(3, _field(1, b"kept") + instrs))


def _copy_metadata(src, dst, keep):
    dst.id, dst.name = src.id, src.name
    hlo_ids = [k for k, v in src.stat_metadata.items() if v.name == HLO_STAT]
    for k in hlo_ids:
        dst.stat_metadata[k].CopyFrom(src.stat_metadata[k])
    for k, md in src.event_metadata.items():
        out = dst.event_metadata[k]
        out.id, out.name = md.id, md.name
        for st in md.stats:
            if st.metadata_id in hlo_ids:
                s = out.stats.add()
                s.metadata_id = st.metadata_id
                s.bytes_value = _cut_hlo(st.bytes_value, keep)


def cut(src_path, dst_path, seconds, name_chars=120):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    space.ParseFromString(pathlib.Path(src_path).read_bytes())
    lo, hi = _window(space)
    hi = min(hi, lo + int(seconds * 1e12))
    devices = sorted((p for p in space.planes
                      if p.name.startswith("/device:TPU:")
                      and not p.name.endswith("SparseCore")),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))

    def host(line, ev, name):
        t = _start_ps(line, ev)
        if name == "bench.window":
            return hi - lo
        ok = name.startswith(("repro.", "bench.")) and lo <= t < hi
        return ev.duration_ps if ok else None

    def device(line, ev, name):
        t = _start_ps(line, ev)
        if line.name == "XLA Ops":
            ok = lo <= t < hi
        else:
            ok = line.name == "XLA Modules" and t < hi and (
                t + ev.duration_ps > lo)
        return ev.duration_ps if ok else None

    out = xplane_pb2.XSpace()
    ops, metadata = set(), None
    for plane in space.planes:
        if plane.name == "/host:metadata":
            metadata = plane
        elif plane.name.startswith("/host:"):
            _copy_plane(plane, out.planes.add(), host, name_chars, True)
        elif devices and plane is devices[0]:
            ops = _copy_plane(plane, out.planes.add(), device, name_chars,
                              False)
    if metadata is not None:
        _copy_metadata(metadata, out.planes.add(), ops)
    pathlib.Path(dst_path).write_bytes(out.SerializeToString())


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--seconds", type=float, default=0.42)
    ap.add_argument("--name-chars", type=int, default=120)
    a = ap.parse_args()
    cut(a.src, a.dst, a.seconds, a.name_chars)
