"""Model FLOPs of the traced rounds (bench/flops.py: forward and backward,
no recomputation) / the traced window (profiler trace) / (chips x the bf16
peak of the device kind, bench/peaks.json), in %. The traced rounds all run
inside that window: it opens when the round before them is done and closes
when the last of them is."""
import flops


def read(run):
    if run.kind != "train" or run.data != "lm" or run.trace is None:
        return None
    if not run.traced_rounds:
        return None
    peak = flops.peak_flops(run.device_kind) * run.chips
    return (run.round_flops * run.traced_rounds / run.trace["window_s"]
            / peak * 100.0)
