"""Mean length of the profiler span `repro.dispatch` (train/loop.py: the
loop's call of the round program, which returns once the round is
enqueued), over the spans that start in the traced window (host clock)."""
import phasetrace


def read(run):
    return phasetrace.span_ms(run, "lm", "repro.dispatch")
