"""Mean length of the profiler span `repro.input_wait` (train/loop.py: the
loop's take of the next staged (batch, schedule) pair from
pipeline_rounds), over the spans that start in the traced window (host
clock). It is the loop's wait per round for its next input: about the
draw's time when the draw sets the pace, about the round's device time
when the device does (the harness then holds the producer back). Its
counter `queued` is the pairs the producer had ready at the take."""
import phasetrace


def read(run):
    return phasetrace.span_ms(run, "lm", "repro.input_wait")
