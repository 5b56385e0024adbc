"""Mean length of the profiler span `repro.draw` (data/pipeline.py
client_batches: one round's synthesis on the producer thread, without the
time the consumer holds the generator suspended), over the spans that
start in the traced window (host clock)."""
import phasetrace


def read(run):
    return phasetrace.span_ms(run, "image", "repro.draw")
