"""Device time per round: the union of device-op intervals in the traced
window / the rounds traced (profiler trace)."""


def read(run):
    if run.kind != "train" or run.data != "image" or run.trace is None:
        return None
    if not run.traced_rounds:
        return None
    return run.trace["busy_s"] / run.traced_rounds * 1e3
