"""Share of the traced window in which no operation ran on the device,
averaged over the chips used (profiler trace), in %."""


def read(run):
    if run.kind != "train" or run.data != "image" or run.trace is None:
        return None
    return (1.0 - run.trace["busy_s"] / run.trace["window_s"]) * 100.0
