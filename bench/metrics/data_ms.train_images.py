"""Mean host time of one draw from the program's synthetic source, over the
window's draws (host clock around next() on the prefetch thread)."""
import numpy as np


def read(run):
    if run.kind != "train" or run.data != "image" or not run.data_s:
        return None
    return float(np.mean(run.data_s)) * 1e3
