"""Images of every round completed in the window / the window (host clock;
the window closes when the device has finished the last round)."""


def read(run):
    if run.kind != "train" or run.data != "image":
        return None
    return run.rounds * run.items_per_round / run.window_s
