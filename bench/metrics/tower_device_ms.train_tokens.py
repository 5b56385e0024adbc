"""Device time per traced round of the ops under the scope
`mtsl.tower` (core/mtsl.py make_loss_fn: the clients' towers,
vmapped, forward and backward): their self time in the traced window
(profiler trace), each instant counted once."""
import phasetrace


def read(run):
    return phasetrace.phase_ms(run, "lm", "tower")
