"""Device time per traced round of the ops under the scope
`mtsl.update` (core/mtsl.py build_train_phases apply_step:
sync, the optimizer and the parameter update): their self time in the traced window
(profiler trace), each instant counted once."""
import phasetrace


def read(run):
    return phasetrace.phase_ms(run, "image", "update")
