"""Device time per traced round of the ops under the scope `mamba.ssd`
(models/ssm.py mamba_forward: the chunked SSD scan of every block,
forward and backward, whichever implementation runs): their self time
in the traced window (profiler trace)."""
import phasetrace


def read(run):
    return phasetrace.ssd_ms(run, "lm")
