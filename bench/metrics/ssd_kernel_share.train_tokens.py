"""Share (%) of the device self time under the scope `mamba.ssd`
(models/ssm.py mamba_forward: the SSD scan of every block, forward and
backward) spent in ops whose HLO instruction is a custom call, i.e. in
the fused Pallas kernels rather than in XLA's ops of the chunked
reference, over the traced window (profiler trace; bench/kernelshare.py)."""
import kernelshare


def read(run):
    return kernelshare.share_pct(run, "lm")
