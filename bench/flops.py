"""Model operations and the chips' peaks.

A family's `train_round_flops(config, traffic)` (`families/<family>.py`)
counts the work the model's definition requires, whatever implements it,
from shapes alone: every matrix product and convolution (2 per
multiply-add) and the model's own recurrences; norms, activations and the
loss are left out, and so is recomputation. Training is 3x the forward
pass (forward, and a backward of twice its cost). The embedding lookup is
free.
"""
from __future__ import annotations


def peak_flops(device_kind):
    """bf16 peak of one chip from peaks.json. An unknown device is an error."""
    import json
    import pathlib

    table = json.loads((pathlib.Path(__file__).parent / "peaks.json")
                       .read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to bench/peaks.json with its source")
    return table[device_kind]["bf16_flops_per_s"]
