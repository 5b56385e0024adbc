"""Model operations of one training round, from shapes alone.

The count is the work the model's definition requires, whatever implements
it: every matrix product (2 per multiply-add), the causal convolution, and
the SSD as its linear recurrence per token and head (decay and injection of
the P x N state, 3PN, and its read-out, 2PN). Norms, activations and the
loss are left out; so is recomputation. Training is 3x the forward pass
(forward, and a backward of twice its cost). The embedding lookup is free.
"""
from __future__ import annotations


def mamba2_forward_per_token(cfg):
    d, V, N, W = (cfg["d_model"], cfg["vocab_size"], cfg["d_state"],
                  cfg["d_conv"])
    d_in = cfg["expand"] * d
    H = d_in // cfg["headdim"]
    P = cfg["headdim"]
    proj = 2 * d * (2 * d_in + 2 * N + H) + 2 * d_in * d
    conv = 2 * W * (d_in + 2 * N)
    ssd = 5 * H * P * N
    return cfg["n_layer"] * (proj + conv + ssd) + 2 * d * V


def mamba2_train_round(cfg, traffic):
    tokens = (traffic["clients"] * traffic["batch_per_client"]
              * traffic["seq_len"])
    return 3 * tokens * mamba2_forward_per_token(cfg)


def _conv(hw, k, cin, cout):
    return 2 * hw * hw * k * k * cin * cout


def resnet_forward_per_image(cfg):
    size, stages = cfg["image_size"], cfg["resnet_stages"]
    c0 = stages[0][0]
    total = _conv(size, 3, cfg["image_channels"], c0)
    cin, hw = c0, size
    for s, (cout, nblocks) in enumerate(stages):
        hw = hw if s == 0 else hw // 2
        for i in range(nblocks):
            c = cin if i == 0 else cout
            total += _conv(hw, 3, c, cout) + _conv(hw, 3, cout, cout)
            if c != cout:
                total += _conv(hw, 1, c, cout)
        cin = cout
    return total + 2 * cin * cfg["num_classes"]


def resnet_train_round(cfg, traffic):
    images = traffic["clients"] * traffic["batch_per_client"]
    return 3 * images * resnet_forward_per_image(cfg)


TRAIN_ROUND = {"mamba2": mamba2_train_round, "resnet": resnet_train_round}


def train_round(cfg, traffic):
    return TRAIN_ROUND[cfg["family"]](cfg, traffic)


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
