"""The comparison that decides `correct`.

A training cell has these numbers:

  loss        |loss_prog - loss_ref| / |loss_ref|, the worst over the
              checked steps
  grad        per unit (one client's slice of a tower leaf, one layer's
              slice of a stacked block leaf, or a whole server leaf):
              | |g_prog| - |g_ref| | / max(|g_ref|, median unit |g_ref|),
              of the first step's gradient as the optimizer got it; the
              worst unit
  update      the same gap for the parameters' change over the checked
              steps; the worst unit
  grad_med,   the median unit's gap of the same two: steadier from seed to
  update_med  seed than the worst unit, which one small leaf's rounding sets

Units whose reference gradient is below a thousandth of the median unit's
(as a key bias under a softmax) move by round-off alone and are left out
of the unit numbers by that rule, not by name.

The cell's limits file names the numbers compared; each is printed beside
its limit, and `correct` holds when each is at or under its limit.
"""
from __future__ import annotations

import numpy as np

SKIP_SHARE = 1e-3


def norm_arrays(tree, ax):
    """Per-unit norms of each leaf, as a list of flat arrays (jit-able);
    `ax` lists each leaf's number of leading unit axes."""
    import jax
    import jax.numpy as jnp

    out = []
    for leaf, k in zip(jax.tree.leaves(tree), ax):
        x = jnp.square(leaf.astype(jnp.float32))
        out.append(jnp.sqrt(jnp.sum(x, axis=tuple(range(k, x.ndim))))
                   .reshape(-1))
    return out


def paths_of(tree):
    import jax

    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def unit_norms(tree, axes):
    """{"path[i]": norm} over units; a jitted reduction on the device."""
    import jax

    ax = jax.tree.leaves(axes)
    return label(paths_of(tree), jax.jit(lambda t: norm_arrays(t, ax))(tree))


def diff_norms(a, b, axes, scale=1.0):
    """unit_norms((a - b) * scale) without holding the difference."""
    import jax

    ax = jax.tree.leaves(axes)
    diff = jax.jit(lambda a, b: norm_arrays(jax.tree.map(
        lambda x, y: (x - y) * scale, a, b), ax))
    return label(paths_of(a), diff(a, b))


def label(paths, arrays):
    out = {}
    for p, v in zip(paths, arrays):
        for i, x in enumerate(np.asarray(v, np.float64)):
            out[f"{p}[{i}]"] = float(x)
    return out


def kept_units(ref_grad):
    med = float(np.median(list(ref_grad.values())))
    return {k for k, v in ref_grad.items() if v >= SKIP_SHARE * med}


def unit_gaps(prog, ref, keep):
    """(worst, median, worst unit) over kept units of
    | |p| - |r| | / max(|r|, median |r|)."""
    med = float(np.median([ref[k] for k in keep]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in sorted(keep)}
    if not all(np.isfinite(g) for g in gaps.values()):
        return float("inf"), float("inf"), ""
    name = max(gaps, key=gaps.get)
    return gaps[name], float(np.median(list(gaps.values()))), name


def loss_gap(prog_losses, ref_losses):
    gaps = [abs(p - r) / abs(r) for p, r in zip(prog_losses, ref_losses)]
    return max(gaps) if all(np.isfinite(gaps)) else float("inf")


def train_numbers(prog, ref):
    """prog/ref: {"losses": [...], "grad": {unit: norm}, "update": {...}}."""
    keep = kept_units(ref["grad"])
    grad, grad_med, grad_unit = unit_gaps(prog["grad"], ref["grad"], keep)
    upd, upd_med, upd_unit = unit_gaps(prog["update"], ref["update"], keep)
    return {"loss": loss_gap(prog["losses"], ref["losses"]),
            "grad": grad, "update": upd,
            "grad_med": grad_med, "update_med": upd_med}, {
                "grad": grad_unit, "update": upd_unit,
                "units_kept": len(keep), "units": len(ref["grad"])}


def verdict(numbers, limits):
    """(correct, [[name, number, limit], ...]) in the limits' order."""
    rows, ok = [], True
    for name, limit in limits.items():
        v = numbers.get(name, float("inf"))
        v = float(v) if v is not None and np.isfinite(v) else float("inf")
        ok = ok and v <= limit
        rows.append([name, v, limit])
    return ok, rows
