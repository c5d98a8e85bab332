"""The comparison that decides ``correct`` for a training cell.

Both sides are a record of the first steps: each step's loss, each leaf's
gradient at step 1 as the optimizer got it, and each leaf's change over the
steps (``refnn.run_steps`` for the reference, the driver's reading of the
trainer's state for the program).  From them, leaf by leaf:

- the *gap of norms*  | ||program|| - ||reference|| |, and
- the *norm of the difference*  || program - reference ||,

both measured against the reference's norm of that leaf or of the median
leaf, whichever is larger (some gradients are all but zero).  The numbers:

- ``loss<i>_gap``       |program - reference| / |reference|, one per step;
- ``grad_gap``, ``delta_gap``                the worst leaf's gap of norms;
- ``grad_gap_median``, ``delta_gap_median``  the median leaf's gap of norms;
- ``grad_diff_median``, ``delta_diff_median`` the median leaf's norm of the
  difference, and ``grad_diff_decile``, ``delta_diff_decile`` the first
  decile's over the leaves.  A gap of norms is blind to rounding noise of
  mean zero (5% of noise on every element moves a norm by a thousandth),
  so the control in a lower precision needs a norm of the difference; and
  at a random start the gradients of a deep BatchNorm net are so sensitive
  that bfloat16 alone turns the median leaf's direction by 40-100% (PR 25's
  readings), so it is read on the tenth of the leaves nearest the loss,
  where little of the backward pass has acted;
- ``grad_diff_min``  the norm of the difference on the leaf that agrees
  best, in practice the bias of the last layer, whose gradient is the mean
  of (prediction - target);
- ``grad_diff_output``  the worst norm of the difference over the kernels of
  the layers the configuration lists as ``output_layers``: their gradient
  is (features)^T (d loss / d output), so it carries the error of the whole
  forward pass and of the loss, and nothing of the backward pass below.
  A forward error grows in proportion to the rounding, while a gradient that
  has come back through ReLUs grows with its square root (a share of the
  gates in proportion to the rounding flips), so bfloat16 and fp8, 16x
  apart in rounding, read some 10x apart here and 3-4x apart on the median
  leaf (PR 25's readings, PERF.md s6).

The ``delta`` numbers leave out the leaves whose reference gradient is under
a thousandth of the median leaf's: under Adam they move by round-off alone.
A number that is not finite counts as infinitely far off.  Which numbers
carry a limit is the configuration file's to say (PERF.md gives the
readings behind each).
"""

from __future__ import annotations

import math
import statistics

import numpy as np

SMALL_GRADIENT = 1e-3  # of the median leaf's gradient norm


def _norm(x) -> float:
    return float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))


def leaf_numbers(program: dict, reference: dict, leaves=None):
    """leaf -> (gap of norms, norm of the difference), both relative."""
    leaves = sorted(reference) if leaves is None else leaves
    ref_norm = {k: _norm(reference[k]) for k in leaves}
    median = statistics.median(ref_norm.values())
    out = {}
    for k in leaves:
        scale = max(ref_norm[k], median, 1e-30)
        if k in program and program[k].shape == reference[k].shape:
            gap = abs(_norm(program[k]) - ref_norm[k]) / scale
            diff = _norm(np.asarray(program[k], np.float64)
                         - np.asarray(reference[k], np.float64)) / scale
        else:
            gap = diff = math.inf
        out[k] = (gap if math.isfinite(gap) else math.inf,
                  diff if math.isfinite(diff) else math.inf)
    return out, ref_norm


def training_numbers(program: dict, reference: dict,
                     output_layers=()) -> tuple[dict, dict]:
    """name -> value of every number, and name -> the leaf behind a worst."""
    numbers, where = {}, {}
    for i, (p, r) in enumerate(zip(program["loss"], reference["loss"]), 1):
        gap = abs(p - r) / max(abs(r), 1e-30)
        numbers[f"loss{i}_gap"] = gap if math.isfinite(gap) else math.inf
    grad, grad_norm = leaf_numbers(program["grad"], reference["grad"])
    heads = [layer + "/kernel" for layer in output_layers]
    if heads:
        worst = max(heads, key=lambda k: grad[k][1])
        numbers["grad_diff_output"] = grad[worst][1]
        where["grad_diff_output"] = worst
    floor = SMALL_GRADIENT * statistics.median(grad_norm.values())
    moving = [k for k in sorted(grad_norm) if grad_norm[k] >= floor]
    delta, _ = leaf_numbers(program["delta"], reference["delta"], moving)
    for name, leaves in (("grad", grad), ("delta", delta)):
        worst = max(leaves, key=lambda k: leaves[k][0])
        numbers[f"{name}_gap"], where[f"{name}_gap"] = leaves[worst][0], worst
        numbers[f"{name}_gap_median"] = statistics.median(
            v[0] for v in leaves.values())
        diffs = [v[1] for v in leaves.values()]
        numbers[f"{name}_diff_median"] = statistics.median(diffs)
        numbers[f"{name}_diff_decile"] = statistics.quantiles(diffs, n=10)[0]
        numbers[f"{name}_diff_min"] = min(diffs)
    return numbers, where


def judge(numbers: dict, limits: dict) -> tuple[bool, list]:
    """``limits``: name -> the largest value that still passes.  Returns the
    verdict and (name, value, limit) for every number that has a limit; a
    number with a limit that was not produced fails."""
    rows, ok = [], True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and math.isfinite(value) and value <= limit
        ok = ok and good
        rows.append((name, value, limit))
    return ok, rows
