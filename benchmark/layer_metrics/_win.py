"""Shared arithmetic of the readers of attention under a window: the
``edl_flash_win_*`` kernels' ops in the reduced trace and their least
time over the pairs inside the band. Every function returns None where
the program has no such kernel or fact (the parent commit, on which the
driver runs the readers too)."""

import re

import events as ev
import flash_win_cost
import flops
from _common import flash_ops as ops  # any kernel's ops by name prefix

KERNELS = tuple(flash_win_cost.MATMULS)
# edl_flash_win_fwd_<dtype>_<batch*heads>_<seq>_<head_dim>_
_OP = re.compile(r"^_([a-z]+\d*)_(\d+)_(\d+)_(\d+)_$")


def built_with_a_window(run):
    """The ``step_built`` event of a step that holds window layers
    (``attention_window`` among its facts), else None."""
    built = ev.of_kind(run["events"], "step_built")
    if not built or "attention_window" not in built[0]:
        return None
    return built[0]


def roofline(run, kernel):
    """Least time over the pairs INSIDE the band
    (benchmark/flash_win_cost.py) over measured time, in percent, over
    every call of ``kernel`` in the slice. The window is the program's
    own fact (``step_built``)."""
    built = built_with_a_window(run)
    if built is None:
        return None
    least = measured = 0.0
    for name, seconds, calls in ops(run, kernel):
        found = _OP.match(name[len(kernel) :])
        if not found:
            continue
        dtype, bh, length, hd = found.groups()
        cost = flash_win_cost.windowed_kernel_cost(
            kernel, int(bh), int(length), int(hd), built["attention_window"],
            itemsize=4 if dtype == "f32" else 2,
        )  # fmt: skip
        least += calls * flops.roofline(*cost, run["device_kind"])[0]
        measured += seconds
    return 100.0 * least / measured if measured else None
