"""Shared arithmetic of the grouped-matmul readers: the kernels' ops in
the trace, and the rows a call really multiplies, from the program's
own counters. Every function returns None (or nothing) where the
program has no such kernel or counter."""

import re

import events as ev
import flops
import gmm_cost
from _common import flash_ops as ops  # any kernel's ops by name prefix

GMM = "edl_gmm"
TGMM = "edl_tgmm"
# edl_gmm_k<K>_<fwd|dlhs>_<dtype>_<buffer rows>_<N>_ : the contraction
# width is in the kernel's name, the result's shape follows it
_GMM_OP = re.compile(r"^edl_gmm_k(\d+)_[a-z]+_([a-z]+\d*)_(\d+)_(\d+)_$")
# edl_tgmm_<dtype>_<groups>_<K>_<N>_
_TGMM_OP = re.compile(r"^edl_tgmm_([a-z]+\d*)_(\d+)_(\d+)_(\d+)_$")


def rows_per_call(run):
    """Rows of held experts in one expert layer in one step, averaged
    over the measured windows (``moe_rows_here`` of the ``train_window``
    events over steps x expert layers), and the experts held; None
    where the events have no such counters."""
    built = ev.of_kind(run["events"], "step_built")
    windows = [w for w in run["windows"] if "moe_rows_here" in w]
    if not built or "expert_layers" not in built[0] or not windows:
        return None
    steps = sum(w["steps"] for w in windows)
    rows = sum(w["moe_rows_here"] for w in windows)
    return rows / (steps * built[0]["expert_layers"]), built[0]["experts_held"]


def roofline(run, kernel):
    """Least time by shapes over measured time, in percent, over every
    call of ``kernel`` in the slice."""
    counted = rows_per_call(run)
    if counted is None:
        return None
    rows, groups = counted
    least = measured = 0.0
    for name, seconds, calls in ops(run, kernel):
        found = (_GMM_OP if kernel == GMM else _TGMM_OP).match(name)
        if not found:
            continue  # not a call of the kernel: a name that only starts alike
        if kernel == GMM:
            k, dtype, _, n = found.groups()
        else:
            dtype, _, k, n = found.groups()
        shape_cost = gmm_cost.grouped_product_cost(
            rows, int(k), int(n), groups, 4 if dtype == "f32" else 2
        )
        least += calls * flops.roofline(*shape_cost, run["device_kind"])[0]
        measured += seconds
    return 100.0 * least / measured if measured else None
