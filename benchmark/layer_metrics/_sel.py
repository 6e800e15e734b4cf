"""Shared arithmetic of the readers of attention under a selection: the
``edl_flash_sel_*`` kernels' ops in the reduced trace, their least time
over the pairs the selection keeps, and the device time of the loops in
which ops/sparse_select.py scores and selects. Every function returns
None where the program has no such kernel or fact (the parent commit,
on which the driver runs the readers too); where the program says it
selects and the loops are not found, the loops' reader raises."""

import os
import re

import events as ev
import flash_sel_cost
import flops
import spec
import trace_reduce
from _common import flash_ops as ops  # any kernel's ops by name prefix

KERNELS = tuple(flash_sel_cost.MATMULS)
# edl_flash_sel_fwd_<dtype>_<batch*heads>_<seq>_<head_dim>_
_OP = re.compile(r"^_([a-z]+\d*)_(\d+)_(\d+)_(\d+)_$")
# The selection runs as ``lax.map`` loops whose carried tuple holds the
# blocks selected so far, int8 (blocks, batch, block, keys): the only
# loops of the step that carry a 4-d int8 array. The trace names an op
# by its whole HLO line, and an op of the scopes
# edl/sparse_select/{scores,topk} carries no scope there, so the loops
# are how the trace tells the selection's ops from the step's others.
_SELECT_LOOP = re.compile(r"^%?while[.\d]* = \(.*?\bs8\[\d+,\d+,\d+,\d+\]")


def roofline(run, kernel):
    """Least time over the KEPT pairs (benchmark/flash_sel_cost.py)
    over measured time, in percent, over every call of ``kernel`` in
    the slice. The top-k is the program's own fact (``step_built``),
    the heads a selection serves the configuration's."""
    built = ev.of_kind(run["events"], "step_built")
    if not built or "select_topk" not in built[0]:
        return None
    topk = built[0]["select_topk"]
    heads = run["config"]["model_params"]["num_heads"]
    least = measured = 0.0
    for name, seconds, calls in ops(run, kernel):
        found = _OP.match(name[len(kernel) :])
        if not found:
            continue
        dtype, bh, length, hd = found.groups()
        cost = flash_sel_cost.selected_kernel_cost(
            kernel, int(bh), int(length), int(hd), topk, heads,
            itemsize=4 if dtype == "f32" else 2,
        )  # fmt: skip
        least += calls * flops.roofline(*cost, run["device_kind"])[0]
        measured += seconds
    return 100.0 * least / measured if measured else None


def trace_file(run):
    """The run's own trace: run.py keeps it under the cell's run
    directory and hands the readers only what it reduced from it."""
    return trace_reduce.find_xplane(
        os.path.join(spec.ROOT, ".bench_runs", run["cell"]["name"], "trace")
    )


def select_loops_s(run, xplane=None):
    """Device seconds, per device, that the selection's loops took
    inside the reduced slice (a loop's whole interval: the ops nested
    in it are the selection's). None of a program that selects nothing
    (no ``select_topk`` on ``step_built``: the parent) and of a run
    that was not traced. A program that does select and a trace in
    which no such loop is found is an ERROR: the selection's lowering
    changed (another loop, a kernel), and this reader has to follow it
    instead of reporting nothing."""
    built = ev.of_kind(run["events"], "step_built")
    trace = run["trace"]
    if not trace or not built or "select_topk" not in built[0]:
        return None
    xplane = xplane or trace_file(run)
    total, devices = 0.0, 0
    if xplane is not None:
        from jax.profiler import ProfileData

        last_step = ev.steps_before(run["events"], run["windows"][-1])
        for plane in ProfileData.from_file(xplane).planes:
            if not trace_reduce.DEVICE_PLANE.match(plane.name):
                continue
            line = trace_reduce._line(plane, trace_reduce.OPS_LINE)
            bounds = trace_reduce._step_slice(plane, last_step, trace["steps"])
            if line is None or bounds is None:
                continue
            lo, hi = bounds
            devices += 1
            for e in line.events:
                start, end = e.start_ns, e.start_ns + e.duration_ns
                if end <= lo or start >= hi or not _SELECT_LOOP.match(e.name):
                    continue
                total += min(end, hi) - max(start, lo)
    if not total:
        raise RuntimeError(
            "the step selects keys (select_topk=%r on step_built) and the "
            "trace %s holds no `while` loop that carries a 4-d int8 array: "
            "the selection is lowered another way now, and "
            "benchmark/layer_metrics/_sel.py has to be taught it"
            % (built[0]["select_topk"], xplane or "(none found)")
        )
    return total / devices / 1e9
