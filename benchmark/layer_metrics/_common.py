"""Shared arithmetic of the readers. A reader is a module with LAYER,
UNIT, SOURCE, BETTER, MOVES and ``read(run)``; ``run`` is the dict
``benchmark/run.py`` builds (events, measured windows, the reduced
trace or None, harness clocks, cell, configuration, traffic). A reader
that finds nothing to read returns None and the metric is left out."""

import statistics

# benchmark/ is on sys.path wherever a reader is loaded (run.py, tests)
import events as ev
import flops


def step_ms_p50(run):
    return 1e3 * statistics.median(
        w["seconds"] / w["steps"] for w in run["windows"]
    )


def step_device_ms(run):
    trace = run["trace"]
    if not trace:
        return None
    return 1e3 * trace["busy_s"] / trace["steps"]


def resize_end(run):
    found = ev.of_kind(run["events"], "resize_end")
    return found[0] if found else None


def flash_ops(run, kernel):
    """(name, seconds, calls) per device of the trace's ops that are
    calls of ``kernel``."""
    trace = run["trace"]
    if not trace:
        return []
    return [
        (name, s, trace["op_calls"][name])
        for name, s in trace["op_s"].items()
        if name.startswith(kernel + "_")
    ]


def flash_roofline(run, kernel):
    """Least time by shapes over measured time, in percent, over every
    call of ``kernel`` in the slice. The op's name carries its result
    shape: <kernel>_<dtype>_<batch*heads>_<seq>_<head_dim>_."""
    least = measured = 0.0
    for name, seconds, calls in flash_ops(run, kernel):
        dtype, bh, length, hd = name[len(kernel) + 1 :].strip("_").split("_")
        cost = flops.flash_kernel_cost(
            kernel, int(bh), int(length), int(hd),
            itemsize=4 if dtype == "f32" else 2,
        )  # fmt: skip
        least += calls * flops.roofline(*cost, run["device_kind"])[0]
        measured += seconds
    return 100.0 * least / measured if measured else None
