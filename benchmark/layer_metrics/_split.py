"""Shared arithmetic of the readers of the device step's classes: the
compiled step's ops summed as forward, backward, recomputed forward,
optimizer or mixed, by the program's own names.

The TPU's trace names an op by its HLO line and carries no scope. A
traced worker of a program that has them writes, beside its trace, a
map from the compiled step's instruction names to the class each holds
(``<trace dir>/edl_step_ops.json``: ``{"module": name, "ops":
{"fusion.1916": "bwd", "fusion.7": "bwd+optimizer", ...}}``) and says
so on ``step_built`` (``step_ops_named``). The walk here is the join:
per device, inside the reduced slice, the ops that lie inside an
execution of THAT module (instruction names are unique within a module
and not across modules, and the slice also holds the tiny per-step
programs), each op's self time summed under its instruction's class.
An op whose class names more than one (``+``) is MIXED and is never
divided.

A program that wrote no map (no ``step_ops_named`` on ``step_built``:
the parent commit, on which the driver runs the readers too) named
nothing, which is true of it: every class reads 0.0 there, not None.
An untraced run gives None. Where the program says it wrote a map and
the map, the trace or the module's executions are not found, the walk
RAISES instead of reporting nothing."""

import bisect
import functools
import json
import os

import numpy as np

import events as ev
import spec
import trace_reduce
from _sel import trace_file

MAP_NAME = "edl_step_ops.json"
# the classes with a metric of their own; `reduce` (the psums) and ops
# the map does not name are what step_split_share leaves out
CLASSES = ("fwd", "bwd", "remat", "optimizer")
MIXED = "mixed"
SPLIT = CLASSES + (MIXED,)


def map_file(run):
    """The map the run's worker wrote beside its trace."""
    return os.path.join(
        spec.ROOT, ".bench_runs", run["cell"]["name"], "trace", MAP_NAME
    )


@functools.lru_cache(maxsize=2)
def _walk(xplane, map_path, last_step, n_steps):
    """{bucket: seconds per device} over the slice; read once a process,
    not once a reader."""
    from jax.profiler import ProfileData

    with open(map_path, encoding="utf-8") as f:
        ops_map = json.load(f)
    module, classes = ops_map["module"], ops_map["ops"]
    total, devices = {}, 0
    for plane in ProfileData.from_file(xplane).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        line = trace_reduce._line(plane, trace_reduce.OPS_LINE)
        bounds = trace_reduce._step_slice(plane, last_step, n_steps)
        if line is None or bounds is None:
            continue
        lo, hi = bounds
        runs = sorted(
            (e.start_ns, e.start_ns + e.duration_ns)
            for e in trace_reduce._line(plane, trace_reduce.MODULES_LINE).events
            if lo <= e.start_ns < hi and e.name.partition("(")[0] == module
        )
        if not runs:
            continue
        devices += 1
        run_starts = [s for s, _ in runs]
        names, starts, ends = [], [], []
        for e in line.events:
            start = e.start_ns
            if not lo <= start < hi:
                continue
            i = bisect.bisect_right(run_starts, start) - 1
            if i >= 0 and start < runs[i][1]:
                names.append(e.name.split(" = ", 1)[0].strip().lstrip("%"))
                starts.append(start)
                ends.append(start + e.duration_ns)
        own = trace_reduce._self_times(
            np.array(starts, float), np.array(ends, float)
        )
        for name, ns in zip(names, own):
            held = classes.get(name, "")
            bucket = MIXED if "+" in held else held
            total[bucket] = total.get(bucket, 0.0) + ns
    if not devices:
        raise RuntimeError(
            "the program wrote a map of module %r (%s) and the trace %s "
            "holds no execution of that module inside the reduced slice"
            % (module, map_path, xplane)
        )
    return {bucket: ns / devices / 1e9 for bucket, ns in total.items()}


def split_s(run, xplane=None, map_path=None):
    """Device seconds, per device, of the slice's ops of the train-step
    module by class: ``{"fwd", "bwd", "remat", "optimizer", "mixed":
    seconds}``. None of an untraced run; zeros of a program that wrote
    no map."""
    trace = run["trace"]
    if not trace:
        return None
    built = ev.of_kind(run["events"], "step_built")
    if not built or "step_ops_named" not in built[0]:
        return dict.fromkeys(SPLIT, 0.0)
    xplane = xplane or trace_file(run)
    map_path = map_path or map_file(run)
    if xplane is None or not os.path.exists(map_path):
        raise RuntimeError(
            "the program says it wrote the step's ops by class "
            "(step_ops_named=%r on step_built) and %s is not there to read"
            % (
                built[0]["step_ops_named"],
                "the trace" if xplane is None else map_path,
            )
        )
    walked = _walk(
        xplane,
        map_path,
        ev.steps_before(run["events"], run["windows"][-1]),
        trace["steps"],
    )
    return {bucket: walked.get(bucket, 0.0) for bucket in SPLIT}


def ms_per_step(run, bucket):
    split = split_s(run)
    if split is None:
        return None
    return 1e3 * split[bucket] / run["trace"]["steps"]
