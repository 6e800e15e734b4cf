"""Shared arithmetic of the reader of the Kimi-Delta-Attention
recurrence (elasticdl_tpu/ops/kda.py): the device time of the loops in
which it runs. None where the program has no such layer or fact (the
parent commit, on which the driver runs the readers too); where the
program says it scans and no loop is found, it raises."""

import re

import numpy as np

import _sel
import events as ev
import trace_reduce


def state_loop(built, batch):
    """The recurrence goes through the sequence as ``lax.scan`` loops,
    forward, recomputed and backward, whose carried tuple holds the
    state handed from chunk to chunk or its cotangent, f32 (batch,
    heads, d_k, d_v) with ``d_k = d_v = kda_head_dim``: the only loops
    of the step that carry an array of that shape. (A Mamba-2 scan's
    loops, ``_ssd.py``, carry (batch, ssm_heads, ssm_head_dim,
    ssm_state): a model with both is told apart by the shapes, and one
    whose two shapes were equal would need another mark.) The trace
    names an op by its whole HLO line and carries no scope, so
    ``edl/kda`` cannot be read there."""
    shape = (
        batch, built["kda_heads"], built["kda_head_dim"], built["kda_head_dim"],
    )  # fmt: skip
    return re.compile(
        r"^%?while[.\d]* = \(.*?\bf32\[" + ",".join(map(str, shape)) + r"\]"
    )


def state_loops_s(run, xplane=None):
    """Device seconds, per device, that the recurrence's loops took
    inside the reduced slice (a loop's whole interval: the ops nested
    in it are the recurrence's). The loops over a group's chunks are
    nested in the loops over the groups and carry the same state, so
    the intervals are united, not added up: what runs once a group in
    front of its chunks (the decay matrices, the triangular solve and its
    right-hand sides) lies inside a group's loop and is in it. None of a
    program that has no KDA layer (no ``kda_layers`` on ``step_built``:
    the parent) and of a run that was not traced. A program that does
    scan and a trace in which no such loop is found is an ERROR: the
    recurrence is lowered another way (a kernel, an unrolled loop), and
    this reader has to follow it instead of reporting nothing."""
    built = ev.of_kind(run["events"], "step_built")
    trace = run["trace"]
    if not trace or not built or not built[0].get("kda_layers"):
        return None
    built = built[0]
    xplane = xplane or _sel.trace_file(run)
    total, devices = 0.0, 0
    if xplane is not None:
        from jax.profiler import ProfileData

        loop = state_loop(
            built, run["traffic"]["minibatch_size"] // run["cell"]["chips"]
        )
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
            found = [
                (max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi))
                for e in line.events
                if e.start_ns < hi
                and e.start_ns + e.duration_ns > lo
                and loop.match(e.name)
            ]
            if found:
                starts, ends = map(np.asarray, zip(*found))
                total += trace_reduce._union_seconds(starts, ends)
    if not total:
        raise RuntimeError(
            "the step holds %d KDA layers (step_built) and the trace %s "
            "holds no `while` loop that carries their f32 state: the "
            "recurrence is lowered another way now, and "
            "benchmark/layer_metrics/_kda.py has to be taught it"
            % (built["kda_layers"], xplane or "(none found)")
        )
    return total / devices / 1e9
