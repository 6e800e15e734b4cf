"""Shared arithmetic of the readers of the state-space scan
(elasticdl_tpu/ops/ssd.py): the device time of the loops in which the
scan runs. None where the program has no such layer or fact (the parent
commit, on which the driver runs the readers too); where the program
says it scans and no loop is found, it raises."""

import re

import _sel
import events as ev
import trace_reduce


def _scan_loop(built, batch):
    """The scan goes through the sequence as ``lax.scan`` loops, forward,
    recomputed and backward, whose carried tuple holds the state handed
    from chunk to chunk or its cotangent, f32 (batch, heads, head_dim,
    state): the only loops of the step that carry an array of that
    shape. The trace names an op by its whole HLO line and carries no
    scope, so ``edl/ssd`` cannot be read there; the loops are how the
    trace tells the scan's ops from the step's others."""
    shape = (batch, built["ssm_heads"], built["ssm_head_dim"], built["ssm_state"])
    return re.compile(
        r"^%?while[.\d]* = \(.*?\bf32\[" + ",".join(map(str, shape)) + r"\]"
    )


def scan_loops_s(run, xplane=None):
    """Device seconds, per device, that the scan's loops took inside the
    reduced slice (a loop's whole interval: the ops nested in it are the
    scan's). None of a program that has no state-space layer (no
    ``mamba_layers`` on ``step_built``: the parent) and of a run that
    was not traced. A program that does scan and a trace in which no
    such loop is found is an ERROR: the scan is lowered another way (a
    kernel, an unrolled loop), and this reader has to follow it instead
    of reporting nothing."""
    built = ev.of_kind(run["events"], "step_built")
    trace = run["trace"]
    if not trace or not built or not built[0].get("mamba_layers"):
        return None
    built = built[0]
    xplane = xplane or _sel.trace_file(run)
    total, devices = 0.0, 0
    if xplane is not None:
        from jax.profiler import ProfileData

        loop = _scan_loop(
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
            for e in line.events:
                start, end = e.start_ns, e.start_ns + e.duration_ns
                if end <= lo or start >= hi or not loop.match(e.name):
                    continue
                total += min(end, hi) - max(start, lo)
    if not total:
        raise RuntimeError(
            "the step holds %d state-space layers (step_built) and the "
            "trace %s holds no `while` loop that carries their f32 state: "
            "the scan is lowered another way now, and "
            "benchmark/layer_metrics/_ssd.py has to be taught it"
            % (built["mamba_layers"], xplane or "(none found)")
        )
    return total / devices / 1e9
