"""From the profiler's trace (an ``.xplane.pb``) to numbers.

The program's hook (``EDL_PROFILE_DIR``) traces from establish to the
worker's exit, compile included, so the reduction cuts the steady slice
itself: ``n_steps`` whole optimizer steps ending with step number
``last_step`` (counted from the job's first step, which the events
give). A step is one execution of the train-step module on a device:
from its start to the start of the next, so the host's gap after a step
belongs to it.

Per device, inside the slice:

- busy: the union of the intervals in which an XLA op ran;
- per-op seconds: each op's SELF time (its interval minus the ops
  nested inside it, so a ``while`` or ``call`` is not counted twice),
  summed under a stable name (the HLO name without its numeric suffix,
  then the result's type and shape);
- collective seconds, and the exposed part of them: the part during
  which no other op ran on that device;
- idle gaps: the complement of busy, each gap named by the host
  TraceMe/annotation that overlaps it most (the program's
  ``TraceAnnotation``s and the runtime's own are on the same clock).

Device numbers are averaged over the devices; the idle gaps are those of
the first device. ``jax.profiler.ProfileData`` reads the file; no
backend is initialised by it.
"""

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)"
)
# python-function events of the profiler's Python tracer: they nest the
# whole call stack around every gap, so they name nothing
PYTHON_FRAME = "$"
TINY_GAP_NS = 400


def find_xplane(trace_dir):
    """The one ``.xplane.pb`` under ``trace_dir``, or None."""
    found = sorted(
        glob.glob(
            os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
        )
    )
    return found[-1] if found else None


def stable_name(name, long_name=""):
    """``fusion.123`` + ``%fusion.123 = bf16[8,2047]{1,0} fusion(...)``
    -> ``fusion_bf16_8_2047_``: the same op keeps its name when the
    compiler renumbers. The TPU's trace puts the whole HLO line in the
    event's name; other backends give the bare name and the text in a
    stat."""
    if " = " in name:
        long_name = name
        name = name.split(" = ", 1)[0]
    base = re.sub(r"[.\d]+$", "", name.lstrip("%"))
    m = re.search(r"=\s*\(?([a-z]+\d*)\[([\d,]*)\]", long_name or "")
    if not m:
        return base
    dims = m.group(2).replace(",", "_")
    return "%s_%s_%s_" % (base, m.group(1), dims) if dims else "%s_%s_" % (
        base, m.group(1)
    )


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def _islands(starts, ends):
    """The union of [start, end) intervals as sorted disjoint (start,
    end) arrays."""
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    # a new island starts where a start lies beyond every earlier end
    new = np.concatenate([[True], s[1:] > e[:-1]])
    return s[new], np.concatenate([e[:-1][new[1:]], e[-1:]])


def _union_seconds(starts, ends):
    """Length of the union of [start, end) intervals, in ns."""
    if len(starts) == 0:
        return 0.0
    island_start, island_end = _islands(starts, ends)
    return float(np.sum(island_end - island_start))


def _self_times(starts, ends):
    """Each interval's length minus the intervals nested directly in
    it. Events of one line nest properly (an op inside a while inside a
    module) or do not overlap."""
    order = np.lexsort((-(ends - starts), starts))
    self_ns = (ends - starts).astype(np.float64)
    stack = []
    for i in order:
        while stack and ends[stack[-1]] <= starts[i]:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= ends[i] - starts[i]
        stack.append(i)
    return self_ns


def _step_slice(plane, last_step, n_steps):
    """(lo, hi) ns of steps last_step-n_steps+1 .. last_step on this
    device: executions of the module that took most device time."""
    line = _line(plane, MODULES_LINE)
    if line is None:
        return None
    by_module = {}
    for e in line.events:
        by_module.setdefault(re.sub(r"\(.*$", "", e.name), []).append(
            (e.start_ns, e.duration_ns)
        )
    if not by_module:
        return None
    runs = sorted(max(by_module.values(), key=lambda r: sum(d for _, d in r)))
    # execution i (0-based) is step i+1; the slice needs the start of
    # the execution after its last step
    if last_step < n_steps or len(runs) <= last_step:
        return None
    return runs[last_step - n_steps][0], runs[last_step][0]


def _device_ops(plane, lo, hi):
    """names, starts, ends (clipped to the slice) of this device's ops."""
    names, starts, ends = [], [], []
    for e in _line(plane, OPS_LINE).events:
        s, d = e.start_ns, e.duration_ns
        if s + d <= lo or s >= hi:
            continue
        name, long_name = e.name, ""
        if " = " not in name:
            for key, value in e.stats:
                if key in ("long_name", "hlo_text"):
                    long_name = value
                    break
        names.append(stable_name(name, long_name))
        starts.append(max(s, lo))
        ends.append(min(s + d, hi))
    return np.array(names), np.array(starts, float), np.array(ends, float)


def _host_events(data, lo, hi):
    """(name, start, end) of host TraceMe events overlapping [lo, hi)."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                s = e.start_ns
                if s >= hi:
                    continue
                d = e.duration_ns
                if s + d <= lo or d <= 0:
                    continue
                name = e.name
                if name.startswith(PYTHON_FRAME):
                    continue
                out.append((name, s, s + d))
    return out


def _name_gaps(gap_starts, gap_ends, host):
    """Seconds of idle by the host event overlapping each gap most (the
    shorter event on a tie: the more specific one)."""
    totals, tiny_n, tiny_ns = {}, 0, 0.0
    for gs, ge in zip(gap_starts, gap_ends):
        if ge - gs <= TINY_GAP_NS:
            tiny_n, tiny_ns = tiny_n + 1, tiny_ns + (ge - gs)
            continue
        best, best_key = "unattributed", (0.0, 0.0)
        for name, s, e in host:
            overlap = min(e, ge) - max(s, gs)
            if overlap > 0:
                key = (overlap, -(e - s))
                if key > best_key:
                    best, best_key = name, key
        totals[best] = totals.get(best, 0.0) + (ge - gs)
    if tiny_n:
        totals[
            "%d_gaps_of_at_most_%.1f_us" % (tiny_n, TINY_GAP_NS / 1e3)
        ] = tiny_ns
    return totals


def _sanitize(name):
    return re.sub(r"[^A-Za-z0-9_.:/-]", "_", name)[:64]


def reduce_trace(xplane_path, last_step, n_steps=16):
    """The steady slice's numbers, or None when the trace does not hold
    the slice (no device plane, too few step executions)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices = sorted(
        (int(DEVICE_PLANE.match(p.name).group(1)), p)
        for p in data.planes
        if DEVICE_PLANE.match(p.name) and _line(p, OPS_LINE) is not None
    )
    if not devices:
        return None
    slice_ns, busy_ns, coll_ns, exposed_ns = [], [], [], []
    op_ns, op_calls, first_gaps = {}, {}, None
    for _, plane in devices:
        bounds = _step_slice(plane, last_step, n_steps)
        if bounds is None:
            return None
        lo, hi = bounds
        names, starts, ends = _device_ops(plane, lo, hi)
        slice_ns.append(hi - lo)
        busy_ns.append(_union_seconds(starts, ends))
        for name, ns in zip(names, _self_times(starts, ends)):
            op_ns[name] = op_ns.get(name, 0.0) + ns
            op_calls[name] = op_calls.get(name, 0) + 1
        is_coll = np.array(
            [bool(COLLECTIVE.match(n)) for n in names], dtype=bool
        )
        if is_coll.any():
            cs, ce = starts[is_coll], ends[is_coll]
            coll = _union_seconds(cs, ce)
            both = _union_seconds(starts, ends)
            others = _union_seconds(starts[~is_coll], ends[~is_coll])
            coll_ns.append(coll)
            # |C \ O| = |C u O| - |O|
            exposed_ns.append(both - others)
        else:
            coll_ns.append(0.0)
            exposed_ns.append(0.0)
        if first_gaps is None:
            isl_s, isl_e = _islands(starts, ends)
            gap_s = np.concatenate([[lo], isl_e])
            gap_e = np.concatenate([isl_s, [hi]])
            keep = gap_e > gap_s
            first_gaps = (lo, hi, gap_s[keep], gap_e[keep])
    lo, hi, gap_s, gap_e = first_gaps
    gaps = _name_gaps(gap_s, gap_e, _host_events(data, lo, hi))
    n = len(devices)

    def top(totals, scale):
        ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
        return [[_sanitize(k), v * scale] for k, v in ranked]

    return {
        "devices": n,
        "steps": n_steps,
        "window_s": float(np.mean(slice_ns)) / 1e9,
        "busy_s": float(np.mean(busy_ns)) / 1e9,
        "collective_s": float(np.mean(coll_ns)) / 1e9,
        "collective_exposed_s": float(np.mean(exposed_ns)) / 1e9,
        # seconds, and calls, per device
        "op_s": {k: v / n / 1e9 for k, v in op_ns.items()},
        "op_calls": {k: v / n for k, v in op_calls.items()},
        "device_ops": top(op_ns, 1.0 / n / 1e9),
        "idle_gaps": top(gaps, 1e-9),
    }
