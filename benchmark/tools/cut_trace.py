#!/usr/bin/env python3
"""Cut a recorded ``.xplane.pb`` down to a few steps, small enough to
keep beside the tests: the device planes' module and op lines inside
the slice, and the host's TraceMe events (no Python frames) that overlap
it, with times shifted so that the slice starts near 0 and event names
cut to NAME_CHARS (an op's name is its whole HLO line).

    python3 benchmark/tools/cut_trace.py <trace dir> <first step> <steps> <out.xplane.pb.gz>

Keeps ``steps`` whole steps from step number ``first step`` on (counted
from the job's first), and the module execution after them, so that
``reduce_trace(out, last_step=steps, n_steps=steps)`` finds the slice.
"""

import gzip
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce  # noqa: E402
import xspace_text  # noqa: E402

NAME_CHARS = 160


def main(argv):
    from jax.profiler import ProfileData

    trace_dir, first, steps, out_path = argv[0], int(argv[1]), int(argv[2]), argv[3]
    data = ProfileData.from_file(trace_reduce.find_xplane(trace_dir))
    planes, slices = [], []
    for plane in data.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        lo, hi = trace_reduce._step_slice(plane, first + steps - 1, steps)
        slices.append((lo, hi))
        cut = xspace_text.Plane(len(planes) + 1, plane.name)
        for line_name, last in (
            (trace_reduce.MODULES_LINE, hi + 1),  # the next execution too
            (trace_reduce.OPS_LINE, hi),
        ):
            cut.line(
                line_name,
                [
                    (e.name[:NAME_CHARS], e.start_ns - lo, e.start_ns + e.duration_ns - lo, {})
                    for e in trace_reduce._line(plane, line_name).events
                    if lo <= e.start_ns < last
                ],
            )  # fmt: skip
        planes.append(cut)
    # host events on the first device's slice clock
    lo, hi = slices[0]
    host = xspace_text.Plane(len(planes) + 1, "/host:CPU")
    threads = {}
    for name, start, end in trace_reduce._host_events(data, lo, hi):
        threads.setdefault("host", []).append(
            (name[:NAME_CHARS], max(start, lo) - lo, min(end, hi) - lo, {})
        )
    for name, events in threads.items():
        host.line(name, events)
    planes.append(host)
    with gzip.open(out_path, "wb") as f:
        f.write(xspace_text.to_xplane_bytes(planes))
    print(out_path, os.path.getsize(out_path), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
