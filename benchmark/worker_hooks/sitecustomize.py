"""Loaded by every Python process the benchmark starts (this directory
is on their PYTHONPATH): lets the harness ask the job's worker for its
device memory statistics, once, after the measured window.

Why it exists: the result line the driver reads has to carry
``device.memory_peak_bytes``, the peak of the process that trained, and
the driver holds a new cell to a floor by it. Only the process that owns
the chip can read that number, the program reports none (PERF.md, Open
questions), and this PR may not edit the program. When the worker emits
``memory_stats()`` itself, this directory goes. The installation has no
``sitecustomize`` of its own, so nothing is shadowed.

On SIGUSR1 a process whose JAX backend is up writes ``memory_stats()``
of its local devices to ``EDL_BENCH_MEMORY_STATS_PATH``; any other
process ignores the signal. Nothing runs until the signal arrives.
"""

import os
import signal
import sys

_PATH = os.environ.get("EDL_BENCH_MEMORY_STATS_PATH")


def _dump_memory_stats(signum, frame):
    jax = sys.modules.get("jax")
    if jax is None:
        return
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return
    import json

    stats = [
        {"id": d.id, "kind": d.device_kind, "stats": d.memory_stats() or {}}
        for d in jax.local_devices()
    ]
    tmp = "%s.%d.tmp" % (_PATH, os.getpid())
    with open(tmp, "w") as f:
        json.dump(stats, f)
    os.replace(tmp, _PATH)


if _PATH:
    signal.signal(signal.SIGUSR1, _dump_memory_stats)
    # SIGUSR1 kills a process that has no handler for it: the harness
    # signals only a pid that has left this marker
    with open("%s.armed.%d" % (_PATH, os.getpid()), "w"):
        pass
