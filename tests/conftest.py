"""Test bootstrap: force JAX onto a virtual 8-device CPU mesh.

Multi-chip hardware is unavailable in CI; all sharding/collective tests run
against ``--xla_force_host_platform_device_count=8`` CPU devices, mirroring
the reference's "fake the cluster in one process" test strategy
(reference tests/in_process_master.py).

``JAX_PLATFORMS=cpu`` in the environment is honoured by JAX itself; the
config pin and ``clear_backends`` below cover a suite started without
it, or after something already initialized a backend in this process.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax
from jax.extend.backend import clear_backends

jax.config.update("jax_platforms", "cpu")
clear_backends()

# Fail fast (not deep inside a sharding test) if the virtual mesh did not
# come up — e.g. a CPU client predating this file already latched XLA_FLAGS.
_n = len(jax.devices())
if _n < 8:
    raise RuntimeError(
        "test bootstrap expected >=8 virtual CPU devices, got %d; a JAX "
        "backend was initialized before conftest could apply XLA_FLAGS" % _n
    )


# ---------------------------------------------------------------------------
# EDL_LOCKTRACE=1: runtime lock-order sanitizer + thread-leak guard
# ---------------------------------------------------------------------------
# The data-plane suites opt into the lockdep-style sanitizer
# (elasticdl_tpu/tools/locktrace.py): every threading.Lock/RLock their
# code creates joins a global acquisition graph and an ABBA inversion
# raises LockOrderError at acquire time instead of deadlocking the run.
# Additionally, EVERY test in a locktraced run asserts that no
# non-daemon thread it started is still alive at teardown — the
# leaked-helper-thread class edlint R4 polices statically.
# scripts/check.sh runs the data-plane suites this way as one gate.

import threading as _conftest_threading

import pytest

_LOCKTRACE_SUITES = {
    "test_input_pipeline",
    "test_ps_overlap",
    "test_async_concurrency",
    "test_elastic_pipeline",
    "test_compile_plane",
    "test_locktrace",
    "test_telemetry",
    "test_tracing",
    "test_wire",
    "test_dense_sharding",
    "test_comm_plane",
    "test_ps_snapshot",
    "test_ps_device_parity",
    "test_tiered_store",
    "test_chaos",
    "test_master_journal",
    "test_serving",
    "test_serving_batcher",
    "test_layout_solver",
}


@pytest.fixture(autouse=True)
def _edl_locktrace_and_thread_leak_guard(request):
    if os.environ.get("EDL_LOCKTRACE") != "1":
        yield
        return
    from elasticdl_tpu.tools import locktrace

    module = request.module.__name__.rsplit(".", 1)[-1]
    traced = module in _LOCKTRACE_SUITES
    if traced:
        locktrace.install()
    before = set(_conftest_threading.enumerate())
    try:
        yield
    finally:
        if traced:
            export_path = os.environ.get("EDL_LOCKTRACE_EXPORT")
            if export_path:
                # the witnessed-edge graph dies with the tracer; dump it
                # first so edlint --lock-coverage can cross-check the
                # static lock-order graph against what the suite saw
                locktrace.export(export_path)
            locktrace.uninstall()
        leaked = [
            t
            for t in _conftest_threading.enumerate()
            if t not in before and t.is_alive() and not t.daemon
        ]
        for t in leaked:
            t.join(timeout=2.0)
        leaked = [t.name for t in leaked if t.is_alive()]
        assert not leaked, (
            "non-daemon thread(s) leaked out of this test: %s "
            "(daemonize, join, or shut the owner down — edlint R4)"
            % ", ".join(leaked)
        )
