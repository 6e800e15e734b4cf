"""Compile-plane fast path: executable reuse + speculative AOT compiles.

Elasticity in this framework means mesh re-formation: every
``ElasticPlane.establish()`` after a membership change used to retrace
and recompile the pjit train step from scratch, so the resize pause was
dominated by XLA compile time rather than by state movement — the
elastic-native cost ElasWave (arxiv 2510.00606) attacks with plan reuse
and the pjit scaling paper (arxiv 2204.06514) amortizes with
ahead-of-time lowering. This module is that amortization layer, shared
by the elastic trainer and the tests:

- :class:`ExecutableCache` — jitted step callables (plus their AOT
  ``Compiled`` executables) keyed by (backend epoch, mesh signature,
  step-config signature). Re-establishing at a previously-seen world
  size hands back the SAME jit callable, so jax's own aval cache
  dispatches without retracing or recompiling. Entries are invalidated
  wholesale when the backend epoch advances (``leave_world`` drops every
  backend, so device handles inside old executables are dead).

- :class:`SpeculativeCompiler` — a cancellable daemon worker that AOT
  ``.lower().compile()``-s the train step for LIKELY NEXT world sizes
  (current ±1, membership-service hints) during steady-state training,
  inserting the results into the cache so a later establish at that size
  pays state re-placement only. Compiles run strictly outside the lock
  (edlint R5); the thread is daemonized AND joined on shutdown (R4); a
  hint for a size that never materializes is simply dropped.

- :func:`enable_persistent_cache` — turns on jax's persistent
  compilation cache (under ``JAX_COMPILATION_CACHE_DIR`` when that is
  set, else the checkout's ``.jax_cache``) so a FRESH PROCESS
  (relaunched pod, promoted standby) skips the XLA compile too: the
  in-memory cache cannot outlive the process, but the HLO-keyed disk
  cache does.

Scope note: in-memory reuse pays off whenever the backend survives the
resize (single-process elastic planes, the CPU test meshes built
over device subsets). A real multi-host re-form tears the backend down
(parallel/distributed.py), where the speculative compiles still warm the
persistent disk cache. docs/compile_plane.md has the full policy.
"""

import os
import threading
import time

from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.utils import profiling


# Where compiled programs are kept when nobody says otherwise: one fixed
# directory inside the checkout. The directory is part of jax's cache
# key, so a path that moves between runs never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def _cpu_platform_selected():
    """Is this process pinned to the CPU backend? Answered from env and
    jax config ONLY — probing the backend itself (jax.default_backend)
    would initialize it, which the elastic worker must not do before
    its world forms."""
    import jax

    if os.environ.get("EDL_DIST_PLATFORM") == "cpu":
        return True
    selected = jax.config.jax_platforms or ""
    return selected.split(",")[0].strip().lower() == "cpu"


def enable_persistent_cache(probe_backend=False):
    """Turn on jax's persistent compilation cache. One rule for where:
    if ``JAX_COMPILATION_CACHE_DIR`` is set, jax already keeps its cache
    there and nothing is set here; otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`. Idempotent. Survives ``clear_backends``
    (it is jax config, not backend state), so one call at process start
    covers every re-formed world.

    CPU processes skip the cache unless ``EDL_COMPILE_CACHE_CPU=1``
    forces it: on this toolchain, EXECUTING a cache-reloaded executable
    with donated buffers on the CPU backend corrupts the native heap
    (measured: the local allreduce train resumed against a warm cache
    aborts in glibc inside the first train_step; the same drive with a
    cold cache, or without donation, is clean). The accelerator path is
    the production target and reloads cleanly. (A CPU process started
    with ``JAX_COMPILATION_CACHE_DIR`` set caches all the same: that is
    jax obeying its own variable, not this function.)

    ``probe_backend=True`` additionally asks the live backend when the
    platform env/config is silent — catching an accelerator-less box
    jax lands on CPU implicitly. Callers that must not initialize a
    backend yet (the elastic worker before its world forms) keep the
    default False and are covered by the env answer
    (``EDL_DIST_PLATFORM=cpu`` is the documented CPU bring-up there).
    """
    import jax

    on_cpu = _cpu_platform_selected()
    if not on_cpu and probe_backend:
        try:
            on_cpu = jax.default_backend() == "cpu"
        except Exception:
            logger.debug(
                "backend probe for the compile cache failed; trusting "
                "the platform env",
                exc_info=True,
            )
    if on_cpu and not os.environ.get("EDL_COMPILE_CACHE_CPU"):
        logger.info(
            "persistent compile cache disabled on the CPU backend "
            "(cache-reloaded donated executables crash this toolchain; "
            "set EDL_COMPILE_CACHE_CPU=1 to force)"
        )
        return False
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return True
    if jax.config.jax_compilation_cache_dir != DEFAULT_CACHE_DIR:
        os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
        # jax's min-compile-time threshold (~1s) is kept DELIBERATELY:
        # it admits exactly the executables worth amortizing (the train
        # steps) while keeping the myriad tiny placement/broadcast
        # programs out — on this toolchain, reloading certain tiny
        # cached CPU executables crashes natively (measured:
        # resume-from-checkpoint with a zero-threshold warm cache
        # segfaults in deserialization; with the default threshold the
        # same drive is clean, and the step compiles still hit)
        logger.info(
            "persistent compilation cache -> %s", DEFAULT_CACHE_DIR
        )
    return True


class CompileStats:
    """Per-owner compile-plane counters (a private
    :class:`profiling.Counters`), mirrored into the process-wide
    profiling registry so traces and tests see the same numbers
    without sharing the per-trainer tallies."""

    def __init__(self, prefix="compile_plane"):
        self._prefix = prefix
        self._local = profiling.Counters()

    def inc(self, name, value=1):
        self._local.inc(name, value)
        profiling.counters.inc("%s/%s" % (self._prefix, name), value)

    def add_time(self, name, seconds):
        self.inc(name + "_s", float(seconds))

    def get(self, name):
        return self._local.get(name)

    def snapshot(self):
        return self._local.snapshot()


def mesh_signature(mesh):
    """Hashable identity of a mesh placement: axis layout plus the flat
    device identity (id + process + platform). Two establishes at the
    same world size over the SAME live backend produce equal signatures;
    any difference in devices or layout misses the cache."""
    devices = tuple(
        (d.id, d.process_index, d.platform) for d in mesh.devices.flat
    )
    sizes = tuple(int(mesh.shape[name]) for name in mesh.axis_names)
    return (tuple(mesh.axis_names), sizes, devices)


def spec_signature(spec_tree):
    """Stable string form of a PartitionSpec pytree (or None): state
    specs are closed over by the step builder, so two step fns with
    different specs must never share a cache entry."""
    if spec_tree is None:
        return "None"
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(
        spec_tree, is_leaf=lambda x: x is None
    )
    return "%s|%s" % (treedef, [str(leaf) for leaf in leaves])


def args_signature(args):
    """(shape, dtype) tuple signature of flattened call args — the key
    an AOT-compiled executable is valid for."""
    import jax
    import numpy as np

    leaves = jax.tree_util.tree_leaves(args)
    return tuple(
        (tuple(leaf.shape), np.dtype(leaf.dtype).str) for leaf in leaves
    )


class _Entry:
    __slots__ = (
        "step_fn",
        "aot",
        "dispatch_memo",
        "backend_epoch",
        "speculative",
    )

    def __init__(self, step_fn, backend_epoch, speculative=False):
        self.step_fn = step_fn
        self.aot = {}  # args_signature -> jax Compiled executable
        # batch-signature -> chosen callable (the hot loop must not
        # re-walk the whole TrainState signature every step)
        self.dispatch_memo = {}
        self.backend_epoch = backend_epoch
        self.speculative = speculative


class ExecutableCache:
    """LRU of compiled elastic train steps.

    Keys carry the backend epoch (parallel/distributed.py bumps it every
    time the backends are dropped): entries minted against a dead
    backend hold invalid device handles and are evicted on sight rather
    than reused. Lookups/inserts hold the lock only for dict bookkeeping
    — builders and compiles run strictly outside it (edlint R5).
    """

    def __init__(self, max_entries=8, stats=None):
        self._lock = threading.Lock()
        self._entries = {}
        self._order = []  # LRU, most recent last
        self._max = max(1, int(max_entries))
        self.stats = stats or CompileStats()

    def _current_epoch(self):
        from elasticdl_tpu.parallel import distributed

        return distributed.backend_epoch()

    def get(self, key, count=True):
        epoch = self._current_epoch()
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None and entry.backend_epoch != epoch:
                # stale backend: the executable's devices are gone
                del self._entries[key]
                self._order.remove(key)
                entry = None
                self.stats.inc("stale_evictions")
            if entry is not None:
                self._order.remove(key)
                self._order.append(key)
        if count:
            self.stats.inc("hits" if entry is not None else "misses")
            if entry is not None and entry.speculative:
                entry.speculative = False  # first hit claims the win
                self.stats.inc("speculative_hits")
                # a background AOT compile just saved a resize pause —
                # worth a fleet-visible event (docs/observability.md)
                profiling.events.emit(
                    "speculative_compile_hit", key=str(key)
                )
        return entry

    def put(self, key, step_fn, speculative=False):
        entry = _Entry(step_fn, self._current_epoch(), speculative)
        with self._lock:
            if key in self._entries:
                self._order.remove(key)
            self._entries[key] = entry
            self._order.append(key)
            while len(self._order) > self._max:
                evicted = self._order.pop(0)
                del self._entries[evicted]
                self.stats.inc("lru_evictions")
        return entry

    def size(self):
        with self._lock:
            return len(self._entries)

    def clear(self):
        with self._lock:
            self._entries.clear()
            self._order[:] = []


def aot_compile(entry, abstract_args, stats=None):
    """AOT ``.lower().compile()`` of ``entry.step_fn`` for one argument
    signature; the Compiled executable lands on the entry so dispatch
    can skip tracing entirely. Returns the executable (or the existing
    one). ``abstract_args`` may mix concrete arrays and
    ShapeDtypeStructs — lowering never executes either."""
    sig = args_signature(abstract_args)
    compiled = entry.aot.get(sig)
    if compiled is not None:
        return compiled
    t0 = time.perf_counter()
    with profiling.span("compile_plane/aot_compile"):
        compiled = entry.step_fn.lower(*abstract_args).compile()
    entry.aot[sig] = compiled
    if stats is not None:
        stats.inc("aot_compiles")
        stats.add_time("aot_compile", time.perf_counter() - t0)
    return compiled


class SpeculativeCompiler:
    """Background AOT compiler for likely next world sizes.

    ``compile_fn(size)`` does the whole job for one hinted size (build
    mesh + step fn + AOT compile + cache insert) and is provided by the
    owner (the elastic trainer); it runs on a
    DAEMON thread, one size at a time, strictly outside this class's
    lock. ``hint(sizes)`` is non-blocking and deduplicates against both
    the pending queue and everything already attempted this generation.

    Lifecycle discipline (edlint R4, EDL_LOCKTRACE): the thread is
    daemonized AND ``shutdown()`` joins it; shutdown is cooperative — a
    size in flight finishes its (uninterruptible C++) compile and then
    observes the cancel event, while every still-pending size is
    DROPPED, never blocking the caller. The owner shuts the compiler
    down before tearing a world down and starts a fresh one after the
    next establish.
    """

    def __init__(self, compile_fn, stats=None, name="edl-spec-compile"):
        self._compile_fn = compile_fn
        self._name = name
        self.stats = stats or CompileStats()
        self._lock = threading.Lock()
        self._pending = []
        self._seen = set()
        self._cancel = threading.Event()
        self._wake = threading.Event()
        self._thread = None

    def hint(self, candidates):
        """Enqueue compile candidates (non-blocking, deduplicated).

        A candidate is either a bare world size (devices, int) or a
        ``(world_size, layout)`` tuple — the layout half is opaque
        hashable data the owner's ``compile_fn`` understands (the
        elastic trainer passes the solver's ``mesh_axes`` items, so a
        PLANNED layout change pre-compiles alongside planned size
        changes). Both forms dedup against everything already hinted
        this generation."""
        fresh = []
        with self._lock:
            if self._cancel.is_set():
                return
            for cand in candidates:
                if isinstance(cand, tuple):
                    key, size = tuple(cand), int(cand[0])
                else:
                    key = size = int(cand)
                if size > 0 and key not in self._seen:
                    self._seen.add(key)
                    self._pending.append(key)
                    fresh.append(key)
        if fresh:
            self.stats.inc("hinted", len(fresh))
            self._wake.set()

    def start(self):
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._run, name=self._name, daemon=True
        )
        self._thread.start()

    def _pop(self):
        with self._lock:
            if self._pending:
                return self._pending.pop(0)
            self._wake.clear()
            return None

    def _run(self):
        while not self._cancel.is_set():
            size = self._pop()
            if size is None:
                self._wake.wait(timeout=0.2)
                continue
            if self._cancel.is_set():
                break
            try:
                t0 = time.perf_counter()
                with profiling.span("compile_plane/speculative"):
                    built = self._compile_fn(size)
                if built:
                    self.stats.inc("speculative_builds")
                    self.stats.add_time(
                        "speculative_build", time.perf_counter() - t0
                    )
                else:
                    # size can never materialize on this backend (not
                    # enough devices / layout misfit): drop it
                    self.stats.inc("dropped")
            except Exception:
                self.stats.inc("failed")
                logger.warning(
                    "speculative compile for candidate %s failed",
                    size,
                    exc_info=True,
                )

    def pending_count(self):
        with self._lock:
            return len(self._pending)

    def idle(self):
        """True when nothing is pending or in flight (test sync)."""
        with self._lock:
            busy = bool(self._pending) or self._wake.is_set()
        return not busy

    def shutdown(self, timeout=5.0):
        """Cancel pending work and join the worker.

        The thread is a daemon, so a compile wedged in C++ past the join
        timeout is abandoned safely (it can no longer insert: hint() and
        the run loop both observe the cancel event, and a stale-epoch
        insert is evicted by the cache anyway). Pending sizes are
        counted as dropped."""
        self._cancel.set()
        self._wake.set()
        with self._lock:
            dropped, self._pending = len(self._pending), []
        if dropped:
            self.stats.inc("dropped", dropped)
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=timeout)
            if t.is_alive():
                logger.warning(
                    "speculative compiler still in a C++ compile at "
                    "shutdown; abandoned (daemon)"
                )
        self._thread = None
