"""Compile-plane fast path (parallel/compile_plane.py + the elastic
trainer's establish/step integration).

Everything runs single-process on the virtual 8-device CPU mesh,
driving the SAME trainer surfaces the elastic worker uses — the mesh is
swapped in-process (``_establish_at`` below) so the backend survives
resizes and the in-memory executable reuse is observable. The
trace-counting tests use a loss_fn that bumps a Python counter: the
counter only advances while jax is TRACING, so "no retrace" is asserted
directly rather than inferred from timings.

Run under ``EDL_LOCKTRACE=1`` (scripts/check.sh) these tests also
assert, via the conftest guard, that no non-daemon thread leaks out of
the speculative compiler / H2D feeder lifecycles.
"""

import threading
import time

import numpy as np
import pytest

import jax
import optax
from jax.sharding import Mesh

from elasticdl_tpu.parallel import compile_plane, distributed
from elasticdl_tpu.parallel import elastic as elastic_mod
from elasticdl_tpu.parallel.compile_plane import (
    ExecutableCache,
    SpeculativeCompiler,
    mesh_signature,
)
from elasticdl_tpu.parallel.distributed import WorldSpec
from elasticdl_tpu.parallel.elastic import ElasticDPTrainer
from model_zoo.transformer_lm import transformer_lm as zoo

VOCAB = 64
LENGTH = 8
BATCH = 16
MODEL_KW = dict(
    vocab_size=VOCAB,
    num_layers=2,
    num_heads=2,
    head_dim=8,
    embed_dim=16,
    mlp_dim=32,
    use_flash=False,
)


def _batch(seed=0, batch=BATCH):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, VOCAB, size=(batch, LENGTH)).astype(np.int32)
    return {"tokens": ids}, ids


def _make_trainer(loss_fn=None, minibatch=BATCH):
    model = zoo.custom_model(**MODEL_KW)
    trainer = ElasticDPTrainer(
        model, loss_fn or zoo.loss, optax.sgd(0.05)
    )
    trainer.default_minibatch_size = minibatch
    trainer._spec = WorldSpec(
        coordinator="", num_processes=1, process_id=0, epoch=0
    )
    trainer._host_ts = trainer._host_init_ts(_batch())
    return trainer


def _establish_at(trainer, k):
    """In-process resize: the establish phases minus the world RPC
    (re-form the mesh over the first k devices, re-broadcast the state,
    acquire the step fn), so a journey can revisit sizes on one live
    backend."""
    if trainer._ts is not None:
        trainer._host_ts = trainer.snapshot()
    trainer._mesh = Mesh(np.asarray(jax.devices()[:k]), ("data",))
    trainer._ts = elastic_mod.broadcast_from_device0(
        trainer._mesh, trainer._host_ts
    )
    trainer._keep_checked(trainer._ts)
    trainer._spec_example = _batch()
    return trainer._acquire_step_fn()


def _counting_loss():
    """A loss whose Python body runs only while jax traces."""
    calls = {"n": 0}

    def loss(output, labels):
        calls["n"] += 1
        return zoo.loss(output, labels)

    return loss, calls


# ---------------------------------------------------------------------------
# executable cache: reuse without retracing, correct misses
# ---------------------------------------------------------------------------


def test_reestablish_at_seen_size_reuses_executable_without_retrace():
    loss_fn, calls = _counting_loss()
    t = _make_trainer(loss_fn)
    features, labels = _batch(1)

    assert _establish_at(t, 8) is False  # first visit: miss
    t.train_step(features, labels, BATCH, sync=True)
    traces_8 = calls["n"]
    assert traces_8 > 0
    fn_8 = t._step_fn

    assert _establish_at(t, 4) is False  # new size: miss, retraces
    t.train_step(features, labels, BATCH, sync=True)
    traces_4 = calls["n"]
    assert traces_4 > traces_8

    assert _establish_at(t, 8) is True  # revisit: cache hit
    assert t._step_fn is fn_8  # the SAME jitted callable
    loss, n, count = t.train_step(features, labels, BATCH, sync=True)
    assert calls["n"] == traces_4, "revisit at a seen size retraced"
    assert np.isfinite(loss) and n == 8 and count == BATCH
    stats = t.compile_stats.snapshot()
    assert stats["hits"] == 1 and stats["misses"] == 2
    t.close()


def test_batch_shape_change_misses_instead_of_stale_reuse():
    loss_fn, calls = _counting_loss()
    t = _make_trainer(loss_fn)
    features, labels = _batch(2)
    _establish_at(t, 8)
    t.train_step(features, labels, BATCH, sync=True)
    traces = calls["n"]

    # same executable-cache entry, DIFFERENT batch shape (a larger
    # minibatch pads to more rows): jax's aval cache must miss and
    # compile the new shape — reusing the 16-row executable for 32-row
    # input would be a stale-executable bug
    wide_f, wide_l = _batch(3, batch=32)
    loss, n, count = t.train_step(wide_f, wide_l, 32, sync=True)
    assert calls["n"] > traces, "batch-shape change did not retrace"
    assert np.isfinite(loss) and count == 32
    t.close()


def _resize_journey(cache_enabled, speculative=False):
    """8 -> 4 -> 8 with three steps at each size; returns the final
    host state and the trainer's compile counters."""
    sizes = (8, 4, 8)
    batches = [_batch(seed) for seed in (10, 11, 12)]
    t = _make_trainer()
    t.compile_cache_enabled = cache_enabled
    t.speculative_compile = speculative
    for i, k in enumerate(sizes):
        _establish_at(t, k)
        for features, labels in batches:
            t.train_step(features, labels, BATCH, sync=True)
        if speculative and i + 1 < len(sizes):
            # the membership service's role in a live job: hint the
            # next size in steady state, and let the build land before
            # the resize
            if t._spec_compiler is None:
                t._start_speculative_compiler()
            t.hint_world_sizes([sizes[i + 1]])
            assert _wait(t._spec_compiler.idle, timeout=120.0)
    host = t.snapshot()
    stats = t.compile_stats.snapshot()
    t.close()
    return host, stats


@pytest.fixture(scope="module")
def cold_journey_params():
    host, _ = _resize_journey(cache_enabled=False)
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(host.params)]


@pytest.mark.parametrize("speculative", [False, True])
def test_cached_executable_matches_fresh_build_bitwise(
    cold_journey_params, speculative
):
    """The resize journey ends in bit-identical parameters whether every
    establish compiles afresh, reuses the cached executable, or finds
    one the speculative compiler built ahead of the resize: a reused or
    AOT-built executable that changed the math would be a correctness
    bug, not a faster resize."""
    cached, stats = _resize_journey(
        cache_enabled=True, speculative=speculative
    )
    if speculative:
        # the first visit to 4 ran the executable built off the loop
        assert stats["speculative_hits"] >= 1, stats
    got = jax.tree_util.tree_leaves(cached.params)
    assert len(got) == len(cold_journey_params)
    for a, b in zip(cold_journey_params, got):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_cache_evicts_entries_from_dead_backends():
    cache = ExecutableCache()
    key = ("mesh-sig", "config-sig")
    cache.put(key, object())
    assert cache.get(key) is not None
    # a world re-form drops every backend; entries minted before must
    # never be handed back (their device handles are dead)
    distributed._bump_backend_epoch()
    assert cache.get(key) is None
    assert cache.stats.get("stale_evictions") == 1


def test_cache_lru_bounds_entries():
    cache = ExecutableCache(max_entries=2)
    for i in range(3):
        cache.put(("k", i), object())
    assert cache.size() == 2
    assert cache.get(("k", 0), count=False) is None  # evicted oldest
    assert cache.get(("k", 2), count=False) is not None


def test_mesh_signature_distinguishes_device_sets():
    devices = np.asarray(jax.devices())
    m8 = Mesh(devices, ("data",))
    m4 = Mesh(devices[:4], ("data",))
    m8b = Mesh(devices, ("data",))
    assert mesh_signature(m8) == mesh_signature(m8b)
    assert mesh_signature(m8) != mesh_signature(m4)


# ---------------------------------------------------------------------------
# speculative compiler: lifecycle, drops, cache pre-warm
# ---------------------------------------------------------------------------


def _wait(predicate, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.02)
    return False


def test_speculative_compile_prewarms_establish():
    t = _make_trainer()
    _establish_at(t, 8)
    features, labels = _batch(4)
    t.train_step(features, labels, BATCH, sync=True)

    t.speculative_compile = True
    t._start_speculative_compiler()
    t.hint_world_sizes([4])
    assert _wait(
        lambda: t.compile_stats.get("speculative_builds") >= 1
        and t._spec_compiler.idle()
    ), "speculative compile never landed"

    assert _establish_at(t, 4) is True  # the speculated entry
    assert t.compile_stats.get("speculative_hits") == 1
    # the AOT executable dispatches this exact signature — no retrace
    loss, n, count = t.train_step(features, labels, BATCH, sync=True)
    assert np.isfinite(loss) and n == 4 and count == BATCH
    t.close()


def test_speculative_size_that_never_materializes_is_dropped():
    t = _make_trainer()
    _establish_at(t, 8)
    features, labels = _batch(5)
    t.train_step(features, labels, BATCH, sync=True)
    t.speculative_compile = True
    t._start_speculative_compiler()
    before = time.perf_counter()
    t.hint_world_sizes([999])  # more devices than the backend has
    hint_cost = time.perf_counter() - before
    assert hint_cost < 0.5, "hint() blocked the hot loop"
    assert _wait(lambda: t.compile_stats.get("dropped") >= 1)
    # the hot loop keeps stepping while the hint dies in the background
    loss, _, _ = t.train_step(features, labels, BATCH, sync=True)
    assert np.isfinite(loss)
    t.close()


def test_speculative_compiler_shuts_down_on_establish_and_close():
    t = _make_trainer()
    _establish_at(t, 8)
    t.speculative_compile = True
    t._start_speculative_compiler()
    sc = t._spec_compiler
    thread = sc._thread
    assert thread is not None and thread.is_alive()

    # establish()'s first act is _shutdown_compile_helpers(): the old
    # backend's compiler must be gone before the world is torn down
    t._shutdown_compile_helpers()
    assert t._spec_compiler is None
    assert not thread.is_alive()

    # restart then close(): same guarantee at worker teardown
    t._start_speculative_compiler()
    thread = t._spec_compiler._thread
    t.close()
    assert not thread.is_alive()


def test_speculative_compiler_shutdown_drops_pending_hints():
    started = threading.Event()
    release = threading.Event()
    built = []

    def slow_compile(size):
        started.set()
        release.wait(timeout=30)
        built.append(size)
        return True

    sc = SpeculativeCompiler(slow_compile)
    sc.start()
    sc.hint([3])
    assert started.wait(timeout=10)
    sc.hint([5, 7])  # queued behind the in-flight compile
    assert sc.pending_count() == 2
    # cooperative cancel lands BEFORE the in-flight compile finishes:
    # the worker completes size 3 (C++ compiles are uninterruptible)
    # and must then exit without touching the queue again
    sc._cancel.set()
    release.set()
    sc.shutdown()
    assert sc.pending_count() == 0
    assert built == [3], "pending hints ran after shutdown"
    assert sc.stats.get("dropped") == 2
    # post-shutdown hints are ignored, not queued
    sc.hint([9])
    assert sc.pending_count() == 0


def test_speculative_compiler_dedups_hints():
    seen = []
    done = threading.Event()

    def compile_fn(size):
        seen.append(size)
        if len(seen) >= 2:
            done.set()
        return True

    sc = SpeculativeCompiler(compile_fn)
    sc.start()
    sc.hint([4, 4, 6, 4, 6])
    assert done.wait(timeout=10)
    sc.shutdown()
    assert sorted(seen) == [4, 6]


def test_speculative_compiler_accepts_layout_hints():
    """ISSUE 20: hints may be (world_size, layout) tuples — the layout
    half is opaque to the compiler but participates in dedup, so two
    different layouts of ONE world size both compile, while a repeated
    (world, layout) pair does not."""
    seen = []
    done = threading.Event()

    def compile_fn(hint):
        seen.append(hint)
        if len(seen) >= 3:
            done.set()
        return True

    lay_a = (8, (("data", 4), ("model", 2)))
    lay_b = (8, (("data", 2), ("model", 4)))
    sc = SpeculativeCompiler(compile_fn)
    sc.start()
    sc.hint([lay_a, lay_b, lay_a, 8, lay_b])
    assert done.wait(timeout=10)
    sc.shutdown()
    assert sorted(seen, key=str) == sorted(
        [lay_a, lay_b, 8], key=str
    )
    # zero/negative world sizes are dropped in either form
    sc2 = SpeculativeCompiler(compile_fn)
    sc2.hint([0, (0, (("data", 1),))])
    assert sc2.pending_count() == 0


# ---------------------------------------------------------------------------
# step overlap: staged H2D equivalence + deferred metric collection
# ---------------------------------------------------------------------------


def test_staged_h2d_placement_is_equivalent_and_feeder_shuts_down():
    batches = [_batch(seed) for seed in (20, 21, 22, 23)]

    def run(staged):
        t = _make_trainer()
        _establish_at(t, 8)
        losses = []
        for i, (features, labels) in enumerate(batches):
            loss, _, _ = t.train_step(features, labels, BATCH, sync=True)
            losses.append(loss)
            if staged and i + 1 < len(batches):
                # stage AFTER the take-side step so the slot is not
                # superseded before train_step(i+1) collects it
                nxt_f, nxt_l = batches[i + 1]
                t.stage_next(nxt_f, nxt_l, BATCH)
        host = t.snapshot()
        feeder_thread = (
            t._feeder._thread if t._feeder is not None else None
        )
        t.close()
        if feeder_thread is not None:
            assert not feeder_thread.is_alive()
        return losses, host

    plain_losses, plain = run(staged=False)
    staged_losses, staged = run(staged=True)
    assert plain_losses == staged_losses
    for a, b in zip(
        jax.tree_util.tree_leaves(plain.params),
        jax.tree_util.tree_leaves(staged.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_deferred_metrics_match_per_step_sync_stream():
    batches = [_batch(seed) for seed in range(30, 39)]

    def run(deferred):
        t = _make_trainer()
        _establish_at(t, 8)
        losses = []
        for i, (features, labels) in enumerate(batches):
            if deferred:
                sync = (i + 1) % 4 == 0 or i == len(batches) - 1
                loss, _, _ = t.train_step(
                    features, labels, BATCH, sync=sync
                )
                if sync:
                    losses.extend(t.drain_metrics())
                    losses.append(loss)
            else:
                loss, _, _ = t.train_step(
                    features, labels, BATCH, sync=True
                )
                losses.append(loss)
        t.close()
        return losses

    assert run(deferred=False) == run(deferred=True)


def test_drain_metrics_empty_and_wedged():
    t = _make_trainer()
    _establish_at(t, 8)
    assert t.drain_metrics() == []
    features, labels = _batch(40)
    t.train_step(features, labels, BATCH, sync=False)
    assert t.steps_in_flight == 1
    # a wedged trainer must not fetch (the device stream would block
    # forever); the receipts in flight are dropped
    t._wedged = True
    assert t.drain_metrics() == []
    assert t.steps_in_flight == 0
    t._wedged = False
    t.close()


def test_take_staged_mismatched_batch_places_inline():
    t = _make_trainer()
    _establish_at(t, 8)
    f1, l1 = _batch(50)
    f2, l2 = _batch(51)
    t.stage_next(f1, l1, BATCH)
    # a DIFFERENT batch steps next (reform reshuffled the stream): the
    # staged placement must be ignored, not misapplied
    loss, _, _ = t.train_step(f2, l2, BATCH, sync=True)
    assert np.isfinite(loss)

    # and the superseded stage slot does not poison the next take
    t.stage_next(f1, l1, BATCH)
    loss2, _, _ = t.train_step(f1, l1, BATCH, sync=True)
    assert np.isfinite(loss2)
    t.close()


def test_persistent_cache_skipped_on_cpu(monkeypatch):
    """CPU-pinned processes must NOT take the persistent cache (reloaded
    donated executables crash this toolchain; see compile_plane)."""
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("EDL_COMPILE_CACHE_CPU", raising=False)
    try:
        assert compile_plane.enable_persistent_cache() is False
        assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_persistent_cache_placed_by_one_rule(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: jax keeps its cache there and the
    code sets nothing. Unset: the fixed directory inside the checkout."""
    prev = jax.config.jax_compilation_cache_dir
    # the suite runs CPU-pinned; exercise the rule via the explicit
    # override the caveat documents
    monkeypatch.setenv("EDL_COMPILE_CACHE_CPU", "1")
    monkeypatch.setattr(
        compile_plane, "DEFAULT_CACHE_DIR", str(tmp_path / "fixed")
    )
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        assert compile_plane.enable_persistent_cache() is True
        assert jax.config.jax_compilation_cache_dir == prev  # untouched
        assert not (tmp_path / "fixed").exists()

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_plane.enable_persistent_cache() is True
        assert jax.config.jax_compilation_cache_dir == str(
            tmp_path / "fixed"
        )
        assert (tmp_path / "fixed").is_dir()
        # idempotent
        assert compile_plane.enable_persistent_cache() is True
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_default_cache_dir_is_inside_the_checkout():
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_plane.DEFAULT_CACHE_DIR == os.path.join(
        repo, ".jax_cache"
    )
