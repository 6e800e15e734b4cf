"""Overlapped PS data-plane tests: concurrent shard fan-out semantics,
the double-buffered async push window, and drain-on-boundary behavior
(docs/dense_overlap.md). Fault injection comes from tests/fake_ps.py."""

import time

import numpy as np
import pytest

from elasticdl_tpu.common.tensor import Tensor
from elasticdl_tpu.worker.ps_client import PSClient
from tests.fake_ps import FaultyPS, ShardKilledError, TablePS


def make_fleet(n, **faulty_kwargs):
    inners = [TablePS() for _ in range(n)]
    return inners, [FaultyPS(t, **faulty_kwargs) for t in inners]


# ---------------------------------------------------------------------------
# fan-out
# ---------------------------------------------------------------------------


def test_fanout_pull_matches_serial_and_overlaps():
    """Concurrent fan-out returns byte-identical results to the serial
    loop, while the per-shard legs actually overlap in time."""
    _, slow = make_fleet(4, delay_s=0.15)
    _, serial_stubs = make_fleet(4)
    ids = np.arange(32, dtype=np.int64)

    serial = PSClient(serial_stubs, fanout=False)
    overlapped = PSClient(slow, fanout=True)

    expect = serial.pull_embedding_vectors("emb", ids)
    t0 = time.monotonic()
    got = overlapped.pull_embedding_vectors("emb", ids)
    wall = time.monotonic() - t0
    np.testing.assert_array_equal(got, expect)
    # 4 shards x 0.15s serially would be >= 0.6s; overlapped tracks the
    # slowest single shard (generous 3x margin for thread scheduling)
    assert wall < 0.45, "fan-out did not overlap: %.3fs" % wall
    assert max(s.max_concurrency() for s in slow) >= 1
    assert any(s.max_concurrency() >= 2 for s in slow) or all(
        len(s.calls) == 1 for s in slow
    )


def test_fanout_wall_tracks_slowest_shard_not_sum():
    """One injected slow shard: wall time ~= the slow shard, not the
    sum over shards."""
    inners = [TablePS() for _ in range(4)]
    stubs = [
        FaultyPS(t, delay_s=(0.4 if i == 2 else 0.05))
        for i, t in enumerate(inners)
    ]
    client = PSClient(stubs, fanout=True)
    ids = np.arange(16, dtype=np.int64)
    t0 = time.monotonic()
    client.pull_embedding_vectors("emb", ids)
    wall = time.monotonic() - t0
    # serial would be 3*0.05 + 0.4 = 0.55s; fan-out ~0.4s
    assert wall < 0.55
    assert wall >= 0.4


def test_fanout_error_is_deterministic_lowest_shard():
    """When several shards fail in one fan-out, the LOWEST-numbered
    shard's exception surfaces, and only after every leg finished."""
    inners = [TablePS() for _ in range(3)]

    class Boom(RuntimeError):
        pass

    class BoomPS(FaultyPS):
        def _forward(self, method, req):
            raise Boom("shard-2 error")

    stubs = [
        FaultyPS(inners[0]),
        FaultyPS(inners[1], kill_after=0),  # shard 1: ShardKilledError
        BoomPS(inners[2]),  # shard 2: Boom
    ]
    client = PSClient(stubs, fanout=True)
    try:
        with pytest.raises(ShardKilledError):
            client.pull_embedding_vectors(
                "emb", np.arange(9, dtype=np.int64)
            )
        # shard 0's leg completed even though the call failed overall
        assert len(stubs[0].calls) == 1
    finally:
        # the captured exception pins this frame (and the client) via
        # its traceback, so pool GC can't collect the fan-out threads —
        # the locktrace leak guard rightly flags that without close()
        client.close()


def test_push_gradient_combines_all_shards_not_last():
    """accepted = all(shards), version = min(shards) — a rejection on a
    NON-final shard must not be masked by the last shard's accept."""
    inners = [TablePS(), TablePS(), TablePS()]
    inners[2].version = 50  # last shard reports the highest version
    stubs = [
        FaultyPS(inners[0], reject_pushes=True),  # first shard rejects
        FaultyPS(inners[1]),
        FaultyPS(inners[2]),
    ]
    client = PSClient(stubs, fanout=True)
    accepted, version = client.push_gradient(
        {"w": np.ones((2,), np.float32)},
        [Tensor("emb", np.ones((3, 2), np.float32), indices=[0, 1, 2])],
        version=0,
    )
    assert not accepted  # the reference's choose-last would say True
    assert version == 1  # min over (1, 1, 51), not the last shard's 51


def test_fanout_off_single_shard_paths_still_work():
    inners, stubs = make_fleet(1)
    client = PSClient(stubs, fanout=True)  # 1 shard -> serial path
    rows = client.pull_embedding_vectors("emb", np.array([3, 1, 3]))
    assert rows.shape == (3, 4)
    accepted, version = client.push_gradient({}, [], 0)
    assert accepted and version == 1


# ---------------------------------------------------------------------------
# double-buffered async push
# ---------------------------------------------------------------------------


def test_async_push_window_bounds_inflight():
    """push_inflight=1: the first push returns ~immediately, the second
    blocks until the first completes (bounded double buffering)."""
    inners = [TablePS()]
    stubs = [
        FaultyPS(inners[0], delay_s=0.3, delay_methods={"push_gradient"})
    ]
    client = PSClient(stubs, push_inflight=1)
    grads = {"w": np.ones((2,), np.float32)}

    t0 = time.monotonic()
    accepted, _ = client.push_gradient(grads, [], 0)
    first = time.monotonic() - t0
    assert accepted  # optimistic accept
    assert first < 0.15, "async push blocked: %.3fs" % first
    assert client.pending_push_count == 1

    t0 = time.monotonic()
    client.push_gradient(grads, [], 0)
    second = time.monotonic() - t0
    assert second >= 0.15, "window did not bound in-flight pushes"

    accepted, version = client.drain()
    assert accepted and version == 2
    assert client.pending_push_count == 0
    assert inners[0].pushes == 2


def test_pull_dense_drains_async_window():
    """The model a worker pulls reflects its own completed pushes: the
    pull waits for the in-flight push and sees the advanced version."""
    inners = [TablePS()]
    stubs = [
        FaultyPS(inners[0], delay_s=0.2, delay_methods={"push_gradient"})
    ]
    client = PSClient(stubs, push_inflight=2)
    client.push_gradient({"w": np.ones((1,), np.float32)}, [], 0)
    ok, version, _ = client.pull_dense()
    assert ok and version == 1
    assert client.pending_push_count == 0


def test_async_push_surfaces_shard_death_at_reap():
    """A shard that dies mid-push raises at the next window reap/drain
    rather than hanging or passing silently."""
    inners = [TablePS()]
    stubs = [FaultyPS(inners[0], kill_after=1)]
    client = PSClient(stubs, push_inflight=1)
    try:
        client.push_gradient({"w": np.ones((1,), np.float32)}, [], 0)
        client.drain()
        client.push_gradient({"w": np.ones((1,), np.float32)}, [], 1)
        with pytest.raises(ShardKilledError):
            client.drain()
        # a later drain is clean: the failed push left the window
        assert client.drain() == (True, 1)
    finally:
        client.close()  # see test_fanout_error: traceback pins the pool


def test_async_push_reports_late_rejection_on_drain():
    inners = [TablePS()]
    stubs = [FaultyPS(inners[0], reject_pushes=True)]
    client = PSClient(stubs, push_inflight=1)
    accepted, _ = client.push_gradient(
        {"w": np.ones((1,), np.float32)}, [], 0
    )
    assert accepted  # optimistic
    accepted, _ = client.drain()
    assert not accepted  # reconciled truth
    assert client.drain()[0]  # rejection consumed by the first drain


def test_async_push_equivalence_with_sync_fixed_seed():
    """Exact equivalence: the same gradient sequence pushed through the
    async window (drained at the end) and through synchronous pushes
    yields bit-identical dense params and embedding rows."""
    import optax

    from elasticdl_tpu.ps.parameters import Parameters
    from elasticdl_tpu.ps.servicer import PserverServicer

    def fleet():
        return [
            PserverServicer(
                Parameters(), 1, optax.sgd(0.1), use_async=True
            )
            for _ in range(2)
        ]

    rng = np.random.default_rng(1234)
    dense_names = ["a/w", "a/b", "b/w"]
    steps = [
        (
            {
                n: rng.normal(size=(3,)).astype(np.float32)
                for n in dense_names
            },
            [
                Tensor(
                    "emb",
                    rng.normal(size=(4, 2)).astype(np.float32),
                    indices=rng.integers(0, 8, size=4),
                )
            ],
        )
        for _ in range(6)
    ]

    def run(push_inflight):
        servicers = fleet()
        client = PSClient(
            servicers, fanout=True, push_inflight=push_inflight
        )
        client.push_model(
            {n: np.zeros((3,), np.float32) for n in dense_names},
            embedding_infos=[
                type(
                    "I",
                    (),
                    {"name": "emb", "dim": 2, "initializer": "zeros"},
                )
            ],
        )
        client.pull_embedding_vectors("emb", np.arange(8))
        for v, (dense, sparse) in enumerate(steps):
            accepted, _ = client.push_gradient(dense, sparse, v)
            assert accepted
        accepted, _ = client.drain()
        assert accepted
        ok, version, named = client.pull_dense()
        assert ok
        rows = client.pull_embedding_vectors("emb", np.arange(8))
        client.close()
        return version, named, rows

    v_sync, named_sync, rows_sync = run(push_inflight=0)
    v_async, named_async, rows_async = run(push_inflight=1)
    assert v_sync == v_async
    assert set(named_sync) == set(named_async)
    for name in named_sync:
        np.testing.assert_array_equal(named_sync[name], named_async[name])
    np.testing.assert_array_equal(rows_sync, rows_async)


# ---------------------------------------------------------------------------
# worker integration: drain on task boundary
# ---------------------------------------------------------------------------


def test_worker_e2e_async_push_drains_and_matches_sync(monkeypatch):
    """Full worker job with the async push window: completes, leaves no
    push in flight at the end, and — because every pull drains — the
    final sharded model state exactly matches the synchronous run."""
    import optax

    from elasticdl_tpu.common.constants import JobType
    from elasticdl_tpu.master.checkpoint_service import CheckpointService
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.ps.parameters import Parameters
    from elasticdl_tpu.ps.servicer import PserverServicer
    from elasticdl_tpu.worker.worker import Worker
    from tests.in_process_master import InProcessMaster
    from tests.test_utils import (
        MODEL_ZOO_PATH,
        DatasetName,
        create_recordio_file,
    )

    model_def = "mnist_functional_api.mnist_functional_api.custom_model"
    f = create_recordio_file(64, DatasetName.IMAGE_DEFAULT, (28, 28))

    # the zoo dataset_fn buffer-shuffles with OS entropy and the
    # dispatcher shuffles tasks via the global random state; pin both
    # so the two arms train on byte-identical batch sequences and the
    # comparison isolates the push mode
    from elasticdl_tpu.data.dataset import Dataset

    monkeypatch.setattr(
        Dataset, "shuffle", lambda self, buffer_size, seed=None: self
    )

    def run(push_inflight):
        import random

        random.seed(42)
        servicers = [
            PserverServicer(
                Parameters(), 1, optax.sgd(0.01), use_async=True
            )
            for _ in range(2)
        ]
        client = PSClient(
            servicers, fanout=True, push_inflight=push_inflight
        )
        task_d = TaskDispatcher({f: (0, 64)}, {}, {}, 32, 1)
        master = MasterServicer(
            1,
            32,
            None,
            task_d,
            checkpoint_service=CheckpointService("", 0, 0, False),
            use_async=True,
        )
        worker = Worker(
            worker_id=1,
            job_type=JobType.TRAINING_ONLY,
            minibatch_size=32,
            model_zoo=MODEL_ZOO_PATH,
            model_def=model_def,
            ps_client=client,
            seed=7,
        )
        worker._stub = InProcessMaster(master)
        worker.run()
        assert task_d.finished()
        assert client.pending_push_count == 0
        state = {}
        for i, s in enumerate(servicers):
            for k, v in s._parameters.to_named_arrays().items():
                state["%d/%s" % (i, k)] = np.array(v)
        client.close()
        return state

    sync_state = run(push_inflight=0)
    async_state = run(push_inflight=1)
    assert set(sync_state) == set(async_state)
    for k in sync_state:
        np.testing.assert_array_equal(sync_state[k], async_state[k])


def test_boundary_drain_failure_does_not_kill_worker():
    """A PS failure surfacing at a task-boundary drain is logged and
    dropped (bounded staleness), never propagated — the worker process
    must survive and let the next minibatch's pull hit the failed-task
    path. (The minibatch-path drain, inside pull_dense, still raises.)
    """
    from elasticdl_tpu.worker.worker import Worker

    class FailingDrainClient:
        def drain(self):
            raise RuntimeError("injected: shard died mid-push")

    worker = Worker.__new__(Worker)  # no heavy init needed
    worker._ps_client = FailingDrainClient()
    worker._drain_ps_pushes()  # must not raise


def test_close_is_best_effort_after_failed_drain():
    inners = [TablePS()]
    stubs = [FaultyPS(inners[0], kill_after=0)]
    client = PSClient(stubs, push_inflight=1)
    client.push_gradient({"w": np.ones((1,), np.float32)}, [], 0)
    client.close()  # drain fails inside; close still releases pools
    assert client.pending_push_count == 0


def test_close_refuses_to_resurrect_pools():
    """A pull/push racing close() must not lazily recreate an executor
    nothing will ever shut down (close() detaches the handles under
    the pool lock and shuts the threads down OUTSIDE it, so a late
    caller would otherwise see None and mint a leaking pool)."""
    _, stubs = make_fleet(2)
    client = PSClient(stubs, fanout=True, push_inflight=1)
    client._get_fanout_pool()  # warm one pool pre-close
    client.close()
    with pytest.raises(RuntimeError, match="closed"):
        client._get_fanout_pool()
    with pytest.raises(RuntimeError, match="closed"):
        client.push_gradient({"w": np.ones((1,), np.float32)}, [], 0)
    assert client._fanout_pool is None and client._push_pool is None


def test_multi_table_pull_one_round_matches_per_table():
    """pull_embedding_vectors_multi returns per-table results identical
    to sequential per-table pulls, in ONE concurrent round (wall tracks
    one leg, not tables x shards legs)."""
    inners = [TablePS(), TablePS()]
    slow = [FaultyPS(t, delay_s=0.15) for t in inners]
    client = PSClient(slow, fanout=True)
    ref_client = PSClient([TablePS(), TablePS()], fanout=False)
    tables = {
        "emb_a": np.arange(12, dtype=np.int64),
        "emb_b": np.array([5, 3, 5, 8], dtype=np.int64),
        "emb_empty": np.array([], dtype=np.int64),
    }
    t0 = time.monotonic()
    got = client.pull_embedding_vectors_multi(tables)
    wall = time.monotonic() - t0
    for name, ids in tables.items():
        np.testing.assert_array_equal(
            got[name], ref_client.pull_embedding_vectors(name, ids)
        )
    # 2 tables x 2 shards x 0.15s serially = 0.6s; one round ~0.15s
    assert wall < 0.45, "multi-pull did not overlap: %.3fs" % wall
    client.close()
    ref_client.close()


def test_cache_probe_once_per_distinct_id():
    """Vectorized cache probe: a batch with duplicates costs one probe
    per DISTINCT id, every position is served, and the RPC-skip
    semantics stay pinned."""
    from tests.fake_ps import TablePS

    stubs = [TablePS(), TablePS()]
    client = PSClient(
        stubs, hot_row_cache_rows=64, staleness_window=1, fanout=True
    )
    ids = np.array([4, 1, 4, 1, 4, 2], dtype=np.int64)
    first = client.pull_embedding_vectors("emb", ids)
    assert stubs[0].pulls == 1 and stubs[1].pulls == 1
    cache = client.hot_row_cache
    hits0, misses0 = cache.hits, cache.misses
    again = client.pull_embedding_vectors("emb", ids)
    np.testing.assert_array_equal(again, first)
    # no new RPC, and exactly one probe per distinct id (3), all hits
    assert stubs[0].pulls == 1 and stubs[1].pulls == 1
    assert cache.hits - hits0 == 3
    assert cache.misses == misses0
