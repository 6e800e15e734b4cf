"""Sparse-comms fast path: dedup/combine/cache correctness.

The fast path (docs/sparse_fast_path.md) must be a pure wire
optimization: identical forward activations and identical gradients to
the naive per-occurrence path on any batch, including heavy id
duplication, non-divisor (PadDim0-style padded) vocabs, and
mask_zero/combiner layer variants. These tests pin that equivalence on
both embedding planes, plus the HotRowCache's LRU/version semantics and
the satellite fixes (prefetch sentinel cancel, stale-round ledger
append).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.parallel.mesh import create_mesh


# ---------------------------------------------------------------------------
# padded_unique
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "ids",
    [
        np.array([5, 3, 5, 5, 7, 3, 0]),
        np.array([4]),
        np.array([9, 9, 9, 9]),
        np.arange(16)[::-1].copy(),
    ],
)
def test_padded_unique_matches_np_unique(ids):
    from elasticdl_tpu.nn.sparse_comms import padded_unique

    ids = ids.astype(np.int32)
    uids, inv, k = jax.jit(padded_unique)(ids)
    expect = np.unique(ids)
    assert int(k) == len(expect)
    np.testing.assert_array_equal(np.asarray(uids)[: len(expect)], expect)
    np.testing.assert_array_equal(np.asarray(uids)[len(expect):], -1)
    # inverse reconstructs the input exactly
    np.testing.assert_array_equal(np.asarray(uids)[np.asarray(inv)], ids)


# ---------------------------------------------------------------------------
# HBM plane: dedup a2a == naive a2a == plain take (fwd + grad)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "mesh_axes", [{"data": 8}, {"data": 2, "model": 4}]
)
def test_a2a_dedup_matches_naive_forward_and_grad(mesh_axes):
    from elasticdl_tpu.nn.hbm_embedding import all_to_all_lookup

    # capacity=None: the always-correct worst case for BOTH paths (a
    # tight capacity is where they legitimately diverge — naive drops
    # per-occurrence overflow, dedup stays exact; covered below)
    capacity = None
    axis = "model" if "model" in mesh_axes else "data"
    mesh = create_mesh(mesh_axes, axis_names=tuple(mesh_axes))
    rng = np.random.default_rng(0)
    table = rng.standard_normal((64, 5)).astype(np.float32)
    # heavy duplication: 48 ids drawn from 6 distinct values
    ids = rng.choice(rng.permutation(64)[:6], size=(8, 6)).astype(np.int32)

    def lookup(t, dedup):
        return all_to_all_lookup(
            t, ids, mesh, axis, capacity=capacity, dedup=dedup
        )

    fwd_naive = np.asarray(jax.jit(lambda t: lookup(t, False))(table))
    fwd_dedup = np.asarray(jax.jit(lambda t: lookup(t, True))(table))
    np.testing.assert_allclose(fwd_dedup, table[ids], rtol=1e-6)
    np.testing.assert_allclose(fwd_dedup, fwd_naive, rtol=1e-6)

    def loss(t, dedup):
        out = lookup(t, dedup)
        return jnp.sum(out * out * jnp.arange(out.size).reshape(out.shape))

    g_naive = np.asarray(jax.jit(jax.grad(lambda t: loss(t, False)))(table))
    g_dedup = np.asarray(jax.jit(jax.grad(lambda t: loss(t, True)))(table))
    np.testing.assert_allclose(g_dedup, g_naive, rtol=1e-5, atol=1e-6)


def test_a2a_dedup_correct_at_unique_sized_capacity():
    """A capacity sized for the UNIQUE count (way below the occurrence
    count) must stay exact under dedup — the whole point of the fast
    path — while the naive path drops rows at the same capacity."""
    from elasticdl_tpu.nn.hbm_embedding import all_to_all_lookup

    mesh = create_mesh({"data": 8}, axis_names=("data",))
    table = np.arange(64, dtype=np.float32).reshape(32, 2)
    ids = np.tile(np.array([3, 17, 3, 3], np.int32), 8)  # 32 ids, 2 unique

    got = np.asarray(
        jax.jit(
            lambda t: all_to_all_lookup(
                t, ids, mesh, "data", capacity=2, dedup=True
            )
        )(table)
    )
    np.testing.assert_allclose(got, table[ids], rtol=1e-6)

    _, n_over = jax.jit(
        lambda t: all_to_all_lookup(
            t, ids, mesh, "data", capacity=2, dedup=True,
            return_overflow=True,
        )
    )(table)
    assert int(n_over) == 0


def test_a2a_dedup_on_padded_non_divisor_vocab():
    """PadDim0-style world: a prime logical vocab padded up to the next
    multiple of the axis size; ids only ever target the logical rows."""
    from elasticdl_tpu.nn.hbm_embedding import all_to_all_lookup

    mesh = create_mesh({"data": 8}, axis_names=("data",))
    logical, padded = 67, 72  # 67 is prime; 72 = next multiple of 8
    rng = np.random.default_rng(3)
    table = np.zeros((padded, 3), np.float32)
    table[:logical] = rng.standard_normal((logical, 3))
    ids = rng.choice(
        rng.permutation(logical)[:9], size=(41,)
    ).astype(np.int32)

    for dedup in (False, True):
        got = np.asarray(
            jax.jit(
                lambda t, d=dedup: all_to_all_lookup(
                    t, ids, mesh, "data", dedup=d
                )
            )(table)
        )
        np.testing.assert_allclose(got, table[ids], rtol=1e-6)

    def loss(t, dedup):
        return jnp.sum(
            all_to_all_lookup(t, ids, mesh, "data", dedup=dedup) ** 2
        )

    g0 = np.asarray(jax.jit(jax.grad(lambda t: loss(t, False)))(table))
    g1 = np.asarray(jax.jit(jax.grad(lambda t: loss(t, True)))(table))
    np.testing.assert_allclose(g1, g0, rtol=1e-5, atol=1e-6)
    assert (g1[logical:] == 0).all()  # padding rows never touched


@pytest.mark.parametrize("mask_zero", [False, True])
def test_hbm_layer_dedup_equivalence_trains(mask_zero):
    """HbmEmbedding(dedup=True) — the default — produces the same
    forward and the same table gradient as dedup=False inside a jitted
    train-style step, mask_zero included."""
    from elasticdl_tpu.nn.hbm_embedding import HbmEmbedding

    mesh = create_mesh({"data": 8}, axis_names=("data",))
    rng = np.random.default_rng(1)
    ids = rng.choice([0, 2, 5, 9], size=(16, 4)).astype(np.int32)

    outs, grads = [], []
    for dedup in (False, True):
        model = HbmEmbedding(
            vocab_size=16, features=4, mesh=mesh, axis="data",
            method="a2a", mask_zero=mask_zero, dedup=dedup,
        )
        variables = model.init(jax.random.PRNGKey(0), ids)

        @jax.jit
        def fwd_loss(params):
            out, _ = model.apply(
                {"params": params}, ids, mutable=["metrics"]
            )
            return jnp.sum(out**2), out

        with mesh:
            (loss, out), g = jax.value_and_grad(
                fwd_loss, has_aux=True
            )(variables["params"])
        outs.append(np.asarray(out))
        grads.append(np.asarray(g["table"]))
    np.testing.assert_allclose(outs[1], outs[0], rtol=1e-6)
    np.testing.assert_allclose(grads[1], grads[0], rtol=1e-5, atol=1e-6)


def test_collective_dedup_matches_naive():
    """The elastic-plane collective body (axis bound by an outer
    shard_map, each device holding a distinct batch slice) under dedup
    matches the naive collective and the dense take."""
    from elasticdl_tpu.nn.hbm_embedding import (
        a2a_dedup_lookup_collective,
        a2a_lookup_collective,
    )
    from jax.sharding import PartitionSpec as P

    mesh = create_mesh({"data": 8}, axis_names=("data",))
    rng = np.random.default_rng(5)
    table = rng.standard_normal((64, 4)).astype(np.float32)
    ids = rng.choice(
        rng.permutation(64)[:7], size=(64,)
    ).astype(np.int32)

    def run(body):
        fn = jax.shard_map(
            lambda t, i: body(t, i, "data"),
            mesh=mesh,
            in_specs=(P("data", None), P("data")),
            out_specs=P("data", None),
            check_vma=False,
        )
        return np.asarray(jax.jit(fn)(table, ids))

    naive = run(a2a_lookup_collective)
    dedup = run(a2a_dedup_lookup_collective)
    np.testing.assert_allclose(naive, table[ids], rtol=1e-6)
    np.testing.assert_allclose(dedup, naive, rtol=1e-6)


# ---------------------------------------------------------------------------
# PS plane: naive plan == dedup plan (fwd + row grads), combiner variants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mask_zero", [False, True])
@pytest.mark.parametrize("combiner", [None, "sum", "mean", "sqrtn"])
def test_ps_plane_plan_equivalence(mask_zero, combiner):
    """Forward and per-unique-id row gradients through the elastic
    Embedding layer are identical between the dedup plan and the naive
    per-occurrence plan (once the naive grads are row-combined)."""
    from elasticdl_tpu.common.tensor import combine_indexed_slices
    from elasticdl_tpu.nn.embedding import (
        Embedding,
        IDX_COLLECTION,
        ROWS_COLLECTION,
        build_collection,
        call_slot_name,
        plan_lookup_multi,
    )

    rng = np.random.default_rng(2)
    ids = rng.choice([0, 3, 3, 7, 11], size=(6, 5)).astype(np.int64)
    dim = 4
    store = rng.standard_normal((16, dim)).astype(np.float32)
    layer = Embedding(
        output_dim=dim, mask_zero=mask_zero, combiner=combiner
    )

    results = {}
    for dedup in (True, False):
        unique, (idx,), bucket = plan_lookup_multi([ids], dedup=dedup)
        rows = store[unique]
        rows = np.concatenate(
            [rows, np.zeros((bucket - len(unique), dim), np.float32)]
        )
        variables = {
            ROWS_COLLECTION: build_collection({(): rows}, "rows"),
            IDX_COLLECTION: build_collection(
                {(call_slot_name(0),): idx}, "idx"
            ),
        }

        def fwd(rows_arr):
            v = dict(variables)
            v[ROWS_COLLECTION] = build_collection({(): rows_arr}, "rows")
            return layer.apply(v, ids)

        out = np.asarray(jax.jit(fwd)(rows))
        g = np.asarray(
            jax.jit(jax.grad(lambda r: jnp.sum(fwd(r) ** 2)))(rows)
        )
        # strip padding, combine to per-unique-id rows
        uid, grows = combine_indexed_slices(unique, g[: len(unique)])
        results[dedup] = (out, uid, grows)

    out_d, uid_d, g_d = results[True]
    out_n, uid_n, g_n = results[False]
    np.testing.assert_allclose(out_d, out_n, rtol=1e-6)
    np.testing.assert_array_equal(uid_d, uid_n)
    np.testing.assert_allclose(g_d, g_n, rtol=1e-5, atol=1e-6)


def test_combine_indexed_slices():
    from elasticdl_tpu.common.tensor import Tensor, combine_indexed_slices

    idx = np.array([7, 2, 7, 2, 5], np.int64)
    vals = np.arange(10, dtype=np.float32).reshape(5, 2)
    uid, combined = combine_indexed_slices(idx, vals)
    np.testing.assert_array_equal(uid, [2, 5, 7])
    np.testing.assert_allclose(
        combined, [[8.0, 10.0], [8.0, 9.0], [4.0, 6.0]]
    )

    t = Tensor("emb", vals, indices=idx).combined()
    np.testing.assert_array_equal(t.indices, uid)
    np.testing.assert_allclose(t.values, combined)
    # duplicate-free input keeps values (sorted by id), dense is a no-op
    t2 = Tensor("e", vals[:3], indices=np.array([9, 1, 4])).combined()
    np.testing.assert_array_equal(t2.indices, [1, 4, 9])
    np.testing.assert_allclose(t2.values, vals[[1, 2, 0]])
    dense = Tensor("d", vals)
    assert dense.combined() is dense


# ---------------------------------------------------------------------------
# hot-row cache
# ---------------------------------------------------------------------------


def test_hot_row_cache_lru_and_version_window():
    from elasticdl_tpu.worker.ps_client import HotRowCache

    cache = HotRowCache(2, window=1)
    r = lambda v: np.full((3,), v, np.float32)  # noqa: E731
    cache.note_version(0, 5)
    cache.put("t", 1, 0, 5, r(1))
    cache.put("t", 2, 0, 5, r(2))
    assert cache.get("t", 1) is not None
    cache.put("t", 3, 0, 5, r(3))  # evicts id 2 (id 1 was touched)
    assert cache.get("t", 2) is None
    assert cache.get("t", 1) is not None

    # within the window: version 6 seen, entries at 5 still serve
    cache.note_version(0, 6)
    assert cache.get("t", 1) is not None
    # beyond the window: entries at 5 age out
    cache.note_version(0, 7)
    assert cache.get("t", 1) is None
    # other shards' versions don't invalidate this shard's rows
    cache.put("t", 4, 1, 0, r(4))
    cache.note_version(0, 50)
    assert cache.get("t", 4) is not None


class _CountingPS:
    """In-process PS stub counting pull_embedding_vector calls."""

    def __init__(self, dim=2):
        self.version = 0
        self.dim = dim
        self.pulls = 0

    def pull_embedding_vector(self, req):
        self.pulls += 1
        ids = np.asarray(req["ids"], np.int64)
        rows = np.stack(
            [np.full((self.dim,), i + 100.0 * self.version) for i in ids]
        ).astype(np.float32)
        return {"rows": rows, "version": self.version}


def test_ps_client_hot_row_cache_serves_repeats_locally():
    from elasticdl_tpu.worker.ps_client import PSClient

    ps = [_CountingPS(), _CountingPS()]
    client = PSClient(ps, hot_row_cache_rows=64, staleness_window=1)
    ids = np.array([0, 1, 2, 3, 4, 5])
    first = client.pull_embedding_vectors("emb", ids)
    assert ps[0].pulls == 1 and ps[1].pulls == 1
    # repeat pull: every id hits, NO rpc at all
    again = client.pull_embedding_vectors("emb", ids)
    np.testing.assert_allclose(again, first)
    assert ps[0].pulls == 1 and ps[1].pulls == 1
    # shard 0 advances beyond the window: only its ids re-pull
    ps[0].version = 2
    client.pull_embedding_vectors("emb", np.array([0, 2]))  # sees v2... cached
    # the client only learns shard 0 moved when a response says so;
    # simulate a push-response version note
    client.hot_row_cache.note_version(0, 2)
    out = client.pull_embedding_vectors("emb", ids)
    assert ps[0].pulls == 2  # shard-0 misses re-pulled
    assert ps[1].pulls == 1  # shard-1 rows still fresh
    np.testing.assert_allclose(out[::2], np.asarray(first)[::2] + 200.0)


def test_ps_client_cache_correct_against_live_servicer():
    """End-to-end against the real PserverServicer: a cached client and
    an uncached client read identical rows while the table mutates,
    as long as pushes note versions (bounded staleness honored)."""
    import optax

    from elasticdl_tpu.common.tensor import Tensor
    from elasticdl_tpu.ps.parameters import Parameters
    from elasticdl_tpu.ps.servicer import PserverServicer
    from elasticdl_tpu.worker.ps_client import PSClient

    params = Parameters()
    servicer = PserverServicer(
        params, 1, optax.sgd(0.5), use_async=True
    )
    client = PSClient(
        [servicer], hot_row_cache_rows=16, staleness_window=0
    )
    client.push_model(
        {"w": np.zeros((2,), np.float32)},
        embedding_infos=[
            type("I", (), {"name": "emb", "dim": 2, "initializer": "zeros"})
        ],
    )
    ids = np.array([1, 3, 1, 5])
    rows1 = client.pull_embedding_vectors("emb", ids)
    # push a sparse grad through the CLIENT (so it notes the version)
    grad = Tensor(
        "emb", np.ones((4, 2), np.float32), indices=ids
    )
    client.push_gradient({}, [grad], version=0)
    rows2 = client.pull_embedding_vectors("emb", ids)
    naive = PSClient([servicer]).pull_embedding_vectors("emb", ids)
    np.testing.assert_allclose(rows2, naive)
    # window=0: the post-push pull must not have served stale rows
    assert not np.allclose(rows1, rows2)


# ---------------------------------------------------------------------------
# satellites: prefetch sentinel cancel; stale-round ledger append
# ---------------------------------------------------------------------------


def test_prefetch_producer_exits_when_abandoned_at_end_of_source():
    """Abandon the consumer with the queue full right as the source
    exhausts: the producer's terminal _END put must honor the cancel
    event instead of blocking forever (ADVICE finding 1)."""
    from elasticdl_tpu.data.dataset import Dataset

    before = set(threading.enumerate())
    ds = Dataset.from_generator(lambda: iter(range(3))).prefetch(1)
    it = iter(ds)
    assert next(it) == 0
    # producer now has the queue full (1) and item 2 pending; let it
    # reach the terminal put with the queue still full, then abandon
    time.sleep(0.1)
    it.close()
    deadline = time.time() + 10
    while time.time() < deadline:
        leaked = [
            t
            for t in set(threading.enumerate()) - before
            if t.is_alive()
        ]
        if not leaked:
            return
        time.sleep(0.05)
    raise AssertionError("prefetch producer thread leaked: %s" % leaked)


def test_prefetch_exception_put_honors_cancel():
    from elasticdl_tpu.data.dataset import Dataset

    def boom():
        yield 0
        yield 1
        raise RuntimeError("source failed")

    before = set(threading.enumerate())
    it = iter(Dataset.from_generator(boom).prefetch(1))
    assert next(it) == 0
    time.sleep(0.1)
    it.close()  # exception sentinel put must also give up
    deadline = time.time() + 10
    while time.time() < deadline:
        if not [
            t
            for t in set(threading.enumerate()) - before
            if t.is_alive()
        ]:
            return
        time.sleep(0.05)
    raise AssertionError("prefetch producer leaked after source error")


def test_escapable_call_returns_raises_and_times_out():
    """The daemon-thread escapable-call machinery the graft-entry device
    probe and the elastic trainer share (parallel/elastic.py)."""
    from elasticdl_tpu.parallel.elastic import EscapeTimeout, escapable_call

    assert escapable_call(lambda: 41 + 1) == 42
    with pytest.raises(ValueError, match="boom"):
        escapable_call(lambda: (_ for _ in ()).throw(ValueError("boom")))

    t0 = time.monotonic()
    with pytest.raises(EscapeTimeout):
        escapable_call(lambda: time.sleep(30), timeout=0.3)
    assert time.monotonic() - t0 < 5  # escaped, did not wait out the sleep

    # abort probe: fires after abort_after, escapes the wedged call
    with pytest.raises(EscapeTimeout):
        escapable_call(
            lambda: time.sleep(30),
            should_abort=lambda: True,
            abort_after=0.1,
            abort_interval=0.05,
        )


def test_record_stream_round_bump_during_get_task_hands_task_back():
    """requeue_inflight landing between the producer's get_task return
    and its ledger append must NOT leave the stale task in the cleared
    ledger (ADVICE finding 2): it is reported back instead."""
    from elasticdl_tpu.worker.task_data_service import TaskDataService

    class _Task:
        def __init__(self):
            self.task_id = 42
            self.shard_name = "shard"
            self.type = "TRAINING"
            self.start, self.end = 0, 8
            self.model_version = 0

    class _Worker:
        def __init__(self):
            self.reported = []
            self.service = None

        def get_task(self, task_type=None):
            task = _Task()
            # the race window: the round is abandoned while this task
            # is in flight back to the producer
            self.service.requeue_inflight("spare park")
            return task

        def report_task_result(self, task_id, err_msg="", exec_counters=None):
            self.reported.append((task_id, err_msg))

    import collections

    from elasticdl_tpu.data.input_stats import InputPlaneStats

    worker = _Worker()
    service = TaskDataService.__new__(TaskDataService)
    service._worker = worker
    service._ledger_lock = threading.Lock()
    service._stream_open = True
    service._parked_export_task = None
    service._clear_ledger()
    service._primed_task = None
    service._metadata_primed = True
    service._round_id = 0
    service._task_prefetch = 0
    service._fetcher = None
    service._ack_queue_size = 0
    service._ack_queue = collections.deque()
    service._ack_lock = threading.Lock()
    service.stats = InputPlaneStats()
    worker.service = service

    stream = service._record_stream()
    assert list(stream) == []  # producer stepped aside, no records
    assert not service._inflight  # nothing appended to the new round
    assert (42, "round abandoned (spare park)") in worker.reported
