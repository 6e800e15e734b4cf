"""The hybrid comm plane (docs/embedding_planes.md).

Plane parity: the same deepfm batch through PS-only, HBM-only, and
hybrid planes must produce IDENTICAL lookups and dense gradients
(power-law duplicated ids included — the dedup planner's combined row
gradients must equal the dense scatter). Plus the overlap machinery's
abandonment contract (a requeued task's prefetched pull drops exactly
once), the per-table selector, the plane-shared hot-row cache, and the
master-channel shm reply path.
"""

import threading
import time

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from elasticdl_tpu.common.constants import JobType
from elasticdl_tpu.master.checkpoint_service import CheckpointService
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.nn.comm_plane import (
    EmbeddingPullPipeline,
    HbmPlane,
    HotRowCache,
    MasterStorePlane,
    PsPlane,
    make_embedding,
    resolve_table_planes,
)
from elasticdl_tpu.ps.parameters import Parameters
from elasticdl_tpu.ps.servicer import PserverServicer
from elasticdl_tpu.worker.ps_client import PSClient
from elasticdl_tpu.worker.worker import Worker
from tests.in_process_master import InProcessMaster
from tests.test_utils import MODEL_ZOO_PATH

VOCAB, DIM, BATCH = 96, 16, 64
MODEL_DEF = "deepfm_edl_embedding.deepfm_edl_embedding.custom_model"


def _powerlaw_batch(seed=11):
    rng = np.random.default_rng(seed)
    pool = rng.permutation(VOCAB)[:24]
    weights = 1.0 / np.arange(1, 25) ** 1.1
    weights /= weights.sum()
    features = {
        "feature": rng.choice(pool, size=(BATCH, 10), p=weights).astype(
            np.int64
        )
    }
    labels = rng.integers(0, 2, size=(BATCH, 1)).astype(np.int32)
    return features, labels


def _servicers(n=2):
    return [
        PserverServicer(
            Parameters(),
            grads_to_wait=1,
            optimizer=optax.sgd(0.1),
            use_async=True,
        )
        for _ in range(n)
    ]


def _make_worker(servicers, zoo_plane, worker_plane, **kwargs):
    return Worker(
        worker_id=1,
        job_type=JobType.TRAINING_ONLY,
        minibatch_size=BATCH,
        model_zoo=MODEL_ZOO_PATH,
        model_def=MODEL_DEF,
        model_params="embedding_dim=%d,fc_unit=16,vocab_size=%d,"
        "embedding_plane='%s'" % (DIM, VOCAB, zoo_plane),
        ps_client=PSClient(servicers),
        embedding_plane=worker_plane,
        embedding_prefetch=kwargs.pop("embedding_prefetch", False),
        **kwargs,
    )


# ---------------------------------------------------------------------------
# per-table plane selection
# ---------------------------------------------------------------------------


def test_resolve_table_planes_forms():
    tables = ["embedding", "id_bias"]
    assert resolve_table_planes("ps", tables) == {
        "embedding": "ps",
        "id_bias": "ps",
    }
    assert resolve_table_planes("hbm", tables) == {
        "embedding": "hbm",
        "id_bias": "hbm",
    }
    split = {"embedding": "ps", "id_bias": "hbm"}
    assert resolve_table_planes("hybrid", tables, split) == split
    assert resolve_table_planes("id_bias:hbm", tables) == {
        "embedding": "ps",
        "id_bias": "hbm",
    }
    assert resolve_table_planes(
        "embedding:hbm/id_bias:ps", tables
    ) == {"embedding": "hbm", "id_bias": "ps"}


def test_resolve_table_planes_rejects_bad_specs():
    with pytest.raises(ValueError, match="hybrid"):
        resolve_table_planes("hybrid", ["t"], hybrid_default=None)
    with pytest.raises(ValueError, match="missing tables"):
        resolve_table_planes("hybrid", ["a", "b"], {"a": "ps"})
    with pytest.raises(ValueError, match="unknown table"):
        resolve_table_planes("nope:ps", ["a"])
    with pytest.raises(ValueError, match="bad embedding_plane entry"):
        resolve_table_planes("a=ps", ["a"])


def test_make_embedding_factory():
    from elasticdl_tpu.nn.embedding import Embedding
    from elasticdl_tpu.nn.hbm_embedding import HbmEmbedding

    ps_layer = make_embedding("ps", output_dim=8, name="t")
    assert isinstance(ps_layer, Embedding)
    hbm_layer = make_embedding(
        "hbm", output_dim=8, name="t", vocab_size=32
    )
    assert isinstance(hbm_layer, HbmEmbedding)
    with pytest.raises(ValueError, match="vocab_size"):
        make_embedding("hbm", output_dim=8, name="t")
    with pytest.raises(ValueError, match="unknown embedding plane"):
        make_embedding("redis", output_dim=8, name="t")


def test_zoo_param_shardings_follow_planes():
    from model_zoo.deepfm_edl_embedding import deepfm_edl_embedding as zoo

    legacy = zoo.param_shardings(None)
    assert set(legacy) == {"embedding", "id_bias"}
    hybrid = zoo.param_shardings(None, embedding_plane="hybrid")
    assert set(hybrid) == {"id_bias"}  # the ps table is not a parameter
    assert zoo.param_shardings(None, embedding_plane="ps") == {}


def test_allreduce_worker_refuses_ps_plane_tables():
    """The collective plane cannot pull per-batch rows; the guard must
    fire at worker construction with a pointer to the hybrid trainer,
    not deep inside establish (crash-loop under relaunch)."""
    from elasticdl_tpu.worker.elastic_allreduce_worker import (
        ElasticAllReduceWorker,
    )

    # "embedding:hbm" leaves the UNLISTED id_bias table on its ps
    # default — the guard must resolve the spec, not string-sniff it
    for spec in (
        "ps",
        "hybrid",
        "embedding:ps/id_bias:hbm",
        "embedding:hbm",
    ):
        with pytest.raises(NotImplementedError, match="PS plane"):
            ElasticAllReduceWorker(
                worker_id=1,
                job_type=JobType.TRAINING_ONLY,
                minibatch_size=4,
                model_zoo=MODEL_ZOO_PATH,
                model_def=MODEL_DEF,
                model_params="embedding_plane='%s'" % spec,
            )


def test_hybrid_worker_rejects_serving_only_jobs():
    """Hybrid's local replica is populated BY training; an eval- or
    predict-only hybrid worker would silently score random init."""
    for job_type in (JobType.EVALUATION_ONLY, JobType.PREDICTION_ONLY):
        with pytest.raises(ValueError, match="training job"):
            Worker(
                worker_id=1,
                job_type=job_type,
                minibatch_size=4,
                model_zoo=MODEL_ZOO_PATH,
                model_def=MODEL_DEF,
                ps_client=PSClient(_servicers()),
                embedding_plane="hybrid",
            )


def test_zoo_collective_refuses_ps_tables():
    from model_zoo.deepfm_edl_embedding import deepfm_edl_embedding as zoo

    model = zoo.DeepFMEdl(
        embedding_dim=8,
        fc_unit=8,
        vocab_size=32,
        embedding_plane="hybrid",
        collective=True,
    )
    features = {"feature": np.zeros((2, 10), np.int64)}
    with pytest.raises(ValueError, match="PS plane"):
        model.init(jax.random.PRNGKey(0), features)


# ---------------------------------------------------------------------------
# plane parity: identical lookups + dense gradients across all three
# ---------------------------------------------------------------------------


def test_plane_parity_ps_hbm_hybrid():
    """One batch, one common initialization, three planes: PS-only and
    hybrid through workers against ONE shared store, HBM-only as the
    dense twin with tables seeded from the same store rows.

    PS vs hybrid is BITWISE (same bucket-gather graph for the
    PS-resident table: the hybrid plane moves bytes, not numerics);
    the HBM-only twin's LOOKUPS are bitwise too, while its logits and
    gradients agree to float tolerance only — its full-table take
    changes downstream XLA fusion, which reassociates the final
    reductions (~1e-8)."""
    features, labels = _powerlaw_batch()
    servicers = _servicers()

    wp = _make_worker(servicers, "ps", "ps")
    wh = _make_worker(servicers, "hybrid", "hybrid")
    wp._run_model_call_before_training(features)
    wh._run_model_call_before_training(features)
    for key in ("Dense_0", "Dense_1"):
        wh._params[key] = wp._params[key]
    all_ids = np.arange(VOCAB)
    bias_rows = np.asarray(
        wp._ps_client.pull_embedding_vectors("id_bias", all_ids),
        np.float32,
    )
    emb_rows = np.asarray(
        wp._ps_client.pull_embedding_vectors("embedding", all_ids),
        np.float32,
    )
    wh._params["id_bias"]["table"] = jnp.asarray(bias_rows)

    # the HBM-only twin: same graph with BOTH tables as parameters
    from model_zoo.deepfm_edl_embedding import deepfm_edl_embedding as zoo
    from elasticdl_tpu.nn.model_api import init_variables, split_variables
    from elasticdl_tpu.training.step import make_grad_fn

    twin = zoo.DeepFMEdl(
        embedding_dim=DIM,
        fc_unit=16,
        vocab_size=VOCAB,
        embedding_plane="hbm",
    )
    t_params, t_state = split_variables(
        init_variables(twin, jax.random.PRNGKey(0), features)
    )
    for key in ("Dense_0", "Dense_1"):
        t_params[key] = wp._params[key]
    t_params["embedding"]["table"] = jnp.asarray(emb_rows)
    t_params["id_bias"]["table"] = jnp.asarray(bias_rows)

    # lookups: the PS plane's gathered rows == the twin's table take,
    # bitwise (power-law duplicate ids and all)
    from elasticdl_tpu.nn.embedding import flatten_collection

    rows_tree, idx_tree, _ = wp._prepare_embedding_batch(features)
    ids = features["feature"].astype(np.int32)
    for name, dim in (("embedding", DIM), ("id_bias", 1)):
        rows = flatten_collection(rows_tree, "rows")[(name,)]
        idx = flatten_collection(idx_tree, "idx")[
            (name, "_CallSlot_0")
        ]
        ps_lookup = rows[idx]
        twin_lookup = np.asarray(
            jnp.take(t_params[name]["table"], ids, axis=0)
        )
        assert np.array_equal(ps_lookup, twin_lookup), name

    fp = wp.forward_process(features)
    fh = wh.forward_process(features)
    twin_out = twin.apply({"params": t_params, **t_state}, features)
    assert np.array_equal(
        np.asarray(fp["logits"]), np.asarray(fh["logits"])
    )
    np.testing.assert_allclose(
        np.asarray(fp["logits"]),
        np.asarray(twin_out["logits"]),
        rtol=1e-6,
        atol=1e-6,
    )

    lp, gp, sp = wp.training_process(features, labels)
    lh, gh, sh = wh.training_process(features, labels)
    rng = jax.random.fold_in(
        jax.random.PRNGKey(0 * 100003 + 1), 1
    )  # the workers' step-1 rng (seed=0, worker_id=1)
    lt, gt, _, _ = make_grad_fn(twin, zoo.loss)(
        t_params, t_state, features, labels, rng
    )

    assert float(lp) == float(lh)
    np.testing.assert_allclose(float(lp), float(lt), rtol=1e-6)
    for key in ("Dense_0", "Dense_1"):
        for leaf in gp[key]:
            a = np.asarray(gp[key][leaf])
            assert np.array_equal(a, np.asarray(gh[key][leaf]))
            np.testing.assert_allclose(
                a, np.asarray(gt[key][leaf]), rtol=1e-5, atol=1e-6
            )

    sp_by = {t.name: t for t in sp}
    sh_by = {t.name: t for t in sh}
    # hybrid pushes only the ps-resident table
    assert sorted(sp_by) == ["embedding", "id_bias"]
    assert sorted(sh_by) == ["embedding"]
    assert np.array_equal(
        sp_by["embedding"].values, sh_by["embedding"].values
    )
    assert np.array_equal(
        sp_by["embedding"].indices, sh_by["embedding"].indices
    )

    # sparse row grads == the dense twin's table grads, scattered
    # (float tolerance vs the twin's differently-fused graph; the
    # hybrid arm's dense bias-table grad matches the PS arm's
    # scattered sparse rows BITWISE — same graph family)
    for name, dim in (("embedding", DIM), ("id_bias", 1)):
        scattered = np.zeros((VOCAB, dim), np.float32)
        t = sp_by[name]
        scattered[np.asarray(t.indices)] = np.asarray(t.values)
        np.testing.assert_allclose(
            scattered,
            np.asarray(gt[name]["table"]),
            rtol=1e-5,
            atol=1e-6,
            err_msg=name,
        )
    bias_scatter = np.zeros((VOCAB, 1), np.float32)
    bias_scatter[np.asarray(sp_by["id_bias"].indices)] = np.asarray(
        sp_by["id_bias"].values
    )
    assert np.array_equal(
        bias_scatter, np.asarray(gh["id_bias"]["table"])
    )
    for worker in (wp, wh):
        worker._ps_client.close()


# ---------------------------------------------------------------------------
# the overlapped pull: staging, consumption, abandonment
# ---------------------------------------------------------------------------


def test_pipeline_consume_returns_staged_pull():
    pipe = EmbeddingPullPipeline()
    key = object()
    pipe.submit(key, "plan", lambda: {"t": np.ones(3)})
    plan, pulled = pipe.consume(key)
    assert plan == "plan" and np.array_equal(pulled["t"], np.ones(3))
    assert pipe.served == 1
    assert pipe.consume(key) is None  # one-shot
    pipe.close()


def test_pipeline_invalidate_drops_exactly_once():
    """The round-abandonment race pin: a requeued task's prefetched
    pull is dropped exactly once — invalidate waits the in-flight pull
    out, a second invalidate (or a consume after it) finds nothing."""
    pipe = EmbeddingPullPipeline()
    release = threading.Event()
    pulled = []

    def slow_pull():
        release.wait(5.0)
        pulled.append(True)
        return {"t": np.zeros(1)}

    key = object()
    pipe.submit(key, "plan", slow_pull)
    dropper = {}

    def invalidate():
        dropper["n"] = pipe.invalidate()

    t = threading.Thread(target=invalidate)
    t.start()
    time.sleep(0.05)
    assert t.is_alive()  # invalidate waits for the in-flight pull
    release.set()
    t.join(5.0)
    assert dropper["n"] == 1
    assert pulled == [True]  # the pull finished (no RPC left mid-air)
    assert pipe.dropped == 1
    assert pipe.invalidate() == 0  # exactly once
    assert pipe.consume(key) is None
    pipe.close()


def test_pipeline_depth_evicts_oldest():
    pipe = EmbeddingPullPipeline(depth=2)
    keys = [object() for _ in range(3)]
    for i, key in enumerate(keys):
        pipe.submit(key, i, lambda i=i: i)
    assert pipe.consume(keys[0]) is None  # evicted (and counted dropped)
    assert pipe.consume(keys[1]) == (1, 1)
    assert pipe.consume(keys[2]) == (2, 2)
    assert pipe.dropped == 1
    pipe.close()


def test_pipeline_failed_pull_surfaces_at_consume():
    pipe = EmbeddingPullPipeline()
    key = object()

    def boom():
        raise RuntimeError("shard died")

    pipe.submit(key, "plan", boom)
    with pytest.raises(RuntimeError, match="shard died"):
        pipe.consume(key)
    pipe.close()


def _run_hybrid_job(tmp_path, fail_on_call=None):
    """A small hybrid training job over in-process PS servicers;
    optionally inject one failing minibatch (task requeues)."""
    from elasticdl_tpu.data.example import encode_example
    from elasticdl_tpu.data.recordio import RecordIOWriter

    records, batch = 64, 16
    rng = np.random.default_rng(5)
    path = str(tmp_path / "hy.edlr")
    with RecordIOWriter(path) as w:
        for _ in range(records):
            w.write(
                encode_example(
                    {
                        "feature": rng.integers(
                            0, VOCAB, size=(10,)
                        ).astype(np.int64),
                        "label": np.array(
                            [rng.integers(0, 2)], np.int64
                        ),
                    }
                )
            )
    servicers = _servicers()
    task_d = TaskDispatcher({path: (0, records)}, {}, {}, batch, 2)
    master = MasterServicer(
        1,
        batch,
        None,
        task_d,
        checkpoint_service=CheckpointService("", 0, 0, False),
        use_async=True,
    )
    client = PSClient(servicers, push_inflight=1)
    worker = Worker(
        worker_id=1,
        job_type=JobType.TRAINING_ONLY,
        minibatch_size=batch,
        model_zoo=MODEL_ZOO_PATH,
        model_def=MODEL_DEF,
        model_params="embedding_dim=%d,fc_unit=8,vocab_size=%d,"
        "embedding_plane='hybrid'" % (DIM, VOCAB),
        ps_client=client,
        embedding_plane="hybrid",
    )
    worker._stub = InProcessMaster(master)
    if fail_on_call is not None:
        orig = worker._run_training_task
        state = {"calls": 0}

        def flaky(features, labels):
            state["calls"] += 1
            if state["calls"] == fail_on_call:
                raise RuntimeError("injected minibatch failure")
            return orig(features, labels)

        worker._run_training_task = flaky
    try:
        worker.run()
        rows = client.pull_embedding_vectors(
            "embedding", np.arange(VOCAB)
        )
    finally:
        client.close()
    return worker, task_d, rows


def test_hybrid_job_end_to_end(tmp_path):
    worker, task_d, rows = _run_hybrid_job(tmp_path)
    assert task_d.finished()
    # the overlapped pull actually served batches
    assert worker._emb_pipeline.served > 0
    assert worker._emb_pipeline.dropped == 0
    # dense half trained locally; sparse table landed on the PS
    assert worker._model_version > 0
    bias = np.asarray(worker._params["id_bias"]["table"])
    assert bias.shape == (VOCAB, 1) and np.abs(bias).sum() > 0
    assert rows.shape == (VOCAB, DIM) and np.isfinite(rows).all()


def test_hybrid_requeued_task_drops_prefetched_pull_once(tmp_path):
    """A failed minibatch requeues its task, and every pull staged at
    that moment — the failed batch's own (it never reached compute)
    and the lookahead batch's — is dropped EXACTLY ONCE: two pending
    entries, two drops, no double-count, nothing served later. The
    job still completes; the requeued records re-run with fresh
    inline pulls."""
    worker, task_d, _ = _run_hybrid_job(tmp_path, fail_on_call=3)
    assert task_d.finished()
    assert worker._emb_pipeline.dropped == 2
    assert worker._emb_pipeline.served > 0
    # nothing left staged after the run (a leak would hold PS rows)
    assert worker._emb_pipeline.invalidate() == 0


# ---------------------------------------------------------------------------
# plane objects + the shared cache
# ---------------------------------------------------------------------------


def test_ps_plane_shares_external_cache():
    from tests.fake_ps import TablePS

    shared = HotRowCache(64, window=2)
    client = PSClient([TablePS(dim=4), TablePS(dim=4)], cache=shared)
    plane = PsPlane(client)
    assert plane.cache is shared
    assert HbmPlane(shared_cache=shared).cache is shared
    # the plane's pull fills the shared cache
    rows = plane.pull({"embedding": np.array([1, 2, 3], np.int64)})
    assert rows["embedding"].shape[0] == 3
    assert len(shared) == 3
    # a second pull through the plane serves from the shared cache
    before = shared.hits
    plane.pull({"embedding": np.array([1, 2, 3], np.int64)})
    assert shared.hits > before
    client.close()


def test_invalidate_table_spares_unrelated_tables():
    """The serving-plane cache fix (ISSUE 15): delta sync's
    whole-table fallback must drop ONLY the named table's stale rows —
    ``invalidate_shard`` was the only reset path before, and it evicts
    every co-sharded table's hot rows plus re-anchors the shard clock
    for what is not a relaunch."""
    cache = HotRowCache(64, window=4)
    for i in range(4):
        cache.put("a", i, 0, 10 + i, np.full(2, i, np.float32))
        cache.put("b", i, 0, 10 + i, np.full(2, 100 + i, np.float32))
    cache.put("a", 9, 1, 3, np.zeros(2, np.float32))  # other shard
    # version-bounded drop: only a's entries below 12 go
    assert cache.invalidate_table("a", below_version=12) == 3  # 10,11,3
    assert cache.get("a", 2) is not None  # tagged 12: kept
    assert cache.get("a", 3) is not None  # tagged 13: kept
    assert cache.get("a", 0) is None
    assert cache.get("a", 9) is None  # cross-shard entries drop too
    # b is untouched — every row still hittable
    assert all(
        r is not None for r in cache.get_rows("b", list(range(4)))
    )
    # and the shard version clock was NOT re-anchored: aging still
    # works off the versions the cache had seen, per entry
    cache.note_version(0, 16)
    assert cache.get("b", 0) is None  # tag 10, lag 6 > window: ages out
    assert cache.get("b", 3) is not None  # tag 13, lag 3: still fresh
    # unbounded form drops everything left of a, and only a
    assert cache.invalidate_table("a") == 2  # the kept 12 and 13
    assert cache.get("b", 3) is not None


def test_master_store_plane_pulls_per_table():
    store = {}

    class Stub:
        def pull_embedding_vectors(self, name, ids):
            store.setdefault(name, 0)
            store[name] += 1
            return np.ones((len(ids), 4), np.float32)

    plane = MasterStorePlane(lambda: Stub())
    out = plane.pull(
        {"a": np.array([1, 2]), "b": np.array([3, 4, 5])}
    )
    assert out["a"].shape == (2, 4) and out["b"].shape == (3, 4)
    assert store == {"a": 1, "b": 1}
    with pytest.raises(NotImplementedError):
        plane.push([], 0)


def test_hbm_plane_is_in_graph_only():
    plane = HbmPlane()
    assert plane.in_graph
    with pytest.raises(RuntimeError, match="jitted step"):
        plane.pull({"t": np.array([1])})
    with pytest.raises(RuntimeError, match="jitted step"):
        plane.push([], 0)
    # the planner is still the shared host-side one
    unique, idxs, bucket = plane.plan_lookup_multi(
        [np.array([5, 5, 7])]
    )
    assert list(unique) == [5, 7] and bucket == 8


# ---------------------------------------------------------------------------
# master-channel shm (get_model replies)
# ---------------------------------------------------------------------------


def _serve_master_with_shm():
    from elasticdl_tpu.master.rpc_service import MasterRpcService
    from elasticdl_tpu.rpc.core import serve
    from elasticdl_tpu.rpc.shm_transport import install_shm_endpoint

    task_d = TaskDispatcher({"f": (0, 16)}, {}, {}, 16, 1)
    master = MasterServicer(
        1,
        16,
        optax.sgd(0.1),
        task_d,
        checkpoint_service=CheckpointService("", 0, 0, False),
        use_async=True,
    )
    master.report_variable(
        {"w": np.arange(12, dtype=np.float32).reshape(3, 4)}
    )
    methods, registry = install_shm_endpoint(
        MasterRpcService(master).rpc_methods()
    )
    server = serve(methods, 0)
    return server, registry, "localhost:%d" % server._edl_port


def test_master_channel_get_model_rides_shm():
    from elasticdl_tpu.master.rpc_service import MasterClient

    server, registry, addr = _serve_master_with_shm()
    client = MasterClient(addr, shm="auto")
    try:
        version, named = client.get_model(0)
        assert client._shm.state == "on"
        assert client._shm.stats["shm"] == 1
        # retained params were materialized off the recycled slot
        assert named["w"].flags.writeable
        first = named["w"].copy()
        _, named2 = client.get_model(0)  # recycles + reuses the slot
        assert np.array_equal(named["w"], first)
        assert np.array_equal(named2["w"], first)
        # non-model RPCs stay on the bytes path (request-retention
        # safety: the master servicer was never audited for slot reuse)
        client.get_task(1)
        assert client._shm.stats["shm"] == 2
    finally:
        client.close()
        registry.close()
        server.stop(grace=None)


def test_master_channel_shm_cross_host_falls_back(monkeypatch):
    from elasticdl_tpu.master.rpc_service import MasterClient
    from elasticdl_tpu.rpc import shm_transport

    server, registry, addr = _serve_master_with_shm()
    # client advertises a foreign fingerprint: server declines, channel
    # stays on the bytes path forever, results identical
    monkeypatch.setattr(
        shm_transport,
        "host_fingerprint",
        lambda: "elsewhere|not-this-boot",
    )
    client = MasterClient(addr, shm="auto")
    try:
        version, named = client.get_model(0)
        assert client._shm.state == "off"
        assert np.array_equal(
            named["w"], np.arange(12, dtype=np.float32).reshape(3, 4)
        )
    finally:
        client.close()
        registry.close()
        server.stop(grace=None)


def test_bytes_path_get_model_stays_zero_copy():
    from elasticdl_tpu.master.rpc_service import MasterClient

    server, registry, addr = _serve_master_with_shm()
    client = MasterClient(addr, shm="off")
    try:
        _, named = client.get_model(0)
        # the advisory gRPC-bytes arena keeps the zero-copy contract:
        # read-only views pinned to the reply buffer
        assert not named["w"].flags.writeable
    finally:
        client.close()
        registry.close()
        server.stop(grace=None)
