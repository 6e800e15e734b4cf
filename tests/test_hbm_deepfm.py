"""HBM-sharded DeepFM as a training strategy (BASELINE.json north
star): tables row-sharded over mesh HBM, all_to_all
row routing, sparse update inside the jitted step, checkpointed through
the params pytree.

Equivalence target: the host-PS elastic-embedding plane applies row-sparse
optax updates that are exactly dense-SGD-on-touched-rows
(tests/test_ps_store.py proves store==dense per step), so HBM-sharded
training is validated against the same dense reference: an unsharded
``jnp.take`` DeepFM trained on the identical batch stream must produce
the same tables.
"""

import jax
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from elasticdl_tpu.common.constants import JobType
from elasticdl_tpu.master.checkpoint_service import CheckpointService
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.nn.model_api import init_variables, split_variables
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.training.step import TrainState, make_train_step
from elasticdl_tpu.worker.allreduce_worker import AllReduceWorker
from model_zoo.deepfm_edl_embedding import deepfm_edl_embedding as zoo
from tests.in_process_master import InProcessMaster
from tests.test_utils import MODEL_ZOO_PATH, DatasetName, create_recordio_file

VOCAB = 96


def _batches(n_steps, batch=16, length=10, seed=3):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_steps):
        ids = rng.integers(0, VOCAB, size=(batch, length))
        labels = rng.integers(0, 2, size=(batch, 1)).astype(np.int64)
        out.append(({"feature": ids.astype(np.int64)}, labels))
    return out


def _train(model, batches, params, state):
    opt = optax.sgd(0.05)
    ts = TrainState.create(params, state, opt)
    step = make_train_step(model, zoo.loss, opt)
    key = jax.random.PRNGKey(0)
    for features, labels in batches:
        ts, _ = step(ts, features, labels, key)
    return jax.tree_util.tree_map(np.asarray, ts.params)


def test_hbm_deepfm_matches_dense_training():
    """10 jitted steps, tables sharded over the 8-device mesh with a2a
    routing == the same model with a plain dense take."""
    mesh = create_mesh({"data": 8}, axis_names=("data",))
    batches = _batches(10)

    dense_model = zoo.DeepFMEdl(
        embedding_dim=8, fc_unit=8, vocab_size=VOCAB, force_hbm=True
    )
    hbm_model = zoo.build_distributed_model(
        mesh, embedding_dim=8, fc_unit=8, vocab_size=VOCAB
    )
    assert hbm_model.mesh is mesh

    variables = init_variables(
        hbm_model, jax.random.PRNGKey(0), batches[0][0]
    )
    params, state = split_variables(variables)
    # identical init for the dense twin: same param tree applies (both
    # are HbmEmbedding under different lookup paths)
    dense_variables = init_variables(
        dense_model, jax.random.PRNGKey(0), batches[0][0]
    )
    dense_params, dense_state = split_variables(dense_variables)
    for a, b in zip(
        jax.tree_util.tree_leaves(params),
        jax.tree_util.tree_leaves(dense_params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # place sharded per the zoo's param_shardings hook (specs may be
    # PadDim0-wrapped; collect_sharded_paths unwraps)
    from elasticdl_tpu.parallel.elastic import collect_sharded_paths

    specs = collect_sharded_paths(zoo.param_shardings(mesh))
    placed = jax.tree_util.tree_map(jax.device_put, params)
    for layer in ("embedding", "id_bias"):
        placed[layer]["table"] = jax.device_put(
            params[layer]["table"],
            NamedSharding(mesh, specs[(layer, "table")]),
        )

    with mesh:
        got = _train(hbm_model, batches, placed, state)
    want = _train(dense_model, batches, dense_params, dense_state)
    for (path_a, a), (path_b, b) in zip(
        jax.tree_util.tree_flatten_with_path(got)[0],
        jax.tree_util.tree_flatten_with_path(want)[0],
    ):
        assert path_a == path_b
        np.testing.assert_allclose(
            a, b, rtol=2e-4, atol=2e-5, err_msg=str(path_a)
        )


def test_hbm_table_gradient_stays_sharded():
    """The table gradient must carry the table's sharding — no device
    ever holds the dense (V, D) gradient."""
    mesh = create_mesh({"data": 8}, axis_names=("data",))
    model = zoo.build_distributed_model(
        mesh, embedding_dim=8, fc_unit=8, vocab_size=VOCAB
    )
    batch = _batches(1)
    features, labels = batch[0]
    variables = init_variables(model, jax.random.PRNGKey(0), features)
    params, state = split_variables(variables)
    spec = NamedSharding(mesh, P("data", None))
    params["embedding"]["table"] = jax.device_put(
        np.asarray(params["embedding"]["table"]), spec
    )
    params["id_bias"]["table"] = jax.device_put(
        np.asarray(params["id_bias"]["table"]), spec
    )

    @jax.jit
    def grads_of(p):
        def loss_fn(pp):
            out = model.apply(
                {"params": pp, **state}, features, training=True
            )
            return zoo.loss(out, labels)

        return jax.grad(loss_fn)(p)

    with mesh:
        g = grads_of(params)
    g_table = g["embedding"]["table"]
    assert g_table.sharding.is_equivalent_to(spec, g_table.ndim)
    # each device's shard is (V/8, D) — the dense (V, D) grad never
    # materializes on any single device
    shard_shapes = {s.data.shape for s in g_table.addressable_shards}
    assert shard_shapes == {(VOCAB // 8, 8)}


def test_allreduce_worker_trains_hbm_deepfm_e2e():
    """Full task-driven job through AllReduceWorker: the zoo hooks swap
    in the HBM model, tables shard, job completes, checkpoint-able host
    state comes back through the params pytree."""
    f = create_recordio_file(128, DatasetName.FRAPPE, 10)
    task_d = TaskDispatcher({f: (0, 128)}, {}, {}, 64, 1)
    master = MasterServicer(
        1,
        16,
        None,
        task_d,
        checkpoint_service=CheckpointService("", 0, 0, False),
        use_async=True,
    )
    worker = AllReduceWorker(
        worker_id=0,
        job_type=JobType.TRAINING_ONLY,
        minibatch_size=16,
        model_zoo=MODEL_ZOO_PATH,
        model_def=(
            "deepfm_edl_embedding.deepfm_edl_embedding.custom_model"
        ),
        model_params="embedding_dim=8,fc_unit=8",
        stub=InProcessMaster(master),
    )
    losses = worker.run()
    assert task_d.finished()
    assert losses and all(np.isfinite(losses))
    # the distributed hooks took effect: tables are mesh-sharded params
    ts = worker.trainer.train_state
    table = ts.params["embedding"]["table"]
    assert len(table.sharding.device_set) == 8
    assert table.shape[0] == zoo.VOCAB_SIZE
    # host state (the checkpoint source) round-trips the sharded table
    host = worker.trainer.get_host_state()
    assert np.asarray(host.params["embedding"]["table"]).shape == (
        zoo.VOCAB_SIZE,
        8,
    )
