"""Transformer LM: dp training, tp sharding rules, dp x tp x sp step.

Exercises the full TPU-native parallelism stack on the virtual 8-device
mesh: data-parallel training through AllReduceTrainer, parameter placement
by the tensor-parallel rules, and a fused train step over a 2x2x2
dp/model/seq mesh with ring attention.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.common.tensor import pytree_to_named_arrays
from elasticdl_tpu.nn.model_api import init_variables, split_variables
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.sharding import (
    param_spec,
    shard_batch_dp_sp,
    shard_params,
)
from elasticdl_tpu.parallel.trainer import AllReduceTrainer
from elasticdl_tpu.training.step import make_train_step
from model_zoo.transformer_lm import transformer_lm as zoo


def _tokens(b=8, l=32, vocab=128, seed=0):
    rng = np.random.default_rng(seed)
    # a learnable pattern: token t follows (t*3+1) % vocab
    start = rng.integers(0, vocab, size=(b, 1))
    seq = [start]
    for _ in range(l - 1):
        seq.append((seq[-1] * 3 + 1) % vocab)
    return np.concatenate(seq, axis=1).astype(np.int32)


def test_transformer_dp_training_learns():
    model = zoo.custom_model(vocab_size=128, num_layers=2)
    trainer = AllReduceTrainer(model, zoo.loss, zoo.optimizer(1e-2))
    tokens = _tokens()
    batch = {"tokens": tokens}
    losses = [float(trainer.train_step(batch, tokens)) for _ in range(30)]
    assert losses[-1] < losses[0] * 0.7, losses[::10]


def test_tp_param_specs_match_rules():
    mesh = create_mesh(
        {"data": 2, "model": 2, "seq": 2},
        axis_names=("data", "model", "seq"),
    )
    model = zoo.custom_model(vocab_size=64)
    variables = init_variables(
        model, jax.random.PRNGKey(0), {"tokens": np.zeros((1, 8), np.int32)}
    )
    params, _ = split_variables(variables)
    named = pytree_to_named_arrays(params)
    qspec = param_spec("block_0/query/kernel", mesh)
    assert "model" in qspec
    assert param_spec("embed/embedding", mesh)[0] == "model"
    assert param_spec("block_0/RMSNorm_0/scale", mesh) == ()
    # placement works for the real parameter tree
    sharded = shard_params(mesh, params)
    leaf = sharded["block_0"]["query"]["kernel"]
    assert "model" in str(leaf.sharding.spec)


def test_dp_tp_sp_fused_step():
    """One full train step over a 2x2x2 mesh with ring attention."""
    mesh = create_mesh(
        {"data": 2, "model": 2, "seq": 2},
        axis_names=("data", "model", "seq"),
    )
    model = zoo.custom_model(
        vocab_size=64,
        num_layers=1,
        mesh=mesh,
        seq_axis="seq",
    )
    tokens = _tokens(b=4, l=16, vocab=64)
    variables = init_variables(
        model, jax.random.PRNGKey(0), {"tokens": tokens}
    )
    params, state = split_variables(variables)
    opt = optax.sgd(0.01)
    from elasticdl_tpu.training.step import TrainState

    ts = TrainState.create(params, state, opt)
    ts = jax.tree_util.tree_map(np.asarray, ts)
    # place: params by tp rules, batch over data+seq
    ts = ts.replace(params=shard_params(mesh, ts.params))
    batch = shard_batch_dp_sp(
        mesh, {"tokens": tokens}, seq_sharded=True
    )
    labels = shard_batch_dp_sp(mesh, tokens, seq_sharded=True)
    step = make_train_step(model, zoo.loss, opt)
    with mesh:
        ts2, loss = step(ts, batch, labels, jax.random.PRNGKey(1))
    assert np.isfinite(float(loss))
    assert int(ts2.version) == 1

    # numerics match an unsharded single-device step
    model_1dev = zoo.custom_model(vocab_size=64, num_layers=1)
    ts_ref = TrainState.create(params, state, opt)
    step_ref = make_train_step(model_1dev, zoo.loss, opt)
    _, loss_ref = step_ref(ts_ref, {"tokens": tokens}, tokens, jax.random.PRNGKey(1))
    np.testing.assert_allclose(
        float(loss), float(loss_ref), rtol=2e-4
    )


def test_dataset_fn_takes_sequence_length_from_the_record():
    """The job path must be able to train at any length the records
    hold (the kernel takes over at 1024): no length constant in the zoo."""
    from elasticdl_tpu.common.constants import Mode
    from elasticdl_tpu.data.dataset import Dataset
    from elasticdl_tpu.data.example import encode_example

    for length in (64, 1024):
        records = [
            encode_example(
                {"tokens": np.arange(length, dtype=np.int64) + i}
            )
            for i in range(4)
        ]
        parsed = zoo.dataset_fn(
            Dataset.from_generator(lambda r=records: iter(r)),
            Mode.EVALUATION,
            None,
        )
        features, labels = next(iter(parsed.batch(4)))
        assert features["tokens"].shape == (4, length)
        assert features["tokens"].dtype == np.int32
        np.testing.assert_array_equal(labels[1], np.arange(length) + 1)
