"""Pipeline parallelism on the ELASTIC weighted step (pp x dp).

The multi-process elastic plane expresses every parallelism inside one
shard_map (a nested shard_map is impossible), so the pipeline ring runs
in its raw-collective form (parallel/pipeline.collective_pipeline_apply)
over a ("data", "pipe") mesh — the same recipe as the HBM embedding's
collective lookups. These tests pin the semantics single-process on the
virtual 8-device CPU mesh: the collective pp x dp step must match the
sequential (mesh=None) pipelined model trained on the plain elastic DP
step, exactly — same losses, same trained parameters — including the
weighted-elasticity cases (weight-0 devices, fractional tail weights).

The reference has no pipeline parallelism at all (SURVEY.md §2.2); its
elasticity premise "any worker can die anytime"
(reference master/task_dispatcher.py:247-255) is what the multi-process
rungs in tests/test_elastic_allreduce.py extend to this topology.
"""

import numpy as np
import pytest

import jax
import optax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.nn.model_api import init_variables, split_variables
from elasticdl_tpu.parallel.elastic import (
    build_state_specs,
    build_world_mesh,
    collect_sharded_paths,
    host_copy,
    make_elastic_train_step,
    place_from_host_specs,
)
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.training.step import TrainState
from model_zoo.transformer_lm import transformer_lm as zoo

VOCAB = 64
LENGTH = 8
MODEL_KW = dict(
    vocab_size=VOCAB,
    num_layers=2,
    num_heads=2,
    head_dim=8,
    embed_dim=16,
    mlp_dim=32,
    use_flash=False,
)


def _batches(n_steps, batch=16, seed=11):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_steps):
        ids = rng.integers(0, VOCAB, size=(batch, LENGTH)).astype(
            np.int32
        )
        out.append(({"tokens": ids}, ids))
    return out


def _init_state(model, example, opt):
    variables = init_variables(model, jax.random.PRNGKey(0), example)
    params, state = split_variables(variables)
    return TrainState.create(params, state, opt)


def _put_rows(mesh, tree, row_axes):
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(
            x,
            NamedSharding(
                mesh, P(*((row_axes,) + (None,) * (np.asarray(x).ndim - 1)))
            ),
        ),
        tree,
    )


def _run(mesh, model, specs, batches, weights, opt):
    """Drive the elastic step over ``batches``; returns (losses, ts)."""
    row_axes = (
        tuple(mesh.axis_names)
        if len(mesh.axis_names) > 1
        else mesh.axis_names[0]
    )
    ts_host = _init_state(model, batches[0][0], opt)
    if specs is not None:
        ts = place_from_host_specs(mesh, ts_host, specs)
    else:
        ts = jax.device_put(ts_host, NamedSharding(mesh, P()))
    step = make_elastic_train_step(
        model, zoo.loss, opt, mesh, state_specs=specs
    )
    w = jax.device_put(
        np.asarray(weights, np.float32),
        NamedSharding(mesh, P(row_axes)),
    )
    ep = jax.device_put(
        np.zeros(8, np.int32), NamedSharding(mesh, P(row_axes))
    )
    key = jax.random.PRNGKey(5)
    losses = []
    with mesh:
        for features, labels in batches:
            ts, loss, n, _, _ = step(
                ts,
                _put_rows(mesh, features, row_axes),
                _put_rows(mesh, labels, row_axes),
                w,
                ep,
                key,
            )
            losses.append(float(loss))
    return losses, ts


def _stacked_leaves(params):
    return {
        "/".join(str(k) for k in path): np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
    }


def _pp_setup(opt, example):
    mesh = create_mesh(
        {"data": 4, "pipe": 2}, axis_names=("data", "pipe")
    )
    model = zoo.build_collective_model(pipeline_stages=2, **MODEL_KW)
    sharded = collect_sharded_paths(
        zoo.param_shardings(mesh, pipeline_stages=2)
    )
    ts_probe = _init_state(model, example, opt)
    specs = build_state_specs(ts_probe, sharded)
    return mesh, model, specs


def test_collective_pp_dp_step_matches_sequential():
    """pp x dp on the elastic weighted step == the sequential pipelined
    model on the plain elastic DP step: same losses, same trained
    parameters (stage subtree included)."""
    opt = optax.sgd(0.05)
    batches = _batches(4)
    mesh, model, specs = _pp_setup(opt, batches[0][0])
    losses, ts = _run(mesh, model, specs, batches, np.ones(8), opt)

    seq_model = zoo.build_distributed_model(
        mesh=None, pipeline_stages=2, **MODEL_KW
    )
    seq_mesh = create_mesh({"data": 8}, axis_names=("data",))
    seq_losses, seq_ts = _run(
        seq_mesh, seq_model, None, batches, np.ones(8), opt
    )

    np.testing.assert_allclose(losses, seq_losses, rtol=2e-4, atol=1e-5)
    got = _stacked_leaves(jax.device_get(ts.params))
    want = _stacked_leaves(jax.device_get(seq_ts.params))
    assert got.keys() == want.keys()
    for k in got:
        np.testing.assert_allclose(
            got[k], want[k], rtol=5e-4, atol=2e-5, err_msg=k
        )


def test_collective_pp_dp_weighted_devices_match_sequential():
    """Per-device participation weights must mean the same thing on the
    pp x dp mesh as on the flat DP mesh: two weight-0 devices and one
    fractional tail weight, identical loss trajectory and parameters."""
    opt = optax.sgd(0.05)
    batches = _batches(3, seed=23)
    weights = np.array([1, 1, 0, 1, 0.25, 1, 0, 1], np.float32)
    mesh, model, specs = _pp_setup(opt, batches[0][0])
    losses, ts = _run(mesh, model, specs, batches, weights, opt)

    seq_model = zoo.build_distributed_model(
        mesh=None, pipeline_stages=2, **MODEL_KW
    )
    seq_mesh = create_mesh({"data": 8}, axis_names=("data",))
    seq_losses, seq_ts = _run(
        seq_mesh, seq_model, None, batches, weights, opt
    )
    np.testing.assert_allclose(losses, seq_losses, rtol=2e-4, atol=1e-5)
    got = _stacked_leaves(jax.device_get(ts.params))
    want = _stacked_leaves(jax.device_get(seq_ts.params))
    for k in got:
        np.testing.assert_allclose(
            got[k], want[k], rtol=5e-4, atol=2e-5, err_msg=k
        )


def test_collective_pp_drain_is_exact_noop():
    """All-zero weights: state passes through bit-identical and the
    version does not advance (drain-mode dummy steps)."""
    opt = optax.sgd(0.05)
    batches = _batches(1, seed=3)
    mesh, model, specs = _pp_setup(opt, batches[0][0])
    row_axes = tuple(mesh.axis_names)
    ts_host = _init_state(model, batches[0][0], opt)
    ts = place_from_host_specs(mesh, ts_host, specs)
    step = make_elastic_train_step(
        model, zoo.loss, opt, mesh, state_specs=specs
    )
    zeros = jax.device_put(
        np.zeros(8, np.float32), NamedSharding(mesh, P(row_axes))
    )
    ep = jax.device_put(
        np.zeros(8, np.int32), NamedSharding(mesh, P(row_axes))
    )
    # the step donates its state on this process-local mesh: copy what
    # the comparison needs to the host before the call
    version_before = int(host_copy(ts.version))
    params_before = jax.tree_util.tree_map(
        np.array, jax.device_get(ts.params)
    )
    with mesh:
        ts2, _, n, _, _ = step(
            ts,
            _put_rows(mesh, batches[0][0], row_axes),
            _put_rows(mesh, batches[0][1], row_axes),
            zeros,
            ep,
            jax.random.PRNGKey(1),
        )
    assert int(n) == 0
    assert int(host_copy(ts2.version)) == version_before
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(ts2.params)),
        jax.tree_util.tree_leaves(params_before),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_build_world_mesh_layouts():
    mesh = build_world_mesh(None)
    assert mesh.axis_names == ("data",)
    mesh = build_world_mesh(lambda n: {"data": n // 2, "pipe": 2})
    assert mesh.axis_names == ("data", "pipe")
    assert mesh.shape["pipe"] == 2
    with pytest.raises(ValueError):
        build_world_mesh(lambda n: {"data": 3, "pipe": 3})
