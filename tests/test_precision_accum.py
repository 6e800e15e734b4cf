"""Gradient accumulation and the mixed-precision policy (training/step.py,
training/precision.py).

The reference has neither: it trains f32 with whatever batch fits
(reference worker.py:545-568). These pin the TPU-side contracts: an
accumulated step equals the full-batch step, microbatch activation
bounding via scan, and the f32-master / bf16-compute / f32-loss split.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen as nn

from elasticdl_tpu.nn.model_api import init_variables, split_variables
from elasticdl_tpu.training.precision import Policy, get_policy
from elasticdl_tpu.training.step import TrainState, make_train_step


class _MLP(nn.Module):
    @nn.compact
    def __call__(self, features, training=False):
        x = features["x"]
        x = nn.Dense(16)(x)
        x = nn.relu(x)
        return nn.Dense(4)(x)


def _mse(output, labels):
    return jnp.mean((output - labels) ** 2)


def _setup(seed=0, batch=16):
    model = _MLP()
    rng = np.random.default_rng(seed)
    features = {"x": rng.standard_normal((batch, 8)).astype(np.float32)}
    labels = rng.standard_normal((batch, 4)).astype(np.float32)
    variables = init_variables(model, jax.random.PRNGKey(0), features)
    params, state = split_variables(variables)
    opt = optax.sgd(0.05)
    ts = TrainState.create(params, state, opt)
    return model, features, labels, opt, ts


class TestGradAccumulation:
    def test_accumulated_step_equals_full_batch_step(self):
        model, features, labels, opt, ts = _setup()
        plain = make_train_step(model, _mse, opt)
        accum = make_train_step(model, _mse, opt, accum_steps=4)
        key = jax.random.PRNGKey(1)
        ts_a, loss_a = plain(ts, features, labels, key)
        *_, ts2 = _setup()
        ts_b, loss_b = accum(ts2, features, labels, key)
        # mean-of-microbatch-means == full-batch mean for equal micros
        np.testing.assert_allclose(
            float(loss_a), float(loss_b), rtol=1e-5
        )
        for pa, pb in zip(
            jax.tree_util.tree_leaves(ts_a.params),
            jax.tree_util.tree_leaves(ts_b.params),
        ):
            np.testing.assert_allclose(
                np.asarray(pa), np.asarray(pb), rtol=1e-5, atol=1e-6
            )
        assert int(ts_b.version) == 1

    def test_indivisible_batch_rejected(self):
        model, features, labels, opt, ts = _setup(batch=10)
        accum = make_train_step(model, _mse, opt, accum_steps=4)
        with pytest.raises(ValueError, match="not divisible"):
            accum(ts, features, labels, jax.random.PRNGKey(1))

    def test_state_threads_through_microbatches(self):
        """A batch-stat collection must see every microbatch once."""

        class Counting(nn.Module):
            @nn.compact
            def __call__(self, features, training=False):
                count = self.variable(
                    "batch_stats", "count", lambda: jnp.float32(0.0)
                )
                if training:
                    count.value = count.value + 1.0
                return nn.Dense(2)(features["x"])

        model = Counting()
        features = {"x": np.ones((8, 3), np.float32)}
        labels = np.zeros((8, 2), np.float32)
        variables = init_variables(model, jax.random.PRNGKey(0), features)
        params, state = split_variables(variables)
        opt = optax.sgd(0.01)
        ts = TrainState.create(params, state, opt)
        step = make_train_step(model, _mse, opt, accum_steps=4)
        ts, _ = step(ts, features, labels, jax.random.PRNGKey(1))
        assert float(ts.state["batch_stats"]["count"]) == 4.0


class TestPrecisionPolicy:
    def test_presets_and_unknown_name(self):
        pol = get_policy("mixed_bfloat16")
        assert pol.param_dtype == jnp.float32
        assert pol.compute_dtype == jnp.bfloat16
        assert get_policy(None) is None
        assert get_policy(pol) is pol
        with pytest.raises(ValueError, match="unknown precision"):
            get_policy("float8_dream")

    def test_cast_rules_skip_integers(self):
        pol = Policy()
        tree = {"w": jnp.ones((2, 2), jnp.float32), "ids": jnp.arange(3)}
        out = pol.cast_to_compute(tree)
        assert out["w"].dtype == jnp.bfloat16
        assert out["ids"].dtype == tree["ids"].dtype

    def test_mixed_step_keeps_f32_masters_and_f32_loss(self):
        model, features, labels, opt, ts = _setup()
        step = make_train_step(
            model, _mse, opt, precision="mixed_bfloat16"
        )
        ts, loss = step(ts, features, labels, jax.random.PRNGKey(1))
        assert loss.dtype == jnp.float32
        assert np.isfinite(float(loss))
        for leaf in jax.tree_util.tree_leaves(ts.params):
            assert leaf.dtype == jnp.float32

    def test_mixed_step_tracks_f32_step_closely(self):
        model, features, labels, opt, ts = _setup()
        f32_step = make_train_step(model, _mse, opt)
        mixed_step = make_train_step(
            model, _mse, opt, precision="mixed_bfloat16"
        )
        key = jax.random.PRNGKey(1)
        ts_a, loss_a = f32_step(ts, features, labels, key)
        *_, ts2 = _setup()
        ts_b, loss_b = mixed_step(ts2, features, labels, key)
        # bf16 mantissa is 8 bits: expect ~1e-2 relative agreement
        np.testing.assert_allclose(
            float(loss_a), float(loss_b), rtol=5e-2
        )

    def test_accum_plus_precision_compose(self):
        model, features, labels, opt, ts = _setup()
        step = make_train_step(
            model, _mse, opt, accum_steps=2, precision="mixed_bfloat16"
        )
        ts, loss = step(ts, features, labels, jax.random.PRNGKey(1))
        assert np.isfinite(float(loss))
        assert int(ts.version) == 1
        for leaf in jax.tree_util.tree_leaves(ts.params):
            assert leaf.dtype == jnp.float32


def test_remat_step_matches_plain():
    """Full and policy-based rematerialization must be numerically
    identical to the plain step (same forward math, just recomputed in
    the backward), on both the plain and elastic step builders."""
    import flax.linen as nn
    import jax
    import optax

    from elasticdl_tpu.nn.model_api import init_variables, split_variables
    from elasticdl_tpu.training.step import (
        TrainState,
        make_train_step,
        parse_remat,
    )

    assert parse_remat("") is False
    assert parse_remat("full") is True
    assert parse_remat("dots_saveable") == "dots_saveable"

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, inputs, training=False):
            x = inputs["x"]
            x = nn.relu(nn.Dense(32)(x))
            return nn.Dense(4)(x)

    def loss_fn(output, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            output, labels.reshape(-1)
        ).mean()

    model = MLP()
    rng = np.random.default_rng(0)
    features = {"x": rng.random((8, 16), dtype=np.float32)}
    labels = rng.integers(0, 4, size=(8, 1)).astype(np.int32)
    variables = init_variables(
        model, jax.random.PRNGKey(0), {"x": features["x"][:1]}
    )
    params, state = split_variables(variables)
    opt = optax.sgd(0.1)
    key = jax.random.PRNGKey(1)

    def run(remat):
        ts = TrainState.create(
            jax.tree_util.tree_map(np.array, params), state, opt
        )
        step = make_train_step(model, loss_fn, opt, remat=remat)
        losses = []
        for _ in range(3):
            ts, loss = step(ts, features, labels, key)
            losses.append(float(loss))
        return losses, jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(np.asarray, ts.params)
        )

    base_losses, base_params = run(False)
    for remat in (True, "dots_saveable"):
        losses, leaves = run(remat)
        np.testing.assert_allclose(losses, base_losses, rtol=1e-6)
        for a, b in zip(leaves, base_params):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)

    with pytest.raises(ValueError, match="unknown remat policy"):
        make_train_step(model, loss_fn, opt, remat="not_a_policy")(
            TrainState.create(params, state, opt), features, labels, key
        )

    # elastic plane: remat step equals its own non-remat step
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from elasticdl_tpu.parallel.elastic import (
        broadcast_from_device0,
        host_copy,
        make_elastic_train_step,
    )

    mesh = Mesh(np.asarray(jax.devices()[:8]), ("data",))

    def put(tree, spec):
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(mesh, spec)), tree
        )

    g_feat = put(features, P("data"))
    g_lab = put(labels, P("data"))
    ones = put(np.ones(8, np.float32), P("data"))
    ep = put(np.zeros(8, np.int32), P("data"))
    outs = []
    for remat in (False, True):
        ts = broadcast_from_device0(
            mesh, host_copy(TrainState.create(params, state, opt))
        )
        estep = make_elastic_train_step(
            model, loss_fn, opt, mesh, remat=remat
        )
        with mesh:
            ts, loss, n, _, _ = estep(ts, g_feat, g_lab, ones, ep, key)
        outs.append((float(host_copy(loss)), host_copy(ts.params)))
    np.testing.assert_allclose(outs[1][0], outs[0][0], rtol=1e-6)
    for a, b in zip(
        jax.tree_util.tree_leaves(outs[1][1]),
        jax.tree_util.tree_leaves(outs[0][1]),
    ):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
