"""The pjit dense plane (ROADMAP item 5): 2D ``data x model`` sharded
dense models inside the elastic world, plus the dlpack wire bridge.

Three contracts pinned here:

- PARITY: the GSPMD weighted step (make_pjit_train_step) computes the
  SAME training trajectory as the replicated shard_map arm from one
  common init — bitwise losses, 1e-6 parameters (XLA may reassociate
  the partitioned matmul reductions).
- LAYOUT RE-SOLVE: a resize moves state DIRECTLY between old and new
  NamedSharding layouts (2x2 -> 4x1 -> 2x2 at the function level, a
  4x2 -> 8x1 -> 2x4 establish journey at the trainer level), carrying
  every leaf bitwise — no host round trip, no disk, no re-init.
- WIRE PIN: a ``jax.Array`` frames BYTE-IDENTICALLY to its host-staged
  twin (fused bf16 downcast included) — the dlpack bridge changes how
  bytes are produced, never which bytes.
"""

import time

import numpy as np
import optax
import pytest

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

import elasticdl_tpu.parallel.distributed as dist_mod
from elasticdl_tpu.nn.model_api import init_variables, split_variables
from elasticdl_tpu.parallel.distributed import WorldSpec
from elasticdl_tpu.parallel.elastic import (
    ElasticDPTrainer,
    build_state_specs,
    collect_sharded_paths,
    make_pjit_train_step,
    place_from_host_specs,
    specs_use_axis,
)
from elasticdl_tpu.parallel.sharding import tp_param_specs
from elasticdl_tpu.training.step import TrainState
from model_zoo.transformer_lm import transformer_lm as tzoo

KW = dict(
    vocab_size=32,
    num_layers=2,
    num_heads=4,
    head_dim=8,
    embed_dim=16,
    mlp_dim=32,
    use_flash=False,
)


def _batches(n, batch=16, length=8, seed=3):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        toks = rng.integers(0, KW["vocab_size"], (batch, length))
        toks = toks.astype(np.int32)
        out.append(({"tokens": toks}, toks.copy()))
    return out


def _gather(tree):
    """Full host values of a (possibly sharded) device pytree."""
    return jax.tree_util.tree_map(
        lambda x: np.asarray(jax.device_get(x)), tree
    )


def _assert_trees_close(a, b, rtol=0.0, atol=0.0):
    for (pa, la), (_pb, lb) in zip(
        jax.tree_util.tree_leaves_with_path(a),
        jax.tree_util.tree_leaves_with_path(b),
    ):
        np.testing.assert_allclose(
            np.asarray(la),
            np.asarray(lb),
            rtol=rtol,
            atol=atol,
            err_msg=str(pa),
        )


@pytest.fixture
def singleton_world(monkeypatch):
    """ElasticDPTrainer establish without jax.distributed (the same
    bypass test_elastic_sharded uses for single-process worlds)."""
    monkeypatch.setattr(dist_mod, "ensure_world", lambda s, **k: None)
    yield


def _tp_builder(tensor_parallel):
    def builder(mesh):
        return (
            tzoo.custom_model(**KW),
            tzoo.param_shardings(mesh, tensor_parallel=tensor_parallel),
        )

    return builder


# ---------------------------------------------------------------------------
# parity: pjit 2D-sharded step vs the replicated arm, one common init
# ---------------------------------------------------------------------------


def test_pjit_sharded_matches_replicated_trainer(singleton_world):
    batches = _batches(4)
    spec = WorldSpec(
        coordinator="", num_processes=1, process_id=0, epoch=0
    )

    trep = ElasticDPTrainer(
        tzoo.custom_model(**KW), tzoo.loss, optax.sgd(0.05)
    )
    trep.establish(spec, example_batch=batches[0])
    tsh = ElasticDPTrainer(
        tzoo.custom_model(**KW),
        tzoo.loss,
        optax.sgd(0.05),
        distributed_builder=_tp_builder(2),
        mesh_axes_fn=lambda n: tzoo.mesh_axes(n, tensor_parallel=2),
    )
    tsh.establish(spec, example_batch=batches[0])
    try:
        assert tsh._pjit_dense
        assert dict(tsh.mesh.shape) == {"data": 4, "model": 2}
        # the dense model is REALLY sharded: a TP kernel holds 1/2 of
        # its rows per device, not a replica
        kern = tsh._ts.params["block_0"]["query"]["kernel"]
        assert kern.sharding.spec == P(None, "model", None)
        shard = kern.addressable_shards[0].data
        assert shard.shape[1] * 2 == kern.shape[1]
        # both inits are the same deterministic host init
        _assert_trees_close(
            _gather(trep._ts.params), _gather(tsh._ts.params)
        )
        for features, labels in batches:
            l_rep, n_rep, _ = trep.train_step(
                features, labels, 16, sync=True
            )
            l_pjit, n_pjit, _ = tsh.train_step(
                features, labels, 16, sync=True
            )
            # losses come out bitwise on this toolchain; the gate is
            # 1e-6 (the acceptance bound — reassociation headroom)
            assert abs(l_rep - l_pjit) <= 1e-6 * max(1.0, abs(l_rep))
            assert n_rep == n_pjit
        _assert_trees_close(
            _gather(trep._ts.params),
            _gather(tsh._ts.params),
            rtol=2e-6,
            atol=2e-6,
        )
    finally:
        trep.close()
        tsh.close()


def test_pjit_weighted_drain_step_is_identity(singleton_world):
    """Weight-0 (drain) steps pass state through unchanged and do not
    advance the version — the elastic no-op contract on the pjit arm."""
    batches = _batches(2)
    spec = WorldSpec(
        coordinator="", num_processes=1, process_id=0, epoch=0
    )
    tsh = ElasticDPTrainer(
        tzoo.custom_model(**KW),
        tzoo.loss,
        optax.sgd(0.05),
        distributed_builder=_tp_builder(2),
        mesh_axes_fn=lambda n: tzoo.mesh_axes(n, tensor_parallel=2),
    )
    tsh.establish(spec, example_batch=batches[0])
    try:
        tsh.train_step(*batches[0], 16, sync=True)
        before = _gather(tsh._ts)
        v_before = tsh.version
        loss, n, count = tsh.train_step(None, None, 16, sync=True)
        assert count == 0 and n == 0
        assert tsh.version == v_before
        _assert_trees_close(before, _gather(tsh._ts))
    finally:
        tsh.close()


def test_pjit_mode_rejects_accum_steps(singleton_world):
    batches = _batches(1)
    t = ElasticDPTrainer(
        tzoo.custom_model(**KW),
        tzoo.loss,
        optax.sgd(0.05),
        accum_steps=2,
        distributed_builder=_tp_builder(2),
        mesh_axes_fn=lambda n: tzoo.mesh_axes(n, tensor_parallel=2),
    )
    with pytest.raises(ValueError, match="accum_steps"):
        t.establish(
            WorldSpec(
                coordinator="", num_processes=1, process_id=0, epoch=0
            ),
            example_batch=batches[0],
        )
    t.close()


# ---------------------------------------------------------------------------
# layout re-solve: 2x2 -> 4x1 -> 2x2 at the function level
# ---------------------------------------------------------------------------


def _mesh4(data, model):
    devs = np.asarray(jax.devices()[:4]).reshape(data, model)
    return Mesh(devs, ("data", "model"))


def _place(mesh, ts, specs):
    return place_from_host_specs(mesh, ts, specs)


def _relayout(ts, mesh, specs):
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs
    )
    return jax.tree_util.tree_map(jax.device_put, ts, shardings)


def test_layout_resolve_2x2_4x1_2x2_carries_state():
    """The ISSUE journey on an explicit 4-device submesh: state placed
    2x2, re-solved to 4x1 (model axis collapses to a divisor of 1),
    stepped, re-solved back to 2x2 — every move bitwise, and the final
    state equals an uninterrupted 2x2 run's."""
    batches = _batches(3, batch=8)
    model = tzoo.custom_model(**KW)
    opt = optax.sgd(0.05)
    variables = init_variables(
        model,
        jax.random.PRNGKey(0),
        jax.tree_util.tree_map(lambda x: x[:1], batches[0][0]),
    )
    params, state = split_variables(variables)
    ts_host = TrainState.create(params, state, opt)
    sharded = collect_sharded_paths(tp_param_specs())
    assert specs_use_axis(sharded, "model")
    specs = build_state_specs(ts_host, sharded)

    row = ("data", "model")

    def run(mesh_seq):
        """Step once per mesh, re-solving the layout between steps."""
        mesh = mesh_seq[0]
        ts = _place(mesh, ts_host, specs)
        losses = []
        steps = {}
        for i, (features, labels) in enumerate(batches):
            if mesh_seq[i] is not mesh:
                mesh = mesh_seq[i]
                ts = _relayout(ts, mesh, specs)
            if id(mesh) not in steps:
                steps[id(mesh)] = make_pjit_train_step(
                    model, tzoo.loss, opt, mesh, specs
                )
            step = steps[id(mesh)]
            n_dev = mesh.devices.size

            def put(x):
                x = np.asarray(x)
                spec = P(*((row,) + (None,) * (x.ndim - 1)))
                return jax.device_put(x, NamedSharding(mesh, spec))

            g_f = jax.tree_util.tree_map(put, features)
            g_l = jax.tree_util.tree_map(put, labels)
            w = put(np.ones((n_dev,), np.float32))
            e = put(np.zeros((n_dev,), np.int32))
            with mesh:
                ts, loss, _n, _, _ = step(
                    ts, g_f, g_l, w, e, jax.random.PRNGKey(7)
                )
            losses.append(float(loss))
        return losses, _gather(ts)

    m22, m41 = _mesh4(2, 2), _mesh4(4, 1)
    # the relayout MOVE is bitwise: place on 2x2, re-solve to 4x1 and
    # back, no step in between — every leaf identical
    placed = _place(m22, ts_host, specs)
    round_tripped = _relayout(
        _relayout(placed, m41, specs), _mesh4(2, 2), specs
    )
    _assert_trees_close(_gather(placed), _gather(round_tripped))
    # training THROUGH the journey tracks the uninterrupted 2x2 run at
    # the 1e-6 parity gate (a step executed on a different layout
    # reassociates its partitioned reductions at float-ulp level)
    journey_losses, journey_ts = run([m22, m41, _mesh4(2, 2)])
    straight_losses, straight_ts = run([m22, m22, m22])
    np.testing.assert_allclose(
        journey_losses, straight_losses, rtol=1e-6, atol=1e-6
    )
    _assert_trees_close(journey_ts, straight_ts, rtol=1e-6, atol=1e-6)


def test_trainer_resize_journey_relayout(singleton_world):
    """Trainer-level establish journey over the 8-device world:
    4x2 -> 8x1 -> 2x4. Each resize takes the DIRECT relayout path
    (state moved between NamedShardings, bitwise), and training
    continues on every new layout."""
    batches = _batches(4)
    layout = {"axes": {"data": 4, "model": 2}}
    tsh = ElasticDPTrainer(
        tzoo.custom_model(**KW),
        tzoo.loss,
        optax.sgd(0.05),
        distributed_builder=_tp_builder(2),
        mesh_axes_fn=lambda n: dict(layout["axes"]),
    )
    spec_of = lambda epoch: WorldSpec(
        coordinator="", num_processes=1, process_id=0, epoch=epoch
    )
    tsh.establish(spec_of(0), example_batch=batches[0])
    try:
        tsh.train_step(*batches[0], 16, sync=True)
        tsh.train_step(*batches[1], 16, sync=True)
        before = _gather(tsh._ts)
        layout["axes"] = {"data": 8, "model": 1}
        tsh.establish(spec_of(1), example_batch=batches[0])
        assert dict(tsh.mesh.shape) == {"data": 8, "model": 1}
        _assert_trees_close(before, _gather(tsh._ts))
        loss_81, _, _ = tsh.train_step(*batches[2], 16, sync=True)
        before = _gather(tsh._ts)
        layout["axes"] = {"data": 2, "model": 4}
        tsh.establish(spec_of(2), example_batch=batches[0])
        assert dict(tsh.mesh.shape) == {"data": 2, "model": 4}
        _assert_trees_close(before, _gather(tsh._ts))
        kern = tsh._ts.params["block_0"]["query"]["kernel"]
        shard = kern.addressable_shards[0].data
        assert shard.shape[1] * 4 == kern.shape[1]
        loss_24, _, _ = tsh.train_step(*batches[3], 16, sync=True)
        assert np.isfinite(loss_81) and np.isfinite(loss_24)
    finally:
        tsh.close()


def test_budget_forced_resolve_carries_state_through_establish(
    singleton_world,
):
    """A :class:`LayoutPlanner` in the trainer's establish path: the
    first establish derives the model profile and lays the 8 devices
    out dp-widest; then a per-device memory budget lands that rules
    dp-only out, and the next establish re-solves to a tp >= 2 layout
    and moves parameters AND optimizer slots there bitwise. With
    speculation on, the planner's layout hints had the post-budget
    winner's executable built before the resize asked for it."""
    from elasticdl_tpu.parallel import layout_solver
    from elasticdl_tpu.parallel.layout_solver import LayoutPlanner

    rows = 128  # the global batch stays; the layout changes under it
    batches = _batches(5, batch=rows)
    model = tzoo.custom_model(**KW)

    def builder(mesh):
        # one module for every mesh: the executable cache keys on it
        return model, tzoo.param_shardings(mesh, tensor_parallel=2)

    planner = LayoutPlanner(memory_budget=None)
    t = ElasticDPTrainer(
        model,
        tzoo.loss,
        optax.adam(1e-3),
        distributed_builder=builder,
        layout_planner=planner,
    )
    t.speculative_compile = True
    spec_of = lambda epoch: WorldSpec(
        coordinator="", num_processes=1, process_id=0, epoch=epoch
    )
    try:
        t.establish(spec_of(0), example_batch=batches[0])
        assert planner.profile is not None, "no profile was derived"
        pre = planner.last_plan.layout
        assert dict(t.mesh.shape) == layout_solver.mesh_axes_for(pre)
        for features, labels in batches[:3]:
            t.train_step(features, labels, rows, sync=True)

        # the budget: replicated state + half the tp-shardable state +
        # the smallest micro-batch's activations, so no dp-only layout
        # fits at any micro-batch and tp = 2 just fits at the smallest
        prof = planner.profile
        planner.memory_budget = (
            prof.replicated_bytes
            + prof.tp_bytes / 2.0
            + prof.activation_bytes_per_row * min(planner.microbatches)
        )
        post = layout_solver.best(
            8, prof, planner.memory_budget, planner.microbatches
        ).layout
        assert post.tp >= 2 and (post.dp, post.tp) != (pre.dp, pre.tp)

        t.hint_world_sizes([8])
        deadline = time.monotonic() + 120.0
        while not t._spec_compiler.idle():
            assert time.monotonic() < deadline, "speculation never landed"
            time.sleep(0.05)
        hits = t.compile_stats.get("speculative_hits")

        before = _gather(t._ts)
        t.establish(spec_of(1), example_batch=batches[3])
        assert dict(t.mesh.shape) == layout_solver.mesh_axes_for(post)
        _assert_trees_close(before, _gather(t._ts))
        assert t.compile_stats.get("speculative_hits") == hits + 1
        loss, _, count = t.train_step(*batches[4], rows, sync=True)
        assert np.isfinite(loss) and count == rows
    finally:
        t.close()


def test_direct_relayout_matches_checkpoint_interchange(
    singleton_world, tmp_path
):
    """ISSUE 20 acceptance: across the dp4x2 -> dp2x4 -> dp8x1
    journey, the DIRECT redistribution path (state device_put old ->
    new NamedShardings) produces the bitwise-identical TrainState the
    sharded-checkpoint interchange (the backend-died fallback) would
    have restored. Both paths run from the same pre-resize state: the
    direct trainer relays in place; a fresh trainer with a
    restore_provider pointed at a pre-resize snapshot establishes cold
    onto the new layout. Bitwise (atol=0) across params, optimizer
    slots, and counters."""
    batches = _batches(4)
    layout = {"axes": {"data": 4, "model": 2}}
    direct = ElasticDPTrainer(
        tzoo.custom_model(**KW),
        tzoo.loss,
        optax.sgd(0.05),
        distributed_builder=_tp_builder(2),
        mesh_axes_fn=lambda n: dict(layout["axes"]),
    )
    spec_of = lambda epoch: WorldSpec(
        coordinator="", num_processes=1, process_id=0, epoch=epoch
    )
    direct.establish(spec_of(0), example_batch=batches[0])
    try:
        direct.train_step(*batches[0], 16, sync=True)
        direct.train_step(*batches[1], 16, sync=True)
        journey = ({"data": 2, "model": 4}, {"data": 8, "model": 1})
        for leg, axes in enumerate(journey):
            before = _gather(direct._ts)
            ckdir = tmp_path / ("leg%d" % leg)
            direct.save_sharded(str(ckdir))
            layout["axes"] = dict(axes)
            direct.establish(spec_of(leg + 1), example_batch=batches[0])
            assert dict(direct.mesh.shape) == axes
            after_direct = _gather(direct._ts)
            # direct trainer has no restore_provider and no mirrors:
            # preserving the trained state proves the relayout branch
            # ran (the only other outcome is deterministic re-init)
            _assert_trees_close(before, after_direct)
            cold = ElasticDPTrainer(
                tzoo.custom_model(**KW),
                tzoo.loss,
                optax.sgd(0.05),
                distributed_builder=_tp_builder(2),
                mesh_axes_fn=lambda n: dict(layout["axes"]),
                restore_provider=lambda: str(ckdir),
            )
            cold.establish(spec_of(0), example_batch=batches[0])
            try:
                assert dict(cold.mesh.shape) == axes
                _assert_trees_close(after_direct, _gather(cold._ts))
            finally:
                cold.close()
            # advance the state so the next leg moves fresh bytes
            direct.train_step(*batches[2 + leg], 16, sync=True)
    finally:
        direct.close()


# ---------------------------------------------------------------------------
# zoo/worker routing
# ---------------------------------------------------------------------------


def test_tp_specs_cover_the_name_rule_families():
    """tp_param_specs is the promotion of parallel/sharding's TP name
    rules: every rule family appears as a suffix-spec, and the specs
    claim the transformer's real parameter paths."""
    from elasticdl_tpu.common.pytree import key_path_names
    from elasticdl_tpu.parallel.elastic import spec_path_matches

    sharded = collect_sharded_paths(tp_param_specs())
    for family in (
        ("query", "kernel"),
        ("key", "kernel"),
        ("value", "kernel"),
        ("out", "kernel"),
        ("mlp_up", "kernel"),
        ("mlp_down", "kernel"),
        ("embed", "embedding"),
    ):
        assert family in sharded, family
    model = tzoo.custom_model(**KW)
    variables = init_variables(
        model,
        jax.random.PRNGKey(0),
        {"tokens": np.zeros((1, 8), np.int32)},
    )
    params, _ = split_variables(variables)
    claimed = []

    def visit(key_path, _leaf):
        names = key_path_names(key_path)
        for spec_path in sharded:
            if spec_path_matches(spec_path, names):
                claimed.append("/".join(names))

    jax.tree_util.tree_map_with_path(visit, params)
    assert "block_0/query/kernel" in claimed
    assert "block_1/mlp_down/kernel" in claimed
    assert "embed/embedding" in claimed


def test_zoo_emits_model_axis_specs_and_worker_routes_pjit():
    specs = tzoo.param_shardings(None, tensor_parallel=2)
    assert specs_use_axis(collect_sharded_paths(specs), "model")
    assert tzoo.mesh_axes(8, tensor_parallel=2) == {
        "data": 4,
        "model": 2,
    }
    with pytest.raises(ValueError):
        tzoo.mesh_axes(6, tensor_parallel=4)
    with pytest.raises(ValueError):
        tzoo.param_shardings(
            None, tensor_parallel=2, pipeline_stages=2
        )
    # the worker's probe routes pjit-dense configs to the plain module
    from elasticdl_tpu.worker.elastic_allreduce_worker import (
        ElasticAllReduceWorker,
    )

    zoo_module = {"param_shardings": tzoo.param_shardings}
    assert ElasticAllReduceWorker._zoo_wants_pjit_dense(
        zoo_module, "tensor_parallel=2"
    )
    assert not ElasticAllReduceWorker._zoo_wants_pjit_dense(
        zoo_module, "pipeline_stages=2"
    )
    # deepfm's hbm-table specs stay on the collective path
    from model_zoo.deepfm_edl_embedding import (
        deepfm_edl_embedding as dzoo,
    )

    assert not ElasticAllReduceWorker._zoo_wants_pjit_dense(
        {"param_shardings": dzoo.param_shardings}, ""
    )


# ---------------------------------------------------------------------------
# wire pin: jax.Array frames byte-identically to its host twin
# ---------------------------------------------------------------------------


def test_device_array_frames_byte_identical():
    import ml_dtypes

    from elasticdl_tpu.common.tensor import (
        Tensor,
        device_host_view,
        is_device_array,
        serialize_tensor,
    )
    from elasticdl_tpu.rpc.core import pack_message, unpack_message

    host = np.random.default_rng(5).standard_normal((256, 16))
    host = host.astype(np.float32)
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    arms = {
        "single": jax.device_put(host, jax.devices()[0]),
        "replicated": jax.device_put(host, NamedSharding(mesh, P())),
        "sharded": jax.device_put(
            host, NamedSharding(mesh, P("data"))
        ),
    }
    for name, dev in arms.items():
        assert is_device_array(dev)
        # Tensor keeps the device array unmaterialized (the bridge)
        t = Tensor("t", dev)
        assert t.values is dev
        assert bytes(serialize_tensor(t)) == bytes(
            serialize_tensor(Tensor("t", host))
        ), name
        # fused bf16 downcast: device and host twins still byte-equal
        td, th = Tensor("t", dev), Tensor("t", host)
        td.wire_dtype = np.dtype(ml_dtypes.bfloat16)
        th.wire_dtype = np.dtype(ml_dtypes.bfloat16)
        assert bytes(serialize_tensor(td)) == bytes(
            serialize_tensor(th)
        ), name + "/bf16"
    # message packer accepts bare jax.Array fields
    m_dev = bytes(pack_message({"params": arms["replicated"], "v": 1}))
    m_host = bytes(pack_message({"params": host, "v": 1}))
    assert m_dev == m_host
    np.testing.assert_array_equal(unpack_message(m_dev)["params"], host)
    # the zero-copy claim itself: a replicated array's host view
    # shares memory with its shard-0 device buffer (CPU backend)
    view = device_host_view(arms["replicated"])
    assert np.shares_memory(
        view, np.from_dlpack(arms["replicated"].addressable_shards[0].data)
    )


def test_wire_bound_pytree_keeps_device_leaves():
    from elasticdl_tpu.common.tensor import (
        is_device_array,
        pytree_to_named_arrays,
    )

    tree = {
        "dense": {"kernel": jax.numpy.ones((4, 4))},
        "host": np.ones((2,), np.float32),
    }
    wire = pytree_to_named_arrays(tree, keep_device=True)
    assert is_device_array(wire["dense/kernel"])
    assert isinstance(wire["host"], np.ndarray)
    ckpt = pytree_to_named_arrays(tree)
    assert isinstance(ckpt["dense/kernel"], np.ndarray)
