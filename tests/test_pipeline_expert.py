"""Pipeline (pp) and expert (ep) parallelism on the virtual 8-dev mesh.

Neither axis exists in the reference (SURVEY §2.2); both are built on
the same seam as dp/tp/sp — mesh axes + shard_map + explicit
collectives — so the elastic scheduler above is untouched.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.parallel.expert import (
    make_moe_fn,
    reference_moe,
    top1_gate,
)
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.pipeline import (
    make_pipeline_fn,
    reference_pipeline,
    stack_stage_params,
)

D = 16


def _stage_fn(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def _stage_params(n_stages, seed=0):
    rng = np.random.default_rng(seed)
    return [
        {
            "w": rng.standard_normal((D, D)).astype(np.float32) * 0.3,
            "b": rng.standard_normal((D,)).astype(np.float32) * 0.1,
        }
        for _ in range(n_stages)
    ]


def test_pipeline_matches_sequential():
    mesh = create_mesh({"pipe": 4}, axis_names=("pipe",))
    stages = _stage_params(4)
    rng = np.random.default_rng(1)
    micro = rng.standard_normal((6, 8, D)).astype(np.float32)

    pipe = make_pipeline_fn(mesh, _stage_fn)
    stacked = stack_stage_params(stages)
    with mesh:
        got = np.asarray(jax.jit(pipe)(stacked, micro))
    want = np.asarray(reference_pipeline(_stage_fn, stages, micro))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_pipeline_gradients_match_sequential():
    mesh = create_mesh({"pipe": 4}, axis_names=("pipe",))
    stages = _stage_params(4, seed=2)
    rng = np.random.default_rng(3)
    micro = rng.standard_normal((4, 8, D)).astype(np.float32)
    pipe = make_pipeline_fn(mesh, _stage_fn)

    def loss_ring(stacked):
        return (pipe(stacked, micro) ** 2).sum()

    def loss_seq(per_stage):
        out = reference_pipeline(_stage_fn, per_stage, micro)
        return (out ** 2).sum()

    with mesh:
        g_ring = jax.jit(jax.grad(loss_ring))(stack_stage_params(stages))
    g_seq = jax.grad(loss_seq)(stages)
    for s in range(4):
        np.testing.assert_allclose(
            np.asarray(g_ring["w"][s]),
            np.asarray(g_seq[s]["w"]),
            rtol=3e-4,
            atol=3e-5,
        )


def test_pipeline_composes_with_data_parallel():
    mesh = create_mesh(
        {"data": 2, "pipe": 4}, axis_names=("data", "pipe")
    )
    stages = _stage_params(4, seed=4)
    rng = np.random.default_rng(5)
    micro = rng.standard_normal((3, 8, D)).astype(np.float32)
    pipe = make_pipeline_fn(mesh, _stage_fn, batch_axis="data")
    with mesh:
        got = np.asarray(jax.jit(pipe)(stack_stage_params(stages), micro))
    want = np.asarray(reference_pipeline(_stage_fn, stages, micro))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def _expert_fn(params, x):
    return jax.nn.relu(x @ params["w"]) @ params["wo"]


def _expert_params(n_experts, seed=0):
    rng = np.random.default_rng(seed)
    return [
        {
            "w": rng.standard_normal((D, 32)).astype(np.float32) * 0.2,
            "wo": rng.standard_normal((32, D)).astype(np.float32) * 0.2,
        }
        for _ in range(n_experts)
    ]


def test_moe_matches_dense_when_under_capacity():
    mesh = create_mesh({"expert": 8}, axis_names=("expert",))
    experts = _expert_params(8)
    rng = np.random.default_rng(1)
    x = rng.standard_normal((64, D)).astype(np.float32)
    logits = rng.standard_normal((64, 8)).astype(np.float32)

    moe = make_moe_fn(mesh, _expert_fn, capacity_factor=8.0)  # no overflow
    stacked = stack_stage_params(experts)
    with mesh:
        got = np.asarray(jax.jit(moe)(stacked, x, logits))
    want = np.asarray(reference_moe(_expert_fn, experts, x, logits))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_moe_gradients_flow_to_experts_and_gate():
    mesh = create_mesh({"expert": 4}, axis_names=("expert",))
    experts = _expert_params(4, seed=2)
    rng = np.random.default_rng(3)
    x = rng.standard_normal((32, D)).astype(np.float32)
    logits = rng.standard_normal((32, 4)).astype(np.float32)
    moe = make_moe_fn(mesh, _expert_fn, capacity_factor=8.0)

    def loss_routed(stacked, logits):
        return (moe(stacked, x, logits) ** 2).sum()

    def loss_dense(per_expert, logits):
        return (
            reference_moe(_expert_fn, per_expert, x, logits) ** 2
        ).sum()

    with mesh:
        g_stack, g_gate = jax.jit(jax.grad(loss_routed, argnums=(0, 1)))(
            stack_stage_params(experts), logits
        )
    g_dense, g_gate_ref = jax.grad(loss_dense, argnums=(0, 1))(
        experts, logits
    )
    for e in range(4):
        np.testing.assert_allclose(
            np.asarray(g_stack["w"][e]),
            np.asarray(g_dense[e]["w"]),
            rtol=3e-4,
            atol=3e-5,
        )
    np.testing.assert_allclose(
        np.asarray(g_gate), np.asarray(g_gate_ref), rtol=3e-4, atol=3e-5
    )


def test_moe_overflow_tokens_bypass():
    """capacity 1 with all tokens gated to one expert: only the first
    token per shard-bucket is served, the rest contribute zero."""
    mesh = create_mesh({"expert": 4}, axis_names=("expert",))
    experts = _expert_params(4, seed=6)
    x = np.ones((8, D), np.float32)
    logits = np.zeros((8, 4), np.float32)
    logits[:, 2] = 5.0  # everyone wants expert 2

    moe = make_moe_fn(mesh, _expert_fn, capacity_factor=1e-9)  # cap -> 1
    with mesh:
        got = np.asarray(
            jax.jit(moe)(stack_stage_params(experts), x, logits)
        )
    nonzero = np.abs(got).sum(axis=1) > 0
    assert nonzero.sum() == 1  # one token served, overflow bypassed
    idx, gate = top1_gate(jnp.asarray(logits))
    assert int(idx[0]) == 2


def test_moe_composes_with_data_parallel():
    mesh = create_mesh(
        {"data": 2, "expert": 4}, axis_names=("data", "expert")
    )
    experts = _expert_params(4, seed=7)
    rng = np.random.default_rng(8)
    x = rng.standard_normal((32, D)).astype(np.float32)
    logits = rng.standard_normal((32, 4)).astype(np.float32)
    moe = make_moe_fn(
        mesh, _expert_fn, batch_axis="data", capacity_factor=8.0
    )
    with mesh:
        got = np.asarray(jax.jit(moe)(stack_stage_params(experts), x, logits))
    want = np.asarray(reference_moe(_expert_fn, experts, x, logits))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_moe_transformer_trains_on_expert_mesh():
    """The transformer family with num_experts: routed MoE blocks over a
    data x expert mesh, gradients flowing end to end."""
    import optax

    from elasticdl_tpu.nn.model_api import init_variables, split_variables
    from elasticdl_tpu.training.step import TrainState, make_train_step
    from model_zoo.transformer_lm import transformer_lm as zoo

    mesh = create_mesh(
        {"data": 2, "expert": 4}, axis_names=("data", "expert")
    )
    model = zoo.custom_model(
        vocab_size=64,
        num_layers=1,
        mesh=mesh,
        num_experts=4,
        use_flash=False,
    )
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 64, size=(8, 16)).astype(np.int32)
    variables = init_variables(
        model, jax.random.PRNGKey(0), {"tokens": tokens}
    )
    params, state = split_variables(variables)
    # expert params carry the stacked (E, ...) leading dim
    moe = params["block_0"]["moe_mlp"]
    assert moe["experts_up"].shape[0] == 4
    opt = optax.sgd(0.05)
    ts = TrainState.create(params, state, opt)
    step = make_train_step(model, zoo.loss, opt)
    with mesh:
        losses = []
        for i in range(3):
            ts, loss = step(
                ts, {"tokens": tokens}, tokens, jax.random.PRNGKey(i)
            )
            losses.append(float(loss))
    assert all(np.isfinite(losses))
    # experts received gradient (params moved)
    moved = np.abs(
        np.asarray(ts.params["block_0"]["moe_mlp"]["experts_up"])
        - np.asarray(moe["experts_up"])
    ).max()
    assert moved > 0


def test_moe_transformer_dense_fallback_matches_routed():
    """Same model, mesh vs no mesh: with generous capacity the routed
    forward equals the dense fallback."""
    from elasticdl_tpu.nn.model_api import init_variables, split_variables
    from model_zoo.transformer_lm import transformer_lm as zoo

    rng = np.random.default_rng(1)
    tokens = rng.integers(0, 64, size=(4, 8)).astype(np.int32)

    dense_model = zoo.custom_model(
        vocab_size=64, num_layers=1, num_experts=4, use_flash=False
    )
    variables = init_variables(
        dense_model, jax.random.PRNGKey(0), {"tokens": tokens}
    )
    params, state = split_variables(variables)
    dense_out = dense_model.apply({"params": params, **state}, {"tokens": tokens})

    for shape, names in (
        ({"expert": 4}, ("expert",)),
        ({"data": 2, "expert": 4}, ("data", "expert")),
    ):
        mesh = create_mesh(shape, axis_names=names)
        routed_model = zoo.custom_model(
            vocab_size=64, num_layers=1, mesh=mesh, num_experts=4,
            use_flash=False, moe_capacity_factor=8.0,  # equality: no overflow
        )
        with mesh:
            routed_out = routed_model.apply(
                {"params": params, **state}, {"tokens": tokens}
            )
        np.testing.assert_allclose(
            np.asarray(dense_out),
            np.asarray(routed_out),
            rtol=2e-4,
            atol=2e-4,
            err_msg=str(shape),
        )


def test_topk_gate_renormalizes():
    from elasticdl_tpu.parallel.expert import topk_gate

    logits = np.array([[2.0, 1.0, 0.0, -1.0]], np.float32)
    idx, gate = topk_gate(jnp.asarray(logits), 2)
    assert idx.shape == (1, 2) and gate.shape == (1, 2)
    assert list(np.asarray(idx[0])) == [0, 1]
    np.testing.assert_allclose(float(gate.sum()), 1.0, rtol=1e-6)
    # relative odds of the two selected experts preserved
    np.testing.assert_allclose(
        float(gate[0, 0] / gate[0, 1]), np.e, rtol=1e-4
    )


def test_moe_top2_matches_dense_top2():
    mesh = create_mesh({"expert": 8}, axis_names=("expert",))
    experts = _expert_params(8, seed=5)
    rng = np.random.default_rng(6)
    x = rng.standard_normal((64, D)).astype(np.float32)
    logits = rng.standard_normal((64, 8)).astype(np.float32)

    moe = make_moe_fn(
        mesh, _expert_fn, capacity_factor=8.0, num_selected=2
    )
    stacked = stack_stage_params(experts)
    with mesh:
        got = np.asarray(jax.jit(moe)(stacked, x, logits))
    want = np.asarray(
        reference_moe(_expert_fn, experts, x, logits, num_selected=2)
    )
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_load_balancing_loss_calibration():
    from elasticdl_tpu.parallel.expert import load_balancing_loss

    e = 8
    # perfectly balanced: token i hard-routes to expert i%e, so BOTH the
    # f (top-1 fraction) and P (mean prob) terms are exercised at 1/e
    logits = np.tile(np.eye(e, dtype=np.float32) * 20.0, (4, 1))
    balanced = float(load_balancing_loss(jnp.asarray(logits)))
    np.testing.assert_allclose(balanced, 1.0, rtol=1e-4)
    # collapsed: every token hard-routes to expert 0
    collapsed = np.zeros((32, e), np.float32)
    collapsed[:, 0] = 20.0
    assert float(load_balancing_loss(jnp.asarray(collapsed))) > e - 1e-3


def test_moe_aux_loss_enters_train_step():
    """The train step's loss must include the sown aux_loss collection
    (gradients reach the router even when the task loss plateaus)."""
    import optax

    from elasticdl_tpu.nn.model_api import init_variables, split_variables
    from elasticdl_tpu.training.step import (
        TrainState,
        aux_loss_total,
        make_train_step,
    )
    from model_zoo.transformer_lm import transformer_lm as zoo

    model = zoo.custom_model(
        vocab_size=32,
        num_layers=1,
        num_experts=4,
        moe_num_selected=2,
        moe_aux_loss_coef=0.1,
        use_flash=False,
    )
    tokens = np.random.default_rng(0).integers(
        0, 32, size=(2, 16)
    ).astype(np.int32)
    variables = init_variables(
        model, jax.random.PRNGKey(0), {"tokens": tokens}
    )
    params, state = split_variables(variables)
    assert "aux_loss" in state
    opt = optax.sgd(0.01)
    ts = TrainState.create(params, state, opt)
    step = make_train_step(model, zoo.loss, opt)
    ts, loss = step(ts, {"tokens": tokens}, tokens, jax.random.PRNGKey(1))

    # manual forward: task loss + aux == step loss
    from elasticdl_tpu.nn.model_api import apply_model

    output, new_state = apply_model(
        model,
        ts.params,
        ts.state,
        {"tokens": tokens},
        training=True,
        rng=jax.random.PRNGKey(2),
    )
    aux = float(aux_loss_total(new_state))
    assert aux > 0.0  # coef 0.1 * lb-loss(>=1.0)
    step2 = make_train_step(model, zoo.loss, opt)
    _, loss2 = step2(ts, {"tokens": tokens}, tokens, jax.random.PRNGKey(2))
    manual = float(zoo.loss(output, tokens)) + aux
    np.testing.assert_allclose(float(loss2), manual, rtol=1e-4)


# -- the pipeline JOB PATH (PipelinedStack -> trainer -> worker) -------------


def _plain_to_staged(plain_params, num_layers, n_stages):
    """Transplant a plain TransformerLM's params into the pipelined
    model's structure (stacked stage subtree), so both models compute
    with identical values."""
    per = num_layers // n_stages
    stages = []
    for s in range(n_stages):
        stage = {}
        for i in range(per):
            stage["block_%d" % i] = plain_params["block_%d" % (s * per + i)]
        stages.append(stage)
    from elasticdl_tpu.parallel.pipeline import stack_stage_params

    return {
        "embed": plain_params["embed"],
        "RMSNorm_0": plain_params["RMSNorm_0"],
        "pipe": {"stages": stack_stage_params(stages)},
    }


def test_pipelined_transformer_matches_plain():
    """Forward logits and a 4-step dp x pp training run must match the
    plain (single-stage) model exactly (same params transplanted)."""
    import optax

    from elasticdl_tpu.nn.model_api import init_variables, split_variables
    from elasticdl_tpu.parallel.trainer import AllReduceTrainer
    from model_zoo.transformer_lm import transformer_lm as zoo

    cfg = dict(
        vocab_size=64, num_layers=2, num_heads=2, head_dim=8,
        embed_dim=32, mlp_dim=64,
    )
    b, l = 8, 16
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 64, size=(b, l)).astype(np.int32)
    batch = {"tokens": tokens}

    plain = zoo.custom_model(**cfg)
    variables = init_variables(
        plain, jax.random.PRNGKey(0), {"tokens": tokens[:1]}
    )
    plain_params, _ = split_variables(variables)

    mesh = create_mesh(
        {"data": 4, "pipe": 2}, axis_names=("data", "pipe")
    )
    piped = zoo.build_distributed_model(
        mesh=mesh, pipeline_stages=2, **cfg
    )
    staged_params = _plain_to_staged(plain_params, cfg["num_layers"], 2)

    out_plain = plain.apply({"params": plain_params}, batch)
    out_piped = piped.apply({"params": staged_params}, batch)
    np.testing.assert_allclose(
        np.asarray(out_piped), np.asarray(out_plain), rtol=2e-4, atol=2e-4
    )

    # ragged batch (eval tail): pads internally, slices back
    ragged = {"tokens": tokens[:5]}
    np.testing.assert_allclose(
        np.asarray(piped.apply({"params": staged_params}, ragged)),
        np.asarray(plain.apply({"params": plain_params}, ragged)),
        rtol=2e-4,
        atol=2e-4,
    )

    # training: same curves through the ALLREDUCE trainer
    param_specs = zoo.param_shardings(mesh, pipeline_stages=2)
    t_plain = AllReduceTrainer(plain, zoo.loss, optax.sgd(0.05), seed=1)
    t_piped = AllReduceTrainer(
        piped, zoo.loss, optax.sgd(0.05), mesh=mesh,
        param_specs=param_specs, seed=1,
    )
    from elasticdl_tpu.training.step import TrainState

    def host_clone(tree):
        # donated steps delete input buffers; each trainer needs its own
        return jax.tree_util.tree_map(lambda a: np.array(a), tree)

    t_plain.load_state(
        TrainState.create(host_clone(plain_params), {}, optax.sgd(0.05))
    )
    t_piped.load_state(
        TrainState.create(host_clone(staged_params), {}, optax.sgd(0.05))
    )
    for step in range(4):
        l_plain = float(t_plain.train_step(batch, tokens))
        l_piped = float(t_piped.train_step(batch, tokens))
        np.testing.assert_allclose(l_piped, l_plain, rtol=2e-4)
    # stage params actually sharded over the pipe axis
    leaf = t_piped.train_state.params["pipe"]["stages"]
    first = jax.tree_util.tree_leaves(leaf)[0]
    assert "pipe" in str(first.sharding.spec)


def test_pipeline_job_path_through_worker(tmp_path):
    """A zoo config trains through the job
    path with stages > 1 — master task dispatch, the single-process
    ALLREDUCE worker (the CLI local-mode engine), pipelined model."""
    from elasticdl_tpu.common.constants import JobType
    from elasticdl_tpu.data.example import encode_example
    from elasticdl_tpu.data.recordio import RecordIOWriter
    from elasticdl_tpu.master.checkpoint_service import CheckpointService
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.worker.allreduce_worker import AllReduceWorker
    from tests.in_process_master import InProcessMaster
    from tests.test_utils import MODEL_ZOO_PATH

    rng = np.random.default_rng(0)
    path = str(tmp_path / "tokens.edlr")
    with RecordIOWriter(path) as f:
        for _ in range(64):
            f.write(
                encode_example(
                    {
                        "tokens": rng.integers(
                            0, 64, size=(64,), dtype=np.int64
                        )
                    }
                )
            )
    task_d = TaskDispatcher({path: (0, 64)}, {}, {}, 32, 1)
    master = MasterServicer(
        1, 16, None, task_d,
        checkpoint_service=CheckpointService("", 0, 0, False),
        use_async=True,
    )
    worker = AllReduceWorker(
        worker_id=0,
        job_type=JobType.TRAINING_ONLY,
        minibatch_size=16,
        model_zoo=MODEL_ZOO_PATH,
        model_def="transformer_lm.transformer_lm.custom_model",
        model_params=(
            "pipeline_stages=2,vocab_size=64,num_layers=2,num_heads=2,"
            "head_dim=8,embed_dim=32,mlp_dim=64"
        ),
        stub=InProcessMaster(master),
    )
    # the worker built the pipelined form over a data x pipe mesh
    assert worker.trainer.mesh.shape.get("pipe") == 2
    losses = worker.run()
    assert task_d.finished()
    assert worker.trainer.version == 4  # 64 records / batch 16
    assert all(np.isfinite(losses))
