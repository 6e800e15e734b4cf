"""The zoo's hybrid LM with a global layer without positions and
window layers with rotary positions, the router on the attention's
input and ReGLU experts (model_zoo/transformer_lm/hybrid_moe_lm.py
``layer_pattern=awww``, ops/flash_attention.py's kernels under a window,
parallel/expert.py's activation) against the plain reference the
benchmark keeps (benchmark/reference/smallthinker_moe_reference.py,
loaded by path as ``benchmark/spec.load_reference`` loads it): float32,
toy widths, on the CPU; and one toy job through ``edl train`` whose
``step_built`` carries the window's facts."""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.common import model_utils
from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.parallel import expert

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW, LENGTH = 12, 32
TOY = dict(
    vocab_size=256, layer_pattern="awww", num_dense_layers=0, embed_dim=64,
    num_heads=4, num_kv_heads=2, head_dim=16, expert_dim=32, num_experts=16,
    experts_held=4, first_expert_held=4, num_experts_per_tok=3,
    routing="softmax", router_input="operator_norm", expert_act="relu",
    attention_window=WINDOW, rope=False, window_rope=True, qk_norm=False,
    tie_head=False, rope_theta=1.5e6, norm_eps=1e-6,
)  # fmt: skip
TOL = 1e-5


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(REPO, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _load(
        "benchmark/reference/smallthinker_moe_reference.py",
        "smallthinker_moe_reference",
    )


@pytest.fixture(scope="module")
def zoo():
    return model_utils.load_module(
        os.path.join(REPO, "model_zoo", "transformer_lm", "hybrid_moe_lm.py")
    )


LEAVES = ["embed", "head", "final_norm"] + [
    "L%d.%s" % (i, name)
    for i in range(len(TOY["layer_pattern"]))
    for name in (
        "operator_norm", "wq", "wk", "wv", "wo", "ffn_norm", "router",
        "expert_w1", "expert_w3", "expert_w2",
    )
]  # fmt: skip


def _loss_and_grads(zoo, sizes, tokens):
    model = zoo.custom_model(**sizes)
    params = model.init(jax.random.PRNGKey(0), {"tokens": tokens})["params"]

    def objective(params):
        logits = model.apply({"params": params}, {"tokens": tokens}, training=True)
        return zoo.loss(logits, tokens)

    with jax.default_matmul_precision("highest"):
        return params, jax.value_and_grad(objective)(params)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, LENGTH), 0, 256)


@pytest.fixture(scope="module")
def both_sides(reference, zoo, tokens):
    """Loss and gradients of the program and of the reference on the
    same seeded weights and tokens."""
    params, (loss, grads) = _loss_and_grads(zoo, TOY, tokens)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = reference.loss_and_grads(
            reference.from_program(params, TOY), tokens, TOY
        )
    return loss, reference.from_program(grads, TOY), ref_loss, ref_grads


def test_loss_matches_the_reference(both_sides):
    loss, _, ref_loss, ref_grads = both_sides
    assert abs(float(loss) - float(ref_loss)) <= TOL * float(ref_loss)
    # every leaf of the program is a leaf of the reference, and no other
    assert sorted(ref_grads) == sorted(LEAVES)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(both_sides, leaf):
    _, grads, _, ref_grads = both_sides
    norm = float(jnp.linalg.norm(ref_grads[leaf].ravel()))
    error = float(jnp.linalg.norm((grads[leaf] - ref_grads[leaf]).ravel()))
    assert norm > 0 and error / norm <= 10 * TOL, (leaf, error / norm)


def test_the_references_logits_are_the_programs(reference, zoo, tokens):
    model = zoo.custom_model(**TOY)
    params = model.init(jax.random.PRNGKey(0), {"tokens": tokens})["params"]
    with jax.default_matmul_precision("highest"):
        ours = model.apply({"params": params}, {"tokens": tokens})
        theirs = reference.forward(
            reference.from_program(params, TOY), tokens, TOY
        )
    np.testing.assert_allclose(ours, theirs, atol=10 * TOL)


@pytest.mark.parametrize(
    "changed",
    [
        dict(router_input="ffn_norm"),
        dict(expert_act="silu"),
        dict(attention_window=WINDOW + 1),
        dict(rope=True),
        dict(window_rope=False),
        dict(layer_pattern="aaaa", attention_window=0),
    ],
    ids=lambda changed: "-".join("%s=%s" % kv for kv in changed.items()),
)
def test_each_thing_the_description_names_moves_the_loss(
    zoo, both_sides, tokens, changed
):
    """What the reference is held to is not what the program does by
    default: the router's input, the activation, the window's edge and
    which layers rotate are each seen by the comparison."""
    _, (loss, _) = _loss_and_grads(zoo, dict(TOY, **changed), tokens)
    assert abs(float(loss) - float(both_sides[2])) > 100 * TOL


def test_the_comparison_blocks_change_no_result(reference, monkeypatch, tokens, zoo):
    """One sequence after the other, 8 queries a block and 5 positions
    a chunk of the loss (neither divides what it cuts evenly into the
    cell's own sizes): when values exist, not which."""
    model = zoo.custom_model(**TOY)
    params = model.init(jax.random.PRNGKey(0), {"tokens": tokens})["params"]
    weights = reference.from_program(params, TOY)
    whole = reference.loss_and_grads(weights, tokens, TOY)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 8)
    monkeypatch.setattr(reference, "LOSS_ROWS", 5)
    cut = reference.loss_and_grads(weights, tokens, TOY)
    np.testing.assert_allclose(cut[0], whole[0], rtol=TOL)
    for name in whole[1]:
        np.testing.assert_allclose(cut[1][name], whole[1][name], atol=TOL, err_msg=name)


# ---------------------------------------------------------------------------
# the expert layer: ReGLU, the router's own input, the shares
# ---------------------------------------------------------------------------


def _layer_weights(seed=3, d=64, width=32, experts=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        h=jax.random.normal(keys[0], (48, d)),  # what the router reads
        u=jax.random.normal(keys[5], (48, d)),  # what the experts read
        router=jax.random.normal(keys[1], (d, experts)) * d**-0.5,
        w1=jax.random.normal(keys[2], (experts, d, width)) * d**-0.5,
        w3=jax.random.normal(keys[3], (experts, d, width)) * d**-0.5,
        w2=jax.random.normal(keys[4], (experts, width, d)) * width**-0.5,
    )


def test_top_k_then_softmax_is_softmax_then_renormalise(reference):
    """The published order (the 6 largest logits, a softmax over those)
    and the program's ``routing=softmax`` (a softmax over all, the
    largest selected, renormalised over them) are one number:
    ``exp(r_e) / sum over the selected of exp(r)``."""
    w = _layer_weights()
    sizes = dict(num_experts_per_tok=6)
    product = reference._product(lambda x: x)
    want = reference.route(w["h"], w["router"], sizes, product)
    selected, gates = expert.softmax_topk_route(w["h"] @ w["router"], 6)
    got = jnp.zeros_like(want).at[jnp.arange(48)[:, None], selected].set(gates)
    np.testing.assert_allclose(got, want, atol=TOL)
    np.testing.assert_allclose(want.sum(-1), 1.0, atol=1e-6)
    assert int((want > 0).sum()) == 48 * 6


@pytest.mark.parametrize(
    "apply", [expert.held_experts_apply, expert.held_experts_apply_masked],
    ids=["grouped", "masked"],
)  # fmt: skip
def test_the_eight_shares_add_up_to_the_uncut_layer(reference, apply):
    """What all eight toy shares give (2 of 16 experts each), added,
    equals what the uncut reference layer gives, with the router on an
    input of its own and ReGLU experts: each selected expert's gated
    output is in exactly one share, the gates are over all the selected
    on every chip alike, and nothing is computed on every chip and so
    counted once. The program's layer gives the same shares."""
    w = _layer_weights()
    sizes = dict(num_experts_per_tok=3)
    product = reference._product(lambda x: x)
    with jax.default_matmul_precision("highest"):
        gates_all = reference.route(w["h"], w["router"], sizes, product)
        whole = reference.expert_share(
            w["u"], gates_all, w["w1"], w["w3"], w["w2"], 0, product
        )
        selected, gates = expert.softmax_topk_route(w["h"] @ w["router"], 3)
        shares, program_shares = [], []
        for first in range(0, 16, 2):
            held = slice(first, first + 2)
            shares.append(
                reference.expert_share(
                    w["u"], gates_all, w["w1"][held], w["w3"][held],
                    w["w2"][held], first, product,
                )  # fmt: skip
            )
            program_shares.append(
                apply(
                    w["u"], selected, gates,
                    jnp.concatenate([w["w1"][held], w["w3"][held]], axis=-1),
                    w["w2"][held], first, act="relu",
                )  # fmt: skip
            )
        # the whole layer written out: relu, and the gate of the ROUTER's
        # input on the product of the EXPERTS' input
        by_hand = sum(
            gates_all[:, e : e + 1]
            * ((jax.nn.relu(w["u"] @ w["w1"][e]) * (w["u"] @ w["w3"][e])) @ w["w2"][e])
            for e in range(16)
        )
    assert len(shares) == 8 and float(jnp.abs(whole).max()) > 0.1
    np.testing.assert_allclose(whole, by_hand, atol=TOL)
    np.testing.assert_allclose(sum(shares), whole, atol=TOL)
    np.testing.assert_allclose(sum(program_shares), whole, atol=TOL)
    for mine, theirs in zip(program_shares, shares):
        np.testing.assert_allclose(mine, theirs, atol=TOL)


@pytest.mark.parametrize("act", expert.EXPERT_ACTS)
def test_grouped_and_masked_agree_under_either_activation(act):
    """Value and all five gradients (tokens, gates, both weight stacks),
    by dispatch and by every held expert over every token."""
    w = _layer_weights()
    selected, gates = expert.softmax_topk_route(w["h"] @ w["router"], 3)
    w_in = jnp.concatenate([w["w1"][4:8], w["w3"][4:8]], axis=-1)
    cotangent = jax.random.normal(jax.random.PRNGKey(9), w["u"].shape)

    def objective(apply):
        def fn(u, gates, w_in, w_out):
            return (apply(u, selected, gates, w_in, w_out, 4, act=act) * cotangent).sum()

        return jax.value_and_grad(fn, argnums=(0, 1, 2, 3))(u, gates, w_in, w["w2"][4:8])

    u = w["u"]
    with jax.default_matmul_precision("highest"):
        grouped = objective(expert.held_experts_apply)
        masked = objective(expert.held_experts_apply_masked)
    np.testing.assert_allclose(grouped[0], masked[0], rtol=TOL)
    for a, b in zip(grouped[1], masked[1]):
        assert float(jnp.abs(a).max()) > 0
        np.testing.assert_allclose(a, b, atol=10 * TOL)


def test_relu_is_not_silu_and_the_default_is_silu():
    w = _layer_weights()
    selected, gates = expert.softmax_topk_route(w["h"] @ w["router"], 3)
    w_in = jnp.concatenate([w["w1"][:4], w["w3"][:4]], axis=-1)
    args = (w["u"], selected, gates, w_in, w["w2"][:4], 0)
    for apply in (expert.held_experts_apply, expert.held_experts_apply_masked):
        np.testing.assert_array_equal(apply(*args), apply(*args, act="silu"))
        assert float(jnp.abs(apply(*args) - apply(*args, act="relu")).max()) > 0.01


@pytest.mark.parametrize("leaf", ["router", "experts_w13", "experts_w2", "ffn_norm", "operator_norm", "embed"])
def test_masked_experts_give_the_gradients_grouped_experts_give(zoo, tokens, leaf):
    got = {
        apply: _loss_and_grads(zoo, dict(TOY, expert_apply=apply), tokens)[1]
        for apply in zoo.EXPERT_APPLIES
    }
    np.testing.assert_allclose(got["grouped"][0], got["masked"][0], rtol=1e-6)
    found = 0
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(got["grouped"][1]),
        jax.tree_util.tree_leaves(got["masked"][1]),
    ):
        if leaf in jax.tree_util.keystr(path):
            np.testing.assert_allclose(a, b, atol=10 * TOL)
            found += float(jnp.abs(a).max()) > 0
    assert found


# ---------------------------------------------------------------------------
# layouts refused, facts, the kernels at the policy's length
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "sizes, message",
    [
        (dict(layer_pattern="awwx"), r"holds \['x'\].*'w' attention within a window"),
        (dict(attention_window=0), "holds a window layer: attention_window=0"),
        (dict(attention_window=-4), "attention_window=-4"),
        (dict(attention_window=12.5), "attention_window=12.5"),
        (dict(layer_pattern="aaaa"), "holds no window layer.*attention_window=12"),
        (
            dict(layer_pattern="aaaa", attention_window=0, window_rope=False),
            "holds no window layer.*window_rope=False say nothing",
        ),
        (dict(window_rope=1), "window_rope=1 is neither True nor False"),
        (dict(router_input="attention"), "router_input 'attention' is not one of ffn_norm, operator_norm"),
        (dict(expert_act="gelu"), "expert_act 'gelu' is not one of silu, relu"),
        (dict(num_dense_layers=4), "holds no expert layer, so router_input and expert_act say nothing"),
    ],
)
def test_a_layout_that_says_nothing_is_refused_by_name(zoo, sizes, message):
    with pytest.raises(ValueError, match=message):
        zoo.custom_model(**dict(TOY, **sizes))


def test_step_facts_cover_the_window_the_router_and_the_activation(zoo):
    model = zoo.custom_model(**TOY)
    facts = model.step_facts({"tokens": np.zeros((2, LENGTH), np.int32)})
    kept = sum(min(t + 1, WINDOW) for t in range(LENGTH))
    assert facts == {
        "expert_layers": 4, "experts_held": 4, "experts_routed": 16,
        "first_expert_held": 4, "routing": "softmax", "tie_head": 0,
        "expert_apply": "grouped", "conv_layers": 0, "attention_layers": 1,
        "moe_dispatch_chunk_rows": expert.DISPATCH_CHUNK_ROWS,
        "window_layers": 3, "attention_window": WINDOW,
        "window_pairs_kept": kept, "window_pairs_causal": LENGTH * (LENGTH + 1) // 2,
        "router_input": "operator_norm", "expert_act": "relu",
    }  # fmt: skip
    assert (kept, LENGTH * (LENGTH + 1) // 2) == fa.window_pairs(LENGTH, WINDOW)
    # with no batch to measure, nothing is said of the pairs
    assert "window_pairs_kept" not in model.step_facts()
    # an earlier model says nothing new
    plain = zoo.custom_model(vocab_size=64).step_facts({"tokens": np.zeros((2, 16))})
    assert not {"window_layers", "router_input", "expert_act"} & set(plain)
    assert zoo.KINDS["w"].kept == 4
    kept_products = zoo.custom_model(**dict(TOY, remat_layers=True)).step_facts()
    assert kept_products["remat_kept_products"] == 16


def _kernel_names(fn, *args):
    return [
        line.split("name=")[1].strip()
        for line in str(jax.make_jaxpr(fn)(*args)).splitlines()
        if line.strip().startswith("name=edl_")
    ]


def test_the_model_takes_each_layers_kernels_from_the_policys_length(zoo):
    """At 1,024 positions the global layer takes the plain kernel and
    the three window layers the windowed one, under its own name and
    scope; rotary positions in the window layers only."""
    model = zoo.custom_model(**dict(TOY, attention_window=256, expert_apply="masked"))
    tokens = jnp.zeros((1, 1024), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), {"tokens": tokens})
    )["params"]
    forward = lambda p: model.apply({"params": p}, {"tokens": tokens})
    assert _kernel_names(forward, params) == ["edl_flash_fwd"] + 3 * ["edl_flash_win_fwd"]
    lowered = jax.jit(forward).lower(params).as_text(debug_info=True)
    assert lowered.count("edl/window_attention") > 0
    # a window that reaches every key: the plain kernel in every layer
    whole = zoo.custom_model(**dict(TOY, attention_window=1024, expert_apply="masked"))
    forward = lambda p: whole.apply({"params": p}, {"tokens": tokens})
    assert _kernel_names(forward, params) == 4 * ["edl_flash_fwd"]
    text = str(jax.make_jaxpr(lambda p: model.apply({"params": p}, {"tokens": tokens}))(params))
    assert text.count(" cos ") == text.count(" sin ") == 2 * 3  # q and k, three layers


def test_attention_in_step_knows_the_windowed_kernels():
    both = list(fa.WINDOWED) + list(fa.WINDOWED.values())
    assert fa.attention_in_step({"mosaic_kernels": both, "pallas_kernels": []}) == "pallas"
    assert fa.attention_in_step(
        {"mosaic_kernels": list(fa.WINDOWED.values()), "pallas_kernels": []}
    ) == "pallas"
    assert fa.attention_in_step(
        {"mosaic_kernels": [], "pallas_kernels": list(fa.WINDOWED.values())}
    ) == "pallas-interpret"


# ---------------------------------------------------------------------------
# a toy job through ``edl train``: the normal path, and what it says it built
# ---------------------------------------------------------------------------

STEPS, MINIBATCH, SYNC_EVERY = 8, 2, 4
FACTS = {
    "expert_layers": 4, "experts_held": 4, "experts_routed": 16,
    "attention_layers": 1, "window_layers": 3, "attention_window": WINDOW,
    "window_pairs_kept": sum(min(t + 1, WINDOW) for t in range(LENGTH)),
    "window_pairs_causal": LENGTH * (LENGTH + 1) // 2,
    "router_input": "operator_norm", "expert_act": "relu",
    "routing": "softmax", "tie_head": 0, "expert_apply": "grouped",
}  # fmt: skip


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    from elasticdl_tpu.data.example import encode_example
    from elasticdl_tpu.data.recordio import create_recordio

    out = tmp_path_factory.mktemp("smallthinker_job")
    data = out / "data"
    data.mkdir()
    rng = np.random.default_rng(0)
    with create_recordio(str(data / "tokens.edlr")) as w:
        for _ in range(STEPS * MINIBATCH):
            w.write(encode_example({"tokens": rng.integers(0, 64, size=LENGTH).astype(np.int64)}))
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", EDL_DIST_PLATFORM="cpu",
        EDL_LOCAL_DEVICES="1", XLA_FLAGS="", PYTHONPATH=REPO,
    )  # fmt: skip
    env.pop("EDL_PROFILE_DIR", None)
    events_path = out / "events.jsonl"
    got = subprocess.run(
        [
            sys.executable, "-m", "elasticdl_tpu.cli", "train",
            "--job_name", "smallthinker",
            "--distribution_strategy", "AllreduceStrategy",
            "--num_workers", "1",
            "--model_zoo", os.path.join(REPO, "model_zoo"),
            "--model_def", "transformer_lm.hybrid_moe_lm.custom_model",
            "--model_params", ",".join("%s=%s" % kv for kv in TOY.items()),
            "--training_data", str(data),
            "--minibatch_size", str(MINIBATCH),
            "--num_minibatches_per_task", str(SYNC_EVERY),
            "--num_epochs", "1",
            "--telemetry_events_path", str(events_path),
        ],  # fmt: skip
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=600,
    )  # fmt: skip
    assert got.returncode == 0, got.stderr[-3000:]
    with open(events_path) as f:
        events = [json.loads(line) for line in f if line.strip()]
    windows = [e for e in events if e["kind"] == "train_window"]
    assert sum(w["steps"] for w in windows) == STEPS
    (built,) = [e for e in events if e["kind"] == "step_built"]
    return windows, built


@pytest.mark.parametrize("fact", sorted(FACTS))
def test_step_built_carries_the_models_fact(job, fact):
    _, built = job
    assert built[fact] == FACTS[fact]


def test_the_job_trains_and_counts_its_routing(job):
    windows, built = job
    for w in windows:
        # steps x expert layers x tokens x assignments a token
        assert w["moe_rows_routed"] == w["steps"] * 4 * MINIBATCH * LENGTH * 3
        assert 0 < w["moe_rows_here"] < w["moe_rows_routed"]
        assert "expert_bias_abs_max" not in w  # softmax: no bias state
        assert "sel_pairs_kept" not in w  # a window is static: no counter
    assert windows[-1]["last_loss"] < windows[0]["first_loss"]
    # 32 positions: under the policy's 1,024, so XLA's masked attention;
    # the dispatching expert layer's grouped products are interpreted
    assert built["attention"] == "xla" and built["mesh"] == "data=1"
    assert built["pallas_calls"] == built["pallas_interpreted"] == 4 * 6
