"""The Mamba-2 state-space layer (elasticdl_tpu/ops/ssd.py and the ``m``
letter of model_zoo/transformer_lm/hybrid_moe_lm.py) and what came with
it (attention with no positions and no q/k norm at a stated scale, the
three multipliers, per-layer recomputation, a stack with no expert
layer) against the plain reference the benchmark keeps
(benchmark/reference/granite_hybrid_reference.py, loaded by path as
``benchmark/spec.load_reference`` loads it): float32, toy widths, on the
CPU. And the five models the module runs, held to what the parent
commit computed for them, bit for bit."""

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.common import model_utils
from elasticdl_tpu.ops import ssd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ZOO_FILE = os.path.join("model_zoo", "transformer_lm", "hybrid_moe_lm.py")
PARENT_OUTPUTS = os.path.join(REPO, "tests", "data", "hybrid_lm_parent_outputs.json")
TOY = dict(
    vocab_size=64, layer_pattern="mmam", num_dense_layers=4, embed_dim=32,
    num_heads=4, num_kv_heads=2, head_dim=8, mlp_dim=64,
    ssm_heads=4, ssm_head_dim=16, ssm_state=8, ssm_groups=2,
    ssm_conv_kernel=4, ssm_chunk=8, rope=False, qk_norm=False,
    attention_scale=1 / 8, embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=8.0, norm_eps=1e-5,
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
        "benchmark/reference/granite_hybrid_reference.py", "granite_hybrid_reference"
    )


@pytest.fixture(scope="module")
def zoo():
    return model_utils.load_module(os.path.join(REPO, ZOO_FILE))


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# ---- the chunked scan against the recurrence, position by position ----

HEADS, P, GROUPS, N, CHUNK = 4, 8, 2, 16, 8
# delta * a: a decay exp(-12) forgets the state at once, exp(-1e-4)
# keeps all of it for the whole sequence
DECAYS = {"near_0": (3.0, -4.0), "mixed": (0.5, -1.0), "near_1": (0.01, -0.01)}
SCAN_INPUTS = ("x", "dt", "a", "b", "c")


def _scan_inputs(length, decay):
    keys = jax.random.split(jax.random.PRNGKey(length), 5)
    dt_scale, a_scale = DECAYS[decay]
    return dict(
        x=jax.random.normal(keys[0], (2, length, HEADS, P)),
        dt=dt_scale * jax.random.uniform(keys[1], (2, length, HEADS), minval=0.5, maxval=1.5),
        a=a_scale * jnp.arange(1.0, HEADS + 1.0),
        b=jax.random.normal(keys[2], (2, length, GROUPS, N)),
        c=jax.random.normal(keys[3], (2, length, GROUPS, N)),
    )  # fmt: skip


@pytest.fixture(scope="module")
def scans(reference):
    """length, decay -> (y and gradients of the chunked scan, the same
    of the recurrence), the gradients of one random weighting of y."""
    made = {}

    def both(length, decay):
        if (length, decay) not in made:
            inputs = _scan_inputs(length, decay)
            weight = jax.random.normal(jax.random.PRNGKey(9), (2, length, HEADS, P))

            def chunked(v):
                y = ssd.ssd_scan(v["x"], v["dt"], v["a"], v["b"], v["c"], CHUNK)
                return jnp.sum(y * weight), y

            def stepwise(v):
                y = reference.selective_scan(v["x"], v["dt"], v["a"], v["b"], v["c"])
                return jnp.sum(y * weight), y

            made[length, decay] = tuple(
                jax.grad(f, has_aux=True)(inputs) for f in (chunked, stepwise)
            )
        return made[length, decay]

    return both


# whole chunks (1, 2, 5), less than one, and a length that is padded
LENGTHS = (CHUNK, 2 * CHUNK, 5 * CHUNK, 3, 2 * CHUNK + 3)


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("length", LENGTHS)
def test_chunked_scan_matches_the_recurrence(scans, length, decay):
    (_, y), (_, want) = scans(length, decay)
    assert y.shape == want.shape == (2, length, HEADS, P)
    assert bool(jnp.all(jnp.isfinite(y)))
    assert _rel(y, want) <= TOL


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("length", (CHUNK, 2 * CHUNK, 5 * CHUNK))
@pytest.mark.parametrize("leaf", SCAN_INPUTS)
def test_chunked_scan_gradient_matches_the_recurrence(scans, length, decay, leaf):
    (grads, _), (want, _) = scans(length, decay)
    assert bool(jnp.all(jnp.isfinite(grads[leaf])))
    assert _rel(grads[leaf], want[leaf]) <= 2e-5, leaf


def test_the_state_is_handed_from_chunk_to_chunk(reference):
    """One token's x at position 0, a decay near 1: the last chunk's
    output is what the state carried through every chunk before it."""
    inputs = _scan_inputs(5 * CHUNK, "near_1")
    inputs["x"] = inputs["x"].at[:, 1:].set(0.0)
    y = ssd.ssd_scan(*(inputs[k] for k in SCAN_INPUTS), CHUNK)
    want = reference.selective_scan(*(inputs[k] for k in SCAN_INPUTS))
    assert float(jnp.max(jnp.abs(want[:, -1]))) > 1e-3
    assert _rel(y[:, -CHUNK:], want[:, -CHUNK:]) <= TOL


def test_bf16_operands_keep_float32_decays():
    """bfloat16 x, b, c: y comes back in bfloat16, within bf16's
    rounding of the float32 scan, with a decay that bf16 cannot hold
    (exp(-1e-4) rounds to 1) still forgetting."""
    inputs = _scan_inputs(5 * CHUNK, "near_1")
    want = ssd.ssd_scan(*(inputs[k] for k in SCAN_INPUTS), CHUNK)
    low = {k: v.astype(jnp.bfloat16) if k in "xbc" else v for k, v in inputs.items()}
    y = ssd.ssd_scan(*(low[k] for k in SCAN_INPUTS), CHUNK)
    assert y.dtype == jnp.bfloat16
    assert _rel(y.astype(jnp.float32), want) <= 2e-2


def test_heads_that_no_group_serves_are_refused():
    inputs = _scan_inputs(CHUNK, "mixed")
    inputs["b"] = inputs["b"][:, :, :1].repeat(3, axis=2)
    inputs["c"] = inputs["b"]
    with pytest.raises(ValueError, match="not a multiple of 3 groups"):
        ssd.ssd_scan(*(inputs[k] for k in SCAN_INPUTS), CHUNK)


# ---- the whole toy model against the reference module ----


def _leaves(pattern):
    names = ["embed", "final_norm"]
    for i, kind in enumerate(pattern):
        layer = ["operator_norm", "ffn_norm", "w1", "w3", "w2"]
        layer += (
            ["in_proj", "conv_taps", "conv_bias", "dt_bias", "A_log", "D", "gated_norm", "out_proj"]
            if kind == "m"
            else ["wq", "wk", "wv", "wo"]
        )  # fmt: skip
        names += ["L%d.%s" % (i, name) for name in layer]
    return names


LEAVES = _leaves(TOY["layer_pattern"])


def _params(zoo, tokens, **sizes):
    """Seeded parameters with the per-head scalars and the conv bias
    moved off their initial values (a bias of zero and a skip of one
    would hide a fault in either)."""
    model = zoo.custom_model(**dict(TOY, **sizes))
    params = model.init(jax.random.PRNGKey(0), {"tokens": tokens})["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 64))

    def moved(path, leaf):
        name = getattr(path[-1], "key", "")
        if name in ("conv_bias", "D", "A_log", "dt_bias"):
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    return model, jax.tree_util.tree_map_with_path(moved, params)


def _loss_and_grads(zoo, model, params, tokens):
    def objective(params):
        logits = model.apply({"params": params}, {"tokens": tokens}, training=True)
        return zoo.loss(logits, tokens)

    return jax.jit(jax.value_and_grad(objective))(params)


@pytest.fixture(scope="module")
def tokens():
    # five chunks of 8, so the state crosses chunk borders
    return jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, TOY["vocab_size"])


@pytest.fixture(scope="module")
def both_sides(reference, zoo, tokens):
    model, params = _params(zoo, tokens, remat_layers=True)
    loss, grads = _loss_and_grads(zoo, model, params, tokens)
    ref_loss, ref_grads = jax.jit(
        lambda p: reference.loss_and_grads(reference.from_program(p, TOY), tokens, TOY)
    )(params)
    return loss, reference.from_program(grads, TOY), ref_loss, ref_grads


def test_loss_matches_the_reference(both_sides):
    loss, _, ref_loss, ref_grads = both_sides
    assert abs(float(loss) - float(ref_loss)) <= TOL * abs(float(ref_loss))
    # the names the reference returns are the leaves compared: every
    # parameter of the program, and nothing else
    assert sorted(ref_grads) == sorted(LEAVES)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(both_sides, leaf):
    _, grads, _, ref_grads = both_sides
    assert _rel(grads[leaf], ref_grads[leaf]) <= TOL, leaf


def test_recomputing_each_layer_changes_no_number(zoo, tokens, both_sides):
    """``remat_layers`` on and off: the same variables, the same loss
    and the same gradients."""
    model, params = _params(zoo, tokens, remat_layers=False)
    kept, kept_grads = _loss_and_grads(zoo, model, params, tokens)
    again, again_params = _params(zoo, tokens, remat_layers=True)
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(again_params)
    recomputed, recomputed_grads = _loss_and_grads(zoo, again, again_params, tokens)
    assert float(kept) == pytest.approx(float(recomputed), rel=1e-6)
    worst = max(
        jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(_rel, recomputed_grads, kept_grads)
        )
    )
    assert worst <= 1e-6


def _grad_jaxpr(zoo, tokens, **sizes):
    model, params = _params(zoo, tokens, **sizes)

    def objective(p):
        logits = model.apply({"params": p}, {"tokens": tokens}, training=True)
        return zoo.loss(logits, tokens)

    return jax.make_jaxpr(jax.grad(objective))(params).jaxpr


def test_the_recomputed_layers_are_in_the_step(zoo, tokens):
    """What ``remat_layers`` buys: the backward pass holds a checkpoint
    a layer. Without it the only ones are the scan's own, of its chunk
    body, one a state-space layer."""

    def checkpoints(remat):
        return str(_grad_jaxpr(zoo, tokens, remat_layers=remat)).count("remat2[")

    scans = TOY["layer_pattern"].count("m")
    assert checkpoints(False) == scans
    assert checkpoints(True) >= scans + len(TOY["layer_pattern"])


# ---- what ``remat_layers`` keeps: a layer's weight products ----

# widths that give each weight below a shape no other array has
DISTINCT = dict(mlp_dim=48, vocab_size=80)
# a weight of each kind by its shape (w1 and w3 are one shape and are
# counted together): how many matrices of it the stack holds
KEPT_WEIGHTS = {
    "mamba_out_proj": ((64, 32), 3),
    "attention_query": ((32, 4, 8), 1),
    "attention_out": ((4, 8, 32), 1),
    "swiglu_w1_and_w3": ((32, 48), 8),
}
IN_PROJ = ((32, 164), 3)


def _equations(jaxpr, name):
    """Every equation of primitive ``name``, nested ones included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        for inner in jax.core.jaxprs_in_params(eqn.params):
            found += _equations(inner, name)
    return found


def _products_reading(jaxpr, shape):
    """The products a weight of ``shape`` is an operand of."""
    return sum(
        any(v.aval.shape == shape for v in eqn.invars)
        for eqn in _equations(jaxpr, "dot_general")
    )


@pytest.fixture(scope="module")
def grad_jaxprs(zoo, tokens):
    return {
        remat: _grad_jaxpr(zoo, tokens, remat_layers=remat, **DISTINCT)
        for remat in (False, True)
    }


@pytest.mark.parametrize("weight", sorted(KEPT_WEIGHTS))
def test_no_kept_product_is_multiplied_a_second_time(grad_jaxprs, weight):
    """Two products read a weight, recomputing or not: the forward's
    and the input gradient's (its own gradient's, the third a weight
    costs, reads the layer's input instead)."""
    shape, matrices = KEPT_WEIGHTS[weight]
    assert _products_reading(grad_jaxprs[False], shape) == 2 * matrices
    assert _products_reading(grad_jaxprs[True], shape) == 2 * matrices


@pytest.fixture(scope="module")
def whole_layer_jaxpr(zoo, tokens):
    """``remat_layers`` with no result given the name."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(zoo, "_kept", lambda product: product)
        return _grad_jaxpr(zoo, tokens, remat_layers=True, **DISTINCT)


@pytest.mark.parametrize("weight", sorted(KEPT_WEIGHTS))
def test_a_layer_recomputed_whole_multiplies_it_again(whole_layer_jaxpr, weight):
    """The name is what keeps the products: with none given it, as
    until PR 34, the backward pass runs the forward's once more."""
    shape, matrices = KEPT_WEIGHTS[weight]
    assert _products_reading(whole_layer_jaxpr, shape) == 3 * matrices


def test_the_operators_input_projection_is_recomputed(grad_jaxprs):
    """``in_proj`` of a state-space layer is not kept (PERF.md section
    6, PR 36): the backward pass runs it once more."""
    shape, matrices = IN_PROJ
    assert _products_reading(grad_jaxprs[False], shape) == 2 * matrices
    assert _products_reading(grad_jaxprs[True], shape) == 3 * matrices


def test_the_scan_and_the_attention_are_still_recomputed(grad_jaxprs):
    """A state-space layer's loop runs three times (forward, recomputed
    forward, backward) where two do without ``remat_layers``, and the
    products under a batch dimension, the scan's and the plain
    attention's, are recomputed with it."""
    scans = TOY["layer_pattern"].count("m")
    assert len(_equations(grad_jaxprs[False], "scan")) == 2 * scans
    assert len(_equations(grad_jaxprs[True], "scan")) == 3 * scans

    def batched(jaxpr):
        return sum(
            bool(eqn.params["dimension_numbers"][1][0])
            for eqn in _equations(jaxpr, "dot_general")
        )

    assert batched(grad_jaxprs[True]) > batched(grad_jaxprs[False])


def test_the_attention_kernel_runs_four_times(zoo):
    """One attention layer at a length that takes the kernels: forward,
    recomputed forward, dq, dkv (the benchmark's configuration states
    four TPU custom calls: the kernel's results are not kept)."""
    long = jnp.zeros((1, 1024), jnp.int32)
    sizes = dict(layer_pattern="ma", num_dense_layers=2, ssm_chunk=256, use_flash=True)

    def kernels(remat):
        return len(_equations(_grad_jaxpr(zoo, long, remat_layers=remat, **sizes), "pallas_call"))

    assert kernels(False) == 3
    assert kernels(True) == 4


KEPT_PRODUCTS = [
    (dict(), 3 * 3 + 6),
    (dict(layer_pattern="mmmmmammmm", num_dense_layers=10), 9 * 3 + 6),
]


@pytest.mark.parametrize("sizes, kept", KEPT_PRODUCTS)
def test_step_facts_count_the_products_kept(zoo, tokens, sizes, kept):
    facts = zoo.custom_model(**dict(TOY, remat_layers=True, **sizes)).step_facts()
    assert facts["remat_kept_products"] == kept
    assert "remat_kept_products" not in zoo.custom_model(**dict(TOY, **sizes)).step_facts()
    # the count is of the names the trace gives
    named = _equations(_grad_jaxpr(zoo, tokens, **sizes), "name")
    assert len(named) == kept


def test_logits_and_loss_are_over_the_slice(reference, zoo, tokens):
    """A sliced vocabulary is a smaller vocabulary: ids index the rows
    held here, the logits are (batch, length, rows held), scaled, and
    the loss is the cross entropy over those rows alone."""
    model, params = _params(zoo, tokens)
    logits = model.apply({"params": params}, {"tokens": tokens}, training=True)
    assert logits.shape == tokens.shape + (TOY["vocab_size"],)
    want = reference.forward(reference.from_program(params, TOY), tokens, TOY)
    assert _rel(logits, want) <= TOL
    log_probs = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
    by_hand = -jnp.mean(
        jnp.take_along_axis(log_probs, tokens[:, 1:, None], axis=-1)
    )
    assert float(zoo.loss(logits, tokens)) == pytest.approx(float(by_hand), rel=1e-6)
    # the embedding enters at embedding_multiplier and the head divides
    # by logits_scaling: both at 1 is another model
    plain, _ = _params(zoo, tokens, embedding_multiplier=1.0, logits_scaling=1.0)
    other = plain.apply({"params": params}, {"tokens": tokens}, training=True)
    assert _rel(other, logits) > 0.1


def test_a_stack_with_no_expert_layer_keeps_no_routing_state(zoo, tokens):
    model = zoo.custom_model(**TOY)
    variables = model.init(jax.random.PRNGKey(0), {"tokens": tokens})
    assert list(variables) == ["params"]
    facts = model.step_facts()
    assert facts["expert_layers"] == 0
    assert {k: facts[k] for k in ("mamba_layers", "ssm_heads", "ssm_head_dim", "ssm_state", "ssm_chunk")} == {
        "mamba_layers": 3, "ssm_heads": 4, "ssm_head_dim": 16, "ssm_state": 8, "ssm_chunk": 8,
    }  # fmt: skip
    assert "remat_layers" not in facts
    assert zoo.custom_model(**dict(TOY, remat_layers=True)).step_facts()["remat_layers"] == 1


def test_the_published_initialisation_of_the_per_head_scalars(zoo, tokens):
    model = zoo.custom_model(**TOY)
    mamba = model.init(jax.random.PRNGKey(0), {"tokens": tokens})["params"]["layer_0_mamba"]
    np.testing.assert_allclose(mamba["A_log"], np.log(np.arange(1.0, 5.0)), rtol=1e-6)
    np.testing.assert_array_equal(mamba["D"], np.ones(4))
    delta = jax.nn.softplus(mamba["dt_bias"])
    assert bool(jnp.all((delta >= 1e-3 * 0.999) & (delta <= 1e-1 * 1.001)))
    assert not np.any(np.asarray(mamba["conv_bias"]))


def test_init_spares_the_scan(zoo, tokens, monkeypatch):
    """The trainer's init is an eager forward: the variables are made
    without the scan's loop, and are those a traced init makes."""
    model = zoo.custom_model(**TOY)
    traced = jax.jit(model.init)(jax.random.PRNGKey(0), {"tokens": tokens})

    def no_scan(*args, **kwargs):
        raise AssertionError("the scan ran at init")

    monkeypatch.setattr(ssd, "ssd_scan", no_scan)
    eager = model.init(jax.random.PRNGKey(0), {"tokens": tokens})
    assert jax.tree_util.tree_structure(eager) == jax.tree_util.tree_structure(traced)
    for made, want in zip(*map(jax.tree_util.tree_leaves, (eager, traced))):
        # the draws are the same; a jitted init scales them in one fusion
        np.testing.assert_allclose(made, want, rtol=1e-6, atol=1e-8)


REFUSED = [
    (dict(layer_pattern="mxam"), "layer_pattern 'mxam' holds \\['x'\\]"),
    (dict(ssm_state=0), "all have to be positive whole numbers"),
    (dict(ssm_chunk=8.5), "all have to be positive whole numbers"),
    (dict(ssm_groups=3), "ssm_heads=4 is not a multiple of ssm_groups=3"),
    (dict(layer_pattern="aaaa"), "holds no state-space layer"),
    (dict(layer_pattern="mmmm"), "holds no attention layer"),
    (dict(attention_scale=-1.0), "attention_scale=-1.0"),
    (dict(residual_multiplier=0.0), "residual_multiplier=0.0 is not a positive number"),
    (dict(logits_scaling=float("inf")), "logits_scaling=inf is not a positive number"),
    (dict(embedding_multiplier=-12.0), "embedding_multiplier=-12.0 is not a positive number"),
    (dict(remat_layers="yes"), "remat_layers='yes'"),
    (dict(rope="no"), "rope='no'"),
]  # fmt: skip


@pytest.mark.parametrize("sizes, message", REFUSED)
def test_a_parameter_that_makes_no_sense_is_refused(zoo, sizes, message):
    with pytest.raises(ValueError, match=message):
        zoo.custom_model(**dict(TOY, **sizes))


# ---- the models the module ran before: the parent's outputs, bit for bit ----

BEFORE = {
    "lfm2": dict(
        vocab_size=256, layer_pattern="caccc", num_dense_layers=1, embed_dim=64,
        num_heads=4, num_kv_heads=2, head_dim=16, mlp_dim=128, expert_dim=32,
        num_experts=16, experts_held=4, first_expert_held=4, num_experts_per_tok=2,
    ),
    "keye": dict(
        vocab_size=256, layer_pattern="ss", num_dense_layers=0, embed_dim=64,
        num_heads=4, num_kv_heads=2, head_dim=16, expert_dim=32, num_experts=16,
        experts_held=4, first_expert_held=4, num_experts_per_tok=2,
        routing="softmax", select_topk=16, indexer_heads=4, indexer_dim=8,
        tie_head=False, expert_apply="masked", rope_theta=1e7, norm_eps=1e-6,
    ),
    # the toy sizes of this file, of tests/test_smallthinker_lm.py and of
    # tests/test_ling_linear_lm.py (the vocabulary the tokens are drawn from)
    "granite": dict(TOY, vocab_size=256, remat_layers=True),
    "smallthinker": dict(
        vocab_size=256, layer_pattern="awww", num_dense_layers=0, embed_dim=64,
        num_heads=4, num_kv_heads=2, head_dim=16, expert_dim=32, num_experts=16,
        experts_held=4, first_expert_held=4, num_experts_per_tok=3,
        routing="softmax", router_input="operator_norm", expert_act="relu",
        expert_apply="masked", attention_window=12, rope=False, window_rope=True,
        qk_norm=False, tie_head=False, rope_theta=1.5e6, norm_eps=1e-6,
    ),
    "ling": dict(
        vocab_size=256, layer_pattern="kkkl", num_dense_layers=1, embed_dim=64,
        num_heads=4, mlp_dim=96, expert_dim=32, num_experts=32, experts_held=4,
        first_expert_held=8, num_experts_per_tok=4, num_expert_groups=4,
        expert_groups_per_tok=2, shared_expert_dim=24, routing="sigmoid_bias",
        routed_scaling_factor=2.5, kda_heads=4, kda_head_dim=16,
        kda_conv_kernel=4, kda_gate_lower_bound=-5.0, kda_chunk=16,
        mla_kv_rank=24, mla_nope_dim=16, mla_rope_dim=8, mla_v_dim=16,
        tie_head=False, rope_theta=6e6, norm_eps=1e-6, remat_layers=True,
    ),
}  # fmt: skip


def _digest(array):
    array = np.ascontiguousarray(np.asarray(array))
    return "%s%s:%s" % (
        array.dtype, list(array.shape), hashlib.sha256(array.tobytes()).hexdigest()
    )  # fmt: skip


def _canary():
    """A product, a softmax and a sum that share nothing with the
    model: where this machine rounds them otherwise than the one that
    recorded the parent's outputs did, no digest can be held to it."""
    a = jax.random.normal(jax.random.PRNGKey(11), (64, 96))
    b = jax.random.normal(jax.random.PRNGKey(12), (96, 48))
    return _digest(jax.jit(lambda a, b: jax.nn.softmax(a @ b).sum(0))(a, b))


def outputs_of(zoo_file):
    """The initial table, the logits, the loss and each gradient
    leaf's bytes of the five toy models, and what each says of its
    layout (``step_facts``, without a batch and with one), from the
    module at ``zoo_file``. ``python tests/test_granite_hybrid_lm.py
    <checkout of the parent>`` wrote
    tests/data/hybrid_lm_parent_outputs.json with it."""
    zoo = model_utils.load_module(zoo_file)
    held = {"canary": _canary()}
    for name, sizes in BEFORE.items():
        model = zoo.custom_model(**sizes)
        tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0, 256)
        variables = model.init(jax.random.PRNGKey(7), {"tokens": tokens})

        def objective(params):
            logits = model.apply(
                dict(variables, params=params), {"tokens": tokens}, training=True
            )
            return zoo.loss(logits, tokens), logits

        (loss, logits), grads = jax.jit(jax.value_and_grad(objective, has_aux=True))(
            variables["params"]
        )
        held[name + ".embed_init"] = _digest(variables["params"]["embed"]["embedding"])
        held[name + ".loss"] = _digest(loss)
        held[name + ".logits"] = _digest(logits)
        for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
            held[name + ".grad" + jax.tree_util.keystr(path)] = _digest(leaf)
        for key, batch in ((".step_facts", None), (".step_facts_of_a_batch", tokens)):
            facts = json.dumps(model.step_facts(batch), sort_keys=True)
            held[name + key] = hashlib.sha256(facts.encode()).hexdigest()
    return held


def _recorded():
    if not os.path.exists(PARENT_OUTPUTS):  # being recorded right now
        return {}
    with open(PARENT_OUTPUTS) as f:
        return json.load(f)


PARENT = _recorded()


@pytest.fixture(scope="module")
def outputs_now():
    got = outputs_of(os.path.join(REPO, ZOO_FILE))
    if got["canary"] != PARENT["canary"]:
        pytest.skip("this machine rounds otherwise than the one that recorded the parent")
    return got


@pytest.mark.parametrize("name", sorted(k for k in PARENT if k != "canary"))
def test_the_models_before_compute_what_the_parent_computed(outputs_now, name):
    """Every model the module runs, toy-sized: the parent commit's
    initial values, logits, loss, gradients and ``step_facts``, bit for
    bit."""
    assert outputs_now[name] == PARENT[name]


def test_no_output_of_the_models_before_goes_unheld(outputs_now):
    assert sorted(outputs_now) == sorted(PARENT)


if __name__ == "__main__":
    import sys

    with open(PARENT_OUTPUTS, "w") as f:
        json.dump(outputs_of(os.path.join(sys.argv[1], ZOO_FILE)), f, indent=1)
