"""The zoo's hybrid LM as GLM-4.7-Flash: latent attention with a
low-rank query in every layer (``l`` with ``mla_q_rank``), a dense layer
in front, a sigmoid router's expert layer with a shared expert behind
the others, and one multi-token-prediction module behind the last layer
(``mtp_layers``), against the plain reference
(benchmark/reference/glm_mla_mtp_reference.py) in float32 on the CPU at
a toy size, on seeded weights: both logits, both losses and every
gradient leaf; the eight shares of a toy layer against the uncut layer,
in the trunk and in the module; where the module runs and where it does
not; and its loss's way into the step's through ``aux_loss`` in both
step builders.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu.common import model_utils
from elasticdl_tpu.parallel import expert

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LENGTH = 64
TOY = dict(
    vocab_size=256, layer_pattern="lll", num_dense_layers=1, embed_dim=64,
    num_heads=4, mlp_dim=96, expert_dim=32, num_experts=32, experts_held=4,
    first_expert_held=8, num_experts_per_tok=4, shared_expert_dim=24,
    routing="sigmoid_bias", routed_scaling_factor=1.8, mla_q_rank=40,
    mla_kv_rank=24, mla_nope_dim=24, mla_rope_dim=8, mla_v_dim=32,
    mtp_layers=1, mtp_loss_weight=0.3, tie_head=False, rope_theta=1e6,
    norm_eps=1e-5,
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
        "benchmark/reference/glm_mla_mtp_reference.py", "glm_mla_mtp_reference"
    )


@pytest.fixture(scope="module")
def zoo():
    return model_utils.load_module(
        os.path.join(REPO, "model_zoo", "transformer_lm", "hybrid_moe_lm.py")
    )


_MLA_LEAVES = ("wqa", "q_norm", "wqb", "wkva", "latent_norm", "wkvb", "wo")
_EXPERT_LEAVES = ("router", "expert_w13", "expert_w2", "shared_w13", "shared_w2")
_LAYER_NORMS = ("operator_norm", "ffn_norm")
LEAVES = (
    ["embed", "head", "final_norm"]
    + [
        "L%d.%s" % (i, name)
        for i in range(len(TOY["layer_pattern"]))
        for name in _LAYER_NORMS
        + _MLA_LEAVES
        + (("w1", "w3", "w2") if i < TOY["num_dense_layers"] else _EXPERT_LEAVES)
    ]
    + [
        "mtp." + name
        for name in ("hidden_norm", "embed_norm", "proj", "final_norm")
        + _LAYER_NORMS
        + _MLA_LEAVES
        + _EXPERT_LEAVES
    ]
)
# the gradient groups the comparison on the chip reads its worst leaf by
GROUPS = {
    "mla": [l for l in LEAVES if l.startswith("L") and l.split(".")[1] in _MLA_LEAVES],
    "experts": [
        l for l in LEAVES if l.startswith("L") and l.split(".")[1] in _EXPERT_LEAVES
    ],
    "dense": ["L0.w1", "L0.w3", "L0.w2"],
    "norms": [
        l for l in LEAVES if l.startswith("L") and l.split(".")[1] in _LAYER_NORMS
    ]
    + ["final_norm"],
    "vocabulary": ["embed", "head"],
    "module": [l for l in LEAVES if l.startswith("mtp.")],
}


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, LENGTH), 0, 256)


@pytest.fixture(scope="module")
def params(zoo, tokens):
    model = zoo.custom_model(**TOY)
    return model.init(jax.random.PRNGKey(0), {"tokens": tokens})["params"]


@pytest.fixture(scope="module")
def both_sides(reference, zoo, tokens, params):
    """The two losses and the gradients of their sum, of the program
    and of the reference, on the same seeded weights and tokens."""
    model = zoo.custom_model(**TOY)

    def objective(params):
        output = model.apply({"params": params}, {"tokens": tokens}, training=True)
        return zoo.loss(output, tokens), output

    with jax.default_matmul_precision("highest"):
        (loss, output), grads = jax.value_and_grad(objective, has_aux=True)(params)
        weights = reference.from_program(params, TOY)
        ref_loss, ref_grads = reference.loss_and_grads(weights, tokens, TOY)
        ref_lm, ref_mtp = reference.losses(weights, tokens, TOY)
    return {
        "loss": (loss, ref_loss),
        "lm_loss": (zoo.loss(output.logits, tokens), ref_lm),
        "mtp_loss": (output.mtp_loss, TOY["mtp_loss_weight"] * ref_mtp),
    }, reference.from_program(grads, TOY), ref_grads


@pytest.mark.parametrize("which", ["loss", "lm_loss", "mtp_loss"])
def test_each_loss_matches_the_reference(both_sides, which):
    ours, theirs = both_sides[0][which]
    assert float(theirs) > 0.1
    assert abs(float(ours) - float(theirs)) <= TOL * float(theirs)


def test_every_leaf_of_the_program_is_a_leaf_of_the_reference(both_sides):
    _, grads, ref_grads = both_sides
    assert sorted(ref_grads) == sorted(grads) == sorted(LEAVES)
    assert sorted(sum(GROUPS.values(), [])) == sorted(LEAVES)


@pytest.mark.parametrize("group", sorted(GROUPS))
def test_gradient_group_matches_the_reference(both_sides, group):
    _, grads, ref_grads = both_sides
    for leaf in GROUPS[group]:
        norm = float(jnp.linalg.norm(ref_grads[leaf].ravel()))
        error = float(jnp.linalg.norm((grads[leaf] - ref_grads[leaf]).ravel()))
        assert norm > 0 and error / norm <= 10 * TOL, (leaf, error / norm)


@pytest.mark.parametrize("which", ["trunk", "module"])
def test_the_references_logits_are_the_programs(reference, zoo, tokens, params, which):
    """The trunk's logits as the model returns them; the module's, which
    the program folds into its loss at once, rebuilt from the module's
    normed output and the head it shares, at the positions it has."""
    model = zoo.custom_model(**TOY)
    with jax.default_matmul_precision("highest"):
        output, kept = model.apply(
            {"params": params}, {"tokens": tokens}, training=True,
            capture_intermediates=lambda module, _: module.name == "mtp_0_final_norm",
        )  # fmt: skip
        trunk, module = reference.forward(
            reference.from_program(params, TOY), tokens, TOY
        )
        normed = kept["intermediates"]["mtp_0_final_norm"]["__call__"][0]
        rebuilt = normed @ params["head"]["kernel"]
    assert module.shape == (2, LENGTH - 1, 256)
    if which == "trunk":
        np.testing.assert_allclose(output.logits, trunk, atol=10 * TOL)
    else:
        np.testing.assert_allclose(rebuilt[:, :-1], module, atol=10 * TOL)


@pytest.mark.parametrize(
    "changed",
    [
        dict(mla_q_rank=0),
        dict(mtp_layers=0),
        dict(mtp_loss_weight=0.5),
        dict(shared_expert_dim=0),
        dict(routed_scaling_factor=1.0),
    ],
    ids=["low_rank_query", "module", "module_weight", "shared_expert", "gate_scale"],
)
def test_each_thing_the_configuration_names_moves_the_loss(
    both_sides, zoo, tokens, changed
):
    """A size that the program ignored would pass every comparison
    above."""
    loss = float(both_sides[0]["loss"][0])
    model = zoo.custom_model(**{**TOY, **changed})
    params = model.init(jax.random.PRNGKey(0), {"tokens": tokens})["params"]
    with jax.default_matmul_precision("highest"):
        other = zoo.loss(
            model.apply({"params": params}, {"tokens": tokens}, training=True), tokens
        )
    assert abs(float(other) - loss) > 1e-4 * loss


def test_the_comparison_blocks_change_no_result(reference, monkeypatch, tokens, params):
    """HEAD_GROUP, QUERY_BLOCK, FF_ROWS and LOSS_ROWS are how the
    reference fits the chip beside the program, not what it computes:
    two heads at a time, blocks that do not divide the module's 63
    positions, and the whole of each give the same loss and leaves."""
    weights = reference.from_program(params, TOY)
    with jax.default_matmul_precision("highest"):
        whole = reference.loss_and_grads(weights, tokens, TOY)
        monkeypatch.setattr(reference, "HEAD_GROUP", 2)
        monkeypatch.setattr(reference, "QUERY_BLOCK", 16)
        monkeypatch.setattr(reference, "FF_ROWS", 24)
        monkeypatch.setattr(reference, "LOSS_ROWS", 40)
        loss, grads = reference.loss_and_grads(weights, tokens, TOY)
    assert abs(float(loss) - float(whole[0])) <= 1e-6 * float(whole[0])
    for leaf in grads:
        np.testing.assert_allclose(
            grads[leaf], whole[1][leaf], rtol=1e-4, atol=2e-6, err_msg=leaf
        )


# ---------------------------------------------------------------------------
# the chip's share against the uncut layer
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("where", ["trunk", "module"])
def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
    reference, zoo, tokens, where
):
    """64 experts cut into 8 shares of 8 (``first_expert_held`` 0, 8,
    ..., 56), as the deployment cuts them: in the trunk's expert layer
    and in the prediction module's, what the eight chips' routed shares
    give, plus the shared expert that every chip computes alike COUNTED
    ONCE, is what the uncut reference gives for the whole layer."""
    sizes = {**TOY, "num_experts": 64, "experts_held": 8, "first_expert_held": 0}
    whole = zoo.custom_model(**{**sizes, "experts_held": 64})
    uncut = whole.init(jax.random.PRNGKey(5), {"tokens": tokens})["params"]
    name = "layer_1_moe" if where == "trunk" else "mtp_0_moe"
    u = jax.random.normal(jax.random.PRNGKey(6), (96, TOY["embed_dim"]))
    moe = uncut[name]
    w13, w2 = moe["experts_w13"], moe["experts_w2"]
    with jax.default_matmul_precision("highest"):
        gates = reference.route(u, moe["router"], sizes, jnp.einsum)
        w1, w3 = jnp.split(moe["shared_w13"], 2, axis=-1)
        wanted = reference.swiglu(
            u, w1, w3, moe["shared_w2"], jnp.einsum
        ) + reference.expert_share(u, gates, w13, w2, 0, jnp.einsum)
        selected, picked = expert.sigmoid_topk_route(
            u @ moe["router"], jnp.zeros(64), 4, TOY["routed_scaling_factor"]
        )
        shares = [
            expert.held_experts_apply(
                u, selected, picked, w13[first : first + 8], w2[first : first + 8],
                first,
            )
            for first in range(0, 64, 8)
        ]  # fmt: skip
        once = expert.shared_expert_apply(u, moe["shared_w13"], moe["shared_w2"])
    assert len(shares) == 8 and all(float(jnp.abs(s).max()) > 0 for s in shares)
    np.testing.assert_allclose(sum(shares) + once, wanted, rtol=1e-4, atol=1e-5)
    # counted on every chip it would be eight times too much
    assert float(jnp.abs(sum(shares) + 8 * once - wanted).max()) > 0.1


# ---------------------------------------------------------------------------
# where the module runs, and its loss's way into the step's
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["prediction", "evaluation_with_state"])
def test_the_module_is_absent_from_a_forward_that_asks_for_no_loss(
    zoo, tokens, mode
):
    """A prediction or evaluation forward returns the trunk's logits,
    and none of the module's products is in what it computes."""
    model = zoo.custom_model(**TOY)
    variables = model.init(jax.random.PRNGKey(0), {"tokens": tokens})
    given = {"params": variables["params"]} if mode == "prediction" else variables
    forward = lambda tokens: model.apply(given, {"tokens": tokens})
    assert forward(tokens).shape == (2, LENGTH, 256)

    def products(fn):
        return str(jax.make_jaxpr(fn)(tokens)).count("dot_general")

    without = zoo.custom_model(**{**TOY, "mtp_layers": 0})
    trunk_alone = products(
        lambda tokens: without.apply({"params": given["params"]}, {"tokens": tokens})
    )
    assert products(forward) == trunk_alone
    trained = lambda tokens: model.apply(given, {"tokens": tokens}, training=True)
    # the projection, a layer's products and the head's second pass
    assert products(trained) > trunk_alone + 10


def test_without_a_module_a_training_forward_returns_logits_alone(zoo, tokens):
    model = zoo.custom_model(**{**TOY, "mtp_layers": 0})
    variables = model.init(jax.random.PRNGKey(0), {"tokens": tokens})
    assert "aux_loss" not in variables
    assert not [name for name in variables["params"] if name.startswith("mtp")]
    out = model.apply(variables, {"tokens": tokens}, training=True, mutable=["moe_state"])
    assert out[0].shape == (2, LENGTH, 256)


def test_the_modules_router_keeps_state_of_its_own(zoo, tokens):
    model = zoo.custom_model(**TOY)
    variables = model.init(jax.random.PRNGKey(0), {"tokens": tokens})
    assert sorted(variables["moe_state"]) == ["layer_1_moe", "layer_2_moe", "mtp_0_moe"]
    assert float(variables["aux_loss"]["mtp_loss"]) == 0.0
    _, state = model.apply(
        variables, {"tokens": tokens}, training=True, mutable=["moe_state", "aux_loss"]
    )
    made = state["moe_state"]["mtp_0_moe"]["assignments"]
    # every position of both sequences, the last one's among them
    assert int(made.sum()) == 2 * LENGTH * TOY["num_experts_per_tok"]
    assert float(jnp.abs(state["moe_state"]["mtp_0_moe"]["expert_bias"]).max()) > 0


def _state_and_manual_loss(zoo, tokens):
    from elasticdl_tpu.nn.model_api import init_variables, split_variables

    model = zoo.custom_model(**TOY, remat_layers=True)
    params, state = split_variables(
        init_variables(model, jax.random.PRNGKey(0), {"tokens": tokens})
    )
    bare = model.apply({"params": params}, {"tokens": tokens}, training=True)
    return model, params, state, bare


@pytest.mark.parametrize("builder", ["training/step.py", "parallel/elastic.py"])
def test_the_modules_loss_reaches_the_steps_loss_through_aux_loss(
    zoo, tokens, builder
):
    """``mtp_loss_weight * L_mtp`` is written to the ``aux_loss``
    collection, which both step builders add to the loss they
    differentiate: the step's loss is the LM's plus it, it is in the
    state the step hands back, and the module's weights move."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from elasticdl_tpu.parallel.elastic import make_elastic_train_step
    from elasticdl_tpu.training.step import TrainState, make_train_step

    model, params, state, bare = _state_and_manual_loss(zoo, tokens)
    opt = optax.sgd(0.1)
    before = np.asarray(params["mtp_0_proj"]["kernel"])  # the step donates its state
    ts = TrainState.create(params, state, opt)
    key = jax.random.PRNGKey(1)
    if builder == "training/step.py":
        new, loss = make_train_step(model, zoo.loss, opt)(
            ts, {"tokens": tokens}, tokens, key
        )
    else:
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
        put = lambda x: jax.device_put(x, NamedSharding(mesh, P("data")))
        with mesh:
            new, loss, _, _, kept = make_elastic_train_step(model, zoo.loss, opt, mesh)(
                jax.device_put(ts, NamedSharding(mesh, P())),
                {"tokens": put(tokens)}, put(tokens), put(np.ones(1, np.float32)),
                put(np.zeros(1, np.int32)), key,
            )  # fmt: skip
    lm = float(zoo.loss(bare.logits, tokens))
    assert float(bare.mtp_loss) > 0.3
    np.testing.assert_allclose(float(loss), lm + float(bare.mtp_loss), rtol=1e-5)
    np.testing.assert_allclose(
        float(new.state["aux_loss"]["mtp_loss"]), float(bare.mtp_loss), rtol=1e-5
    )
    if builder != "training/step.py":
        # and beside it, in the step's receipt: an output of its own
        assert float(kept["state"]["aux_loss"]["mtp_loss"]) == float(
            new.state["aux_loss"]["mtp_loss"]
        )
    assert np.abs(np.asarray(new.params["mtp_0_proj"]["kernel"]) - before).max() > 0


def test_the_trainer_reads_the_last_steps_parts(zoo, tokens):
    """What the worker's ``train_window`` event carries as ``mtp_loss``
    (and, with the window's last loss, ``lm_loss``): the ``aux_loss``
    collection of the last VALIDATED step's receipt, a leaf under its
    own name; never a read of the live train state."""
    from elasticdl_tpu.parallel.elastic import ElasticDPTrainer, receipt_state

    model, params, state, bare = _state_and_manual_loss(zoo, tokens)
    state = {**state, "aux_loss": {"mtp_loss": np.asarray(bare.mtp_loss)}}
    trainer = ElasticDPTrainer.__new__(ElasticDPTrainer)
    trainer._validated_state = receipt_state(state)
    assert set(trainer._validated_state) == {"aux_loss", "moe_state"}
    parts = trainer.aux_losses()
    assert list(parts) == ["mtp_loss"]
    np.testing.assert_allclose(parts["mtp_loss"], float(bare.mtp_loss), rtol=1e-6)
    trainer._validated_state = {}
    assert trainer.aux_losses() == {}


# ---------------------------------------------------------------------------
# the model's own checks and facts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "sizes, message",
    [
        (dict(mla_q_rank=-1), "mla_q_rank=-1: a width"),
        (dict(mla_q_rank=2.5), "mla_q_rank=2.5: a width"),
        (dict(mtp_layers=2), "mtp_layers=2: 0 for no prediction module"),
        (dict(mtp_layers=True), "mtp_layers=True"),
        (dict(mtp_loss_weight=-0.1), "mtp_loss_weight=-0.1 is not a weight"),
        (dict(mtp_layers=0, mtp_loss_weight=0.5), "mtp_loss_weight=0.5 says nothing"),
        (dict(layer_pattern="aaa"), "mla_q_rank=40"),
    ],
)
def test_a_size_that_is_wrong_or_says_nothing_is_refused_by_name(zoo, sizes, message):
    with pytest.raises(ValueError, match=message):
        zoo.custom_model(**{**TOY, **sizes})


@pytest.mark.parametrize(
    "model_params, named",
    [
        ("layer_pattern='l,l,l',mla_q_rank=40", "'l'"),
        ("mtp_layers=1,mtp_depth=2", "mtp_depth"),
        ("mla_q_lora_rank=768", "mla_q_lora_rank"),
    ],
    ids=["a_comma_in_a_value", "an_unknown_size", "the_published_key"],
)
def test_a_comma_or_an_unknown_size_is_refused_with_the_sizes_name(
    zoo, model_params, named
):
    """``--model_params`` travels as ``k=v,k=v``: a value with a comma
    comes apart into a size nobody knows, which is refused by name, as
    any unknown size is."""
    with pytest.raises((TypeError, ValueError), match=named):
        model_utils.load_model_from_module(
            "hybrid_moe_lm.custom_model", {"custom_model": zoo.custom_model},
            model_params,
        )  # fmt: skip


def test_step_facts_count_the_modules_layer_and_state_the_new_sizes(zoo):
    model = zoo.custom_model(**TOY, remat_layers=True)
    facts = model.step_facts({"tokens": np.zeros((2, LENGTH), np.int32)})
    assert facts["mtp_layers"] == 1 and facts["mtp_loss_weight"] == 0.3
    assert facts["mla_q_rank"] == 40
    # the module's layer is one more latent attention and one more expert layer
    assert facts["mla_layers"] == 4 and facts["expert_layers"] == 3
    assert (facts["mla_qk_dim"], facts["mla_v_dim"]) == (32, 32)
    # five products a latent attention (the query's down projection
    # among them), two of the dense FF
    assert facts["remat_kept_products"] == 4 * 5 + 2
    plain = zoo.custom_model(**{**TOY, "mla_q_rank": 0, "mtp_layers": 0})
    facts = plain.step_facts()
    assert "mtp_layers" not in facts and "mla_q_rank" not in facts
    assert facts["mla_layers"] == 3 and facts["expert_layers"] == 2


def test_the_defaults_written_out_are_the_defaults(zoo, tokens):
    """``mla_q_rank=0`` and ``mtp_layers=0`` are "none": the model built
    with them named is the model built without (the digests of
    tests/test_granite_hybrid_lm.py hold the earlier models to the
    parent's bytes)."""
    sizes = {k: v for k, v in TOY.items() if k not in ("mla_q_rank", "mtp_layers", "mtp_loss_weight")}
    named = zoo.custom_model(**sizes, mla_q_rank=0, mtp_layers=0)
    assert named == zoo.custom_model(**sizes)
    params = named.init(jax.random.PRNGKey(0), {"tokens": tokens})["params"]
    assert sorted(params["layer_0_mla"]) == ["kv_down", "kv_norm", "kv_up", "out", "query"]


def test_remat_layers_changes_no_number_of_the_module(zoo, tokens, params):
    def loss_and_grads(remat):
        model = zoo.custom_model(**TOY, remat_layers=remat)
        return jax.value_and_grad(
            lambda p: zoo.loss(
                model.apply({"params": p}, {"tokens": tokens}, training=True), tokens
            )
        )(params)

    with jax.default_matmul_precision("highest"):
        (plain, plain_grads), (kept, kept_grads) = loss_and_grads(False), loss_and_grads(True)
    assert abs(float(plain) - float(kept)) <= TOL * float(plain)
    for a, b in zip(*map(jax.tree_util.tree_leaves, (plain_grads, kept_grads))):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-6)


@pytest.mark.slow
def test_the_comparison_fits_the_chip_because_the_latent_products_read_together(reference):
    """The comparison of the cell's configuration with its reference at
    2 x 8,192 tokens (what ``benchmark/compare.py`` builds), compiled
    for a described v5e with no chip: 14.44 GiB of 15.75 with a latent
    attention's products handing their input's gradient on after the
    kernel's (``_prompt_dot_general``), and refused with the plain
    ``lax.dot_general`` in its place (the compiler: "Used 16.03G of
    15.75G hbm"). About ten minutes of compiling each, so not in tier
    1."""
    import json

    topologies = pytest.importorskip("jax.experimental.topologies")
    try:
        topology = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip("no described v5e to compile for: %r" % (e,))
    from jax.sharding import SingleDeviceSharding

    from elasticdl_tpu.ops import flash_attention as fa
    from elasticdl_tpu.ops import grouped_matmul as gm

    with open(os.path.join(REPO, "benchmark", "configs", "glm-4.7-flash-ep8.json")) as f:
        sizes = json.load(f)["model_params"]
    one = SingleDeviceSharding(topology.devices[0])
    tokens = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one)

    def compiled_peak(prompt):
        module = model_utils.load_module(
            os.path.join(REPO, "model_zoo", "transformer_lm", "hybrid_moe_lm.py")
        )
        if not prompt:
            module._prompt_dot_general = jax.lax.dot_general
        model = module.custom_model(**sizes)

        def objective(params, tokens):
            output = model.apply({"params": params}, {"tokens": tokens}, training=True)
            return module.loss(output, tokens).astype(jnp.float32)

        def errors(params, tokens):
            wanted_loss, wanted = reference.loss_and_grads(
                reference.from_program(params, sizes), tokens, sizes
            )
            loss, grads = jax.value_and_grad(objective)(params, tokens)
            got = reference.from_program(grads, sizes)
            return loss, wanted_loss, {
                name: jnp.linalg.norm((got[name] - wanted[name]).ravel())
                / jnp.linalg.norm(wanted[name].ravel())
                for name in wanted
            }

        params = jax.eval_shape(
            lambda key, tokens: model.init(key, {"tokens": tokens})["params"],
            jax.random.PRNGKey(0),
            jnp.zeros((2, 8192), jnp.int32),
        )
        params = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), params
        )
        compiled = jax.jit(errors).lower(params, tokens).compile()
        return compiled.memory_analysis().peak_memory_in_bytes

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fa, "kernel_interpret_mode", lambda: False)
        patch.setattr(gm, "kernel_interpret_mode", lambda: False)
        assert compiled_peak(prompt=True) < 15.75 * 2**30
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            compiled_peak(prompt=False)
