"""The zoo's hybrid LM as Ling-3.0-flash: Kimi-Delta-Attention layers
(``k``) and a latent-attention layer (``l``), a dense layer in front and
a group-limited router's expert layer with a shared expert behind the
others, against the plain reference
(benchmark/reference/ling_linear_moe_reference.py) in float32 on the
CPU at a toy size, on seeded weights; the group limit against a loop
written out; the eight shares of a toy layer (as the deployment's 64
shares of a layer) against the uncut layer; and one
toy job through ``edl train``.
"""

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
from elasticdl_tpu.ops import kda
from elasticdl_tpu.parallel import expert

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LENGTH = 64
TOY = dict(
    vocab_size=256, layer_pattern="kkkl", num_dense_layers=1, embed_dim=64,
    num_heads=4, mlp_dim=96, expert_dim=32, num_experts=32, experts_held=4,
    first_expert_held=8, num_experts_per_tok=4, num_expert_groups=4,
    expert_groups_per_tok=2, shared_expert_dim=24, routing="sigmoid_bias",
    routed_scaling_factor=2.5, kda_heads=4, kda_head_dim=16,
    kda_conv_kernel=4, kda_gate_lower_bound=-5.0, kda_chunk=16,
    mla_kv_rank=24, mla_nope_dim=16, mla_rope_dim=8, mla_v_dim=16,
    tie_head=False, rope_theta=6e6, norm_eps=1e-6,
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
        "benchmark/reference/ling_linear_moe_reference.py",
        "ling_linear_moe_reference",
    )


@pytest.fixture(scope="module")
def zoo():
    return model_utils.load_module(
        os.path.join(REPO, "model_zoo", "transformer_lm", "hybrid_moe_lm.py")
    )


_KDA_LEAVES = (
    "wq", "wk", "wv", "wf", "wbeta", "wgate", "wo", "taps_q", "taps_k",
    "taps_v", "a_log", "dt_bias", "head_norm",
)  # fmt: skip
_MLA_LEAVES = ("wq", "wkva", "latent_norm", "wkvb", "wo")
_EXPERT_LEAVES = (
    "router", "expert_w1", "expert_w3", "expert_w2", "shared_w1",
    "shared_w3", "shared_w2",
)  # fmt: skip
LEAVES = ["embed", "head", "final_norm"] + [
    "L%d.%s" % (i, name)
    for i, kind in enumerate(TOY["layer_pattern"])
    for name in ("operator_norm", "ffn_norm")
    + (_KDA_LEAVES if kind == "k" else _MLA_LEAVES)
    + (("w1", "w3", "w2") if i < TOY["num_dense_layers"] else _EXPERT_LEAVES)
]
# the gradient groups the comparison on the chip reads its worst leaf by
GROUPS = {
    "kda": ["L%d.%s" % (i, name) for i in range(3) for name in _KDA_LEAVES],
    "mla": ["L3." + name for name in _MLA_LEAVES],
    "experts": [l for l in LEAVES if l.split(".")[-1] in _EXPERT_LEAVES],
    "dense": ["L0.w1", "L0.w3", "L0.w2"],
    "norms": [l for l in LEAVES if l.endswith(("operator_norm", "ffn_norm", "final_norm"))],
    "vocabulary": ["embed", "head"],
}


def _loss_and_grads(zoo, sizes, tokens, jit=False):
    model = zoo.custom_model(**sizes)
    params = model.init(jax.random.PRNGKey(0), {"tokens": tokens})["params"]

    def objective(params):
        logits = model.apply({"params": params}, {"tokens": tokens}, training=True)
        return zoo.loss(logits, tokens)

    loss_and_grads = jax.value_and_grad(objective)
    with jax.default_matmul_precision("highest"):
        return params, (jax.jit(loss_and_grads) if jit else loss_and_grads)(params)


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
    assert sorted(sum(GROUPS.values(), [])) == sorted(set(LEAVES))


@pytest.mark.parametrize("group", sorted(GROUPS))
def test_gradient_group_matches_the_reference(both_sides, group):
    _, grads, _, ref_grads = both_sides
    for leaf in GROUPS[group]:
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
        dict(kda_gate_lower_bound=-1.0),
        dict(shared_expert_dim=0),
        dict(num_expert_groups=1, expert_groups_per_tok=1),
        dict(routed_scaling_factor=1.0),
        dict(layer_pattern="kkkk", mla_kv_rank=0, mla_nope_dim=0,
             mla_rope_dim=0, mla_v_dim=0),
    ],
    ids=["gate_bound", "shared_expert", "group_limit", "gate_scale", "mla_layer"],
)  # fmt: skip
def test_each_thing_the_configuration_names_moves_the_loss(
    both_sides, zoo, tokens, changed
):
    """A bound, a shared expert, a group limit, a scale or a layer kind
    that the program ignored would pass every comparison above."""
    loss = float(both_sides[0])
    _, (other, _) = _loss_and_grads(zoo, {**TOY, **changed}, tokens)
    assert abs(float(other) - loss) > 1e-4 * loss


def test_the_comparison_blocks_change_no_result(reference, monkeypatch, tokens, zoo):
    """STATE_BLOCK, QUERY_BLOCK and LOSS_ROWS are how the reference
    fits the chip, not what it computes."""
    model = zoo.custom_model(**TOY)
    params = model.init(jax.random.PRNGKey(0), {"tokens": tokens})["params"]
    weights = reference.from_program(params, TOY)
    whole = reference.loss_and_grads(weights, tokens, TOY)
    monkeypatch.setattr(reference, "STATE_BLOCK", 4)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)
    monkeypatch.setattr(reference, "LOSS_ROWS", 24)
    loss, grads = reference.loss_and_grads(weights, tokens, TOY)
    assert abs(float(loss) - float(whole[0])) <= 1e-6 * float(whole[0])
    for leaf in grads:
        np.testing.assert_allclose(
            grads[leaf], whole[1][leaf], rtol=1e-4, atol=2e-6, err_msg=leaf
        )


# ---------------------------------------------------------------------------
# the router's group limit, the shared expert and the shares
# ---------------------------------------------------------------------------


def _selected_by_a_loop(scores, groups, kept, k):
    """The selection written out a token at a time: a group's score the
    sum of its two largest, the ``kept`` best groups (ties to the lower
    group), the ``k`` largest among their experts (ties to the lower
    expert)."""
    size = scores.shape[1] // groups
    out = []
    for row in np.asarray(scores):
        group_score = [
            np.sort(row[g * size : (g + 1) * size])[-2:].sum()
            for g in range(groups)
        ]
        stay = sorted(range(groups), key=lambda g: (-group_score[g], g))[:kept]
        among = [e for e in range(len(row)) if e // size in stay]
        out.append(sorted(sorted(among, key=lambda e: (-row[e], e))[:k]))
    return np.asarray(out)


@pytest.mark.parametrize("groups, kept, k", [(4, 2, 4), (8, 4, 8), (4, 1, 3), (2, 2, 5)])
def test_the_group_limit_against_a_loop_written_out(groups, kept, k):
    logits = jax.random.normal(jax.random.PRNGKey(groups + k), (64, 32))
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(7), (32,))
    selected, gates = expert.sigmoid_topk_route(logits, bias, k, 2.5, groups, kept)
    scores = jax.nn.sigmoid(logits)
    want = _selected_by_a_loop(scores + bias, groups, kept, k)
    np.testing.assert_array_equal(np.sort(selected, axis=1), want)
    # the bias steers the selection and is not in the gates
    picked = np.take_along_axis(np.asarray(scores), np.asarray(selected), axis=1)
    np.testing.assert_allclose(
        gates, 2.5 * picked / (picked.sum(axis=1, keepdims=True) + 1e-6), rtol=1e-6
    )


def test_the_references_router_is_the_programs(reference):
    u = jax.random.normal(jax.random.PRNGKey(2), (48, 64))
    router = jax.random.normal(jax.random.PRNGKey(3), (64, 32)) * 0.3
    sizes = {**TOY, "num_expert_groups": 4, "expert_groups_per_tok": 2}
    with jax.default_matmul_precision("highest"):
        theirs = reference.route(u, router, sizes, jnp.einsum)
        selected, gates = expert.sigmoid_topk_route(
            u @ router, jnp.zeros(32), 4, 2.5, 4, 2
        )
    ours = jnp.sum(jax.nn.one_hot(selected, 32) * gates[..., None], axis=1)
    np.testing.assert_allclose(ours, theirs, rtol=1e-5, atol=1e-7)


def test_no_group_limit_and_no_shared_expert_is_todays_layer_bit_for_bit(zoo):
    """``n_group = topk_group = 1``: the selection, the gates and the
    layer's result are what they were, bit for bit; and the layer keeps
    no parameter more."""
    logits = jax.random.normal(jax.random.PRNGKey(5), (64, 32))
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(6), (32,))

    def todays(router_logits, expert_bias, k, scaling):
        scores = jax.nn.sigmoid(router_logits.astype(jnp.float32))
        _, selected = jax.lax.top_k(
            scores + jax.lax.stop_gradient(expert_bias.astype(jnp.float32)), k
        )
        picked = jnp.take_along_axis(scores, selected, axis=-1)
        gates = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
        return selected.astype(jnp.int32), gates * scaling

    for got, want in zip(
        expert.sigmoid_topk_route(logits, bias, 4, 2.5, 1, 1),
        todays(logits, bias, 4, 2.5),
    ):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(
        expert.sigmoid_topk_route(logits, bias, 4), todays(logits, bias, 4, 1.0)
    ):
        np.testing.assert_array_equal(got, want)
    plain = dict(
        h=jax.random.normal(jax.random.PRNGKey(8), (2, 16, 64)),
        num_experts=32, experts_held=4, first_expert_held=8,
        num_experts_per_tok=4, expert_dim=32, routed_scaling_factor=1.0,
        expert_bias_rate=1e-3, dtype=jnp.float32,
    )  # fmt: skip

    def layer(**more):
        sizes = {k: v for k, v in {**plain, **more}.items() if k != "h"}
        module = zoo.HeldExperts(**sizes)
        variables = module.init(jax.random.PRNGKey(0), plain["h"])
        # the module's ``apply`` field shadows flax's method of the name
        import flax.linen as nn

        return variables["params"], nn.Module.apply(module, variables, plain["h"])

    params, out = layer()
    assert sorted(params) == ["experts_w13", "experts_w2", "router"]
    same_params, same = layer(
        num_expert_groups=1, expert_groups_per_tok=1, shared_expert_dim=0
    )
    np.testing.assert_array_equal(out, same)
    with_shared, _ = layer(shared_expert_dim=24)
    assert sorted(set(with_shared) - set(params)) == ["shared_w13", "shared_w2"]
    for name in params:
        np.testing.assert_array_equal(params[name], with_shared[name])


@pytest.mark.parametrize("apply", ["grouped", "masked"])
def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
    reference, apply
):
    """A toy layer of 32 experts in 4 groups, cut into 8 shares of 4:
    what the eight chips' routed shares give, plus the shared expert
    that every chip computes alike COUNTED ONCE, is what the uncut
    reference gives for the whole layer."""
    d, width, experts, shared = 64, 32, 32, 24
    keys = jax.random.split(jax.random.PRNGKey(11), 7)
    u = jax.random.normal(keys[0], (96, d))
    router = jax.random.normal(keys[1], (d, experts)) * 0.3
    w1 = jax.random.normal(keys[2], (experts, d, width)) * d**-0.5
    w3 = jax.random.normal(keys[3], (experts, d, width)) * d**-0.5
    w2 = jax.random.normal(keys[4], (experts, width, d)) * width**-0.5
    shared_w13 = jax.random.normal(keys[5], (d, 2 * shared)) * d**-0.5
    shared_w2 = jax.random.normal(keys[6], (shared, d)) * shared**-0.5
    sizes = dict(
        num_experts=experts, num_experts_per_tok=4, num_expert_groups=4,
        expert_groups_per_tok=2, routed_scaling_factor=2.5,
    )  # fmt: skip
    with jax.default_matmul_precision("highest"):
        gates = reference.route(u, router, sizes, jnp.einsum)
        uncut = reference.swiglu(
            u, shared_w13[:, :shared], shared_w13[:, shared:], shared_w2,
            jnp.einsum,
        ) + reference.expert_share(u, gates, w1, w3, w2, 0, jnp.einsum)  # fmt: skip
        selected, picked = expert.sigmoid_topk_route(
            u @ router, jnp.zeros(experts), 4, 2.5, 4, 2
        )
        held_apply = (
            expert.held_experts_apply
            if apply == "grouped"
            else expert.held_experts_apply_masked
        )
        shares = [
            held_apply(
                u, selected, picked,
                jnp.concatenate([w1, w3], axis=-1)[first : first + 4],
                w2[first : first + 4], first,
            )
            for first in range(0, experts, 4)
        ]  # fmt: skip
        once = expert.shared_expert_apply(u, shared_w13, shared_w2)
    assert len(shares) == 8 and any(float(jnp.abs(s).max()) > 0 for s in shares)
    np.testing.assert_allclose(sum(shares) + once, uncut, rtol=1e-4, atol=1e-5)
    # counted on every chip it would be eight times too much
    assert float(jnp.abs(sum(shares) + 8 * once - uncut).max()) > 0.1


@pytest.mark.parametrize(
    "leaf", ["router", "experts_w13", "experts_w2", "shared_w13", "shared_w2", "ffn_norm"]
)
def test_masked_experts_give_the_gradients_grouped_experts_give(zoo, tokens, leaf):
    _, (loss, grouped) = _loss_and_grads(zoo, TOY, tokens)
    _, (other, masked) = _loss_and_grads(
        zoo, {**TOY, "expert_apply": "masked"}, tokens
    )
    assert abs(float(loss) - float(other)) <= TOL * float(loss)
    layer = "layer_2_ffn_norm" if leaf == "ffn_norm" else "layer_2_moe"
    got = masked[layer]["scale" if leaf == "ffn_norm" else leaf]
    want = grouped[layer]["scale" if leaf == "ffn_norm" else leaf]
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-6)


# ---------------------------------------------------------------------------
# the model's own checks and facts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "sizes, message",
    [
        (dict(kda_heads=0), "positive whole numbers"),
        (dict(mla_v_dim=0), "positive whole numbers"),
        (dict(kda_gate_lower_bound=0.0), "log-decay"),
        (dict(kda_gate_lower_bound=-50.0), "log-decay"),
        (dict(mla_rope_dim=7), "odd"),
        (dict(num_expert_groups=5), "groups of equal size"),
        (dict(expert_groups_per_tok=5), "groups of equal size"),
        (dict(routing="softmax"), "group limit"),
        (dict(num_experts_per_tok=20), "group limit"),
        (dict(shared_expert_dim=-1), "a width"),
        (dict(layer_pattern="kkkk"), "holds no latent attention"),
        (dict(layer_pattern="lll", num_dense_layers=0), "holds no a Kimi"),
        (dict(num_dense_layers=4), "holds no expert layer"),
        (dict(layer_pattern="kxl"), "a letter a layer"),
    ],
)
def test_a_layout_that_says_nothing_is_refused_by_name(zoo, sizes, message):
    with pytest.raises(ValueError, match=message):
        zoo.custom_model(**{**TOY, **sizes})


def test_the_earlier_models_take_no_size_of_the_new_letters(zoo):
    with pytest.raises(ValueError, match="say nothing"):
        zoo.custom_model(layer_pattern="caccc", kda_chunk=64)
    with pytest.raises(ValueError, match="say nothing"):
        zoo.custom_model(layer_pattern="caccc", mla_kv_rank=8)
    zoo.custom_model(layer_pattern="caccc")  # every default stays valid


def test_step_facts_cover_the_recurrence_the_latent_and_the_router(zoo):
    model = zoo.custom_model(**TOY, remat_layers=True)
    facts = model.step_facts({"tokens": np.zeros((2, LENGTH), np.int32)})
    assert facts["kda_layers"] == 3 and facts["mla_layers"] == 1
    assert (facts["kda_heads"], facts["kda_head_dim"], facts["kda_chunk"]) == (4, 16, 16)
    assert (facts["mla_qk_dim"], facts["mla_v_dim"]) == (24, 16)
    assert facts["shared_expert_dim"] == 24
    assert (facts["expert_groups"], facts["expert_groups_per_tok"]) == (4, 2)
    assert facts["expert_layers"] == 3 and facts["expert_apply"] == "grouped"
    # a KDA layer keeps its output projection, the MLA layer its four
    # products, the dense FF two
    assert facts["remat_kept_products"] == 3 * 1 + 4 + 2
    # and each KDA layer its recurrence, which is no weight product
    assert facts["remat_kept_recurrences"] == 3
    assert "remat_kept_recurrences" not in zoo.custom_model(**TOY).step_facts()
    no_k = zoo.custom_model(layer_pattern="caccc", remat_layers=True).step_facts()
    assert "remat_kept_products" in no_k and "remat_kept_recurrences" not in no_k
    plain = zoo.custom_model(layer_pattern="caccc").step_facts()
    assert not {"kda_layers", "mla_layers", "shared_expert_dim", "expert_groups"} & set(plain)


# ---------------------------------------------------------------------------
# ``remat_layers`` keeps a ``k`` layer's recurrence
# ---------------------------------------------------------------------------

# 16 chunks of 16: two groups of eight, so a kept state that is not zeros
REMAT_LENGTH = 256


@pytest.fixture(scope="module")
def kept_and_not(reference, zoo):
    """Loss and gradients of the toy model on 2 x 256 tokens with
    ``remat_layers`` off and on, by the reference's names."""
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, REMAT_LENGTH), 0, 256)
    sides = []
    for remat in (False, True):
        _, (loss, grads) = _loss_and_grads(
            zoo, {**TOY, "remat_layers": remat}, tokens, jit=True
        )
        sides.append((loss, reference.from_program(grads, TOY)))
    return sides


@pytest.mark.parametrize("group", ["loss"] + sorted(GROUPS))
def test_keeping_the_recurrence_changes_no_number(kept_and_not, group):
    """A rematerialised ``k`` layer hands its backward pass the kept
    output and group states in place of recomputed ones: the same
    arrays, so the loss and every leaf's gradient are what they are
    with ``remat_layers`` off, up to how the compiler fuses two
    programs' float32 sums: the limit of the comparison with the
    reference above (the worst leaf read 1.8e-5, an ``A_log``, whose
    gradient is the smallest)."""
    (loss, grads), (kept_loss, kept_grads) = kept_and_not
    if group == "loss":
        assert abs(float(kept_loss) - float(loss)) <= 1e-6 * float(loss)
        return
    for leaf in GROUPS[group]:
        norm = float(jnp.linalg.norm(grads[leaf].ravel()))
        error = float(jnp.linalg.norm((kept_grads[leaf] - grads[leaf]).ravel()))
        assert norm > 0 and error / norm <= 10 * TOL, (leaf, error / norm)


def _loops_and_solves(zoo, remat_layers, kept_names=None):
    """Of the compiled gradient of ONE ``k`` layer (2 x 256 tokens: two
    groups of eight chunks): how many ``while`` loops carry the
    recurrence's state or its cotangent, by the regular expression the
    benchmark's reader finds them with in a device trace, and how many
    triangular solves it holds (LAPACK's ``trsm`` on the CPU; on the
    chip each is an inversion)."""
    sys.path[:0] = [os.path.join(REPO, "benchmark"), os.path.join(REPO, "benchmark", "layer_metrics")]
    try:
        import _kda
    finally:
        del sys.path[:2]
    kda_sizes = {name: size for name, size in TOY.items() if name.startswith("kda_")}
    model = zoo.custom_model(
        vocab_size=256, layer_pattern="k", num_dense_layers=1, embed_dim=64,
        num_heads=4, mlp_dim=96, tie_head=False, remat_layers=remat_layers,
        **kda_sizes,
    )  # fmt: skip
    tokens = jnp.zeros((2, REMAT_LENGTH), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), {"tokens": tokens})["params"]
    )

    def objective(params):
        logits = model.apply({"params": params}, {"tokens": tokens}, training=True)
        return zoo.loss(logits, tokens)

    with pytest.MonkeyPatch.context() as patch:
        if kept_names is not None:
            patch.setattr(kda, "KEPT_NAMES", kept_names)
        # value_and_grad: under grad alone the forward's value is unused
        # and a pass is dropped whatever is kept
        text = jax.jit(jax.value_and_grad(objective)).lower(params).compile().as_text()
    loop = _kda.state_loop(model.step_facts(), 2)
    return (
        sum(bool(loop.match(line.strip())) for line in text.splitlines()),
        text.count('custom_call_target="lapack_strsm_ffi"'),
    )


def test_a_rematerialised_layer_runs_the_recurrence_forward_twice_not_three_times(zoo):
    """A pass forward is two such loops (the groups, and in a group its
    chunks); a pass backward three (the groups last to first, and in a
    group its chunks forward again, then backward). With what
    ``ops/kda.py`` names kept, a rematerialised layer's gradient holds
    the five loops of a layer that is not rematerialised; with nothing
    of the recurrence kept (the names taken out of the policy: PR 42's
    program) it holds a third pass forward, seven. Should jax stop
    honouring a ``checkpoint_name`` inside a ``custom_vjp``'s forward
    rule, this reads seven.

    And it inverts a chunk's matrix ONCE, in the forward pass, which
    keeps the inverse for the backward pass under a name of its own:
    with that name alone out of the policy (PR 43's two) the
    recomputed layer inverts again to hand the backward pass what it
    reads (in loops that carry no state: nothing reads it), as it does
    with nothing kept."""
    assert _loops_and_solves(zoo, remat_layers=False) == (2 + 3, 1)
    assert _loops_and_solves(zoo, remat_layers=True) == (2 + 3, 1)
    assert _loops_and_solves(
        zoo, remat_layers=True, kept_names=(kda.KEPT_OUTPUT, kda.KEPT_STATES)
    ) == (2 + 3, 2)
    assert _loops_and_solves(zoo, remat_layers=True, kept_names=()) == (2 + 2 + 3, 2)


def test_an_eager_init_runs_no_loop_of_the_recurrence(zoo, monkeypatch, tokens):
    """The trainer's init is an eager forward pass: the op hands back
    ``v`` there, as the selecting attention hands back ``q``."""

    def refuse(*args, **kwargs):
        raise AssertionError("the recurrence ran while initialising")

    monkeypatch.setattr(kda, "kda", refuse)
    model = zoo.custom_model(**TOY)
    variables = model.init(jax.random.PRNGKey(0), {"tokens": tokens})
    assert "layer_1_kda" in variables["params"]


def test_reading_gradients_together_changes_no_value(zoo):
    """``_read_together`` moves WHEN an input's gradient may be handed
    on, never a number: ``PromptDense`` against ``nn.Dense`` on the same
    kernel, output and both gradients bit for bit."""
    import flax.linen as nn

    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 24), jnp.bfloat16)
    prompt = zoo.PromptDense(40, jnp.bfloat16)
    plain = nn.Dense(40, use_bias=False, dtype=jnp.bfloat16)
    params = prompt.init(jax.random.PRNGKey(2), x)

    def both(module):
        def objective(params, x):
            return jnp.sum(jnp.sin(module.apply(params, x).astype(jnp.float32)))

        return module.apply(params, x), jax.grad(objective, argnums=(0, 1))(params, x)

    got, wanted = both(prompt), both(plain)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(wanted)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_the_comparison_fits_the_chip_because_gradients_are_read_together(reference):
    """Why ``_read_together`` is in the model at all: the comparison of
    the cell's configuration with its reference at 2 x 4,096 tokens
    (what ``benchmark/compare.py`` builds), compiled for a described
    v5e with no chip, fits the chip's memory with it (14.27 GiB of
    15.75) and does not with the identity in its place (the compiler:
    "Used 17.85G of 15.75G hbm"). The program's forward and backward
    pass ALONE peak 0.33 GB lower without it, and the step 0.14 GB: it
    is there for the comparison. About twenty minutes of compiling, so
    not in tier 1."""
    topologies = pytest.importorskip("jax.experimental.topologies")
    try:
        topology = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip("no described v5e to compile for: %r" % (e,))
    from jax.sharding import SingleDeviceSharding

    from elasticdl_tpu.ops import grouped_matmul as gm

    with open(os.path.join(REPO, "benchmark", "configs", "ling-3.0-flash-vl-ep64.json")) as f:
        sizes = json.load(f)["model_params"]
    one = SingleDeviceSharding(topology.devices[0])
    tokens = jax.ShapeDtypeStruct((2, 4096), jnp.int32, sharding=one)

    def compiled_peak(read_together):
        module = model_utils.load_module(
            os.path.join(REPO, "model_zoo", "transformer_lm", "hybrid_moe_lm.py")
        )
        if not read_together:
            module._read_together = lambda x, *others: (x,) + others
        model = module.custom_model(**sizes)

        def objective(params, tokens):
            logits = model.apply({"params": params}, {"tokens": tokens}, training=True)
            return module.loss(logits, tokens).astype(jnp.float32)

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
            jnp.zeros((2, 4096), jnp.int32),
        )
        params = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), params
        )
        compiled = jax.jit(errors).lower(params, tokens).compile()
        return compiled.memory_analysis().peak_memory_in_bytes

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fa, "kernel_interpret_mode", lambda: False)
        patch.setattr(gm, "kernel_interpret_mode", lambda: False)
        assert compiled_peak(read_together=True) < 15.75 * 2**30
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            compiled_peak(read_together=False)


def _kernel_names(fn, *args):
    names, todo = [], [jax.make_jaxpr(fn)(*args).jaxpr]
    while todo:
        for eqn in todo.pop().eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            todo.extend(
                getattr(j, "jaxpr", j) for j in jax.core.jaxprs_in_params(eqn.params)
            )
    return names


def test_the_latent_layer_takes_the_kernels_from_the_policys_length(zoo):
    """At 1,024 positions the ``l`` layer runs the three kernels under
    their own names (q and k at 24, v at 16); under it, XLA."""
    sizes = {**TOY, "layer_pattern": "l", "num_dense_layers": 1,
             "kda_heads": 0, "kda_head_dim": 0, "kda_conv_kernel": 0,
             "kda_chunk": 0, "kda_gate_lower_bound": 0.0,
             "shared_expert_dim": 0, "num_expert_groups": 1,
             "expert_groups_per_tok": 1}  # fmt: skip
    model = zoo.custom_model(**sizes)
    tokens = np.zeros((1, 1024), np.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), {"tokens": tokens})
    )["params"]

    def objective(params, tokens):
        return zoo.loss(model.apply({"params": params}, {"tokens": tokens}), tokens)

    names = _kernel_names(jax.grad(objective), params, tokens)
    assert sorted(set(names)) == sorted(fa.UNEQUAL.values())
    short = _kernel_names(jax.grad(objective), params, tokens[:, :LENGTH])
    assert short == []


# ---------------------------------------------------------------------------
# one toy job through `edl train`
# ---------------------------------------------------------------------------

STEPS, MINIBATCH, SYNC_EVERY = 8, 2, 4
FACTS = {
    "expert_layers": 3, "experts_held": 4, "experts_routed": 32,
    "kda_layers": 3, "kda_heads": 4, "kda_head_dim": 16, "kda_chunk": 16,
    "mla_layers": 1, "mla_qk_dim": 24, "mla_v_dim": 16,
    "shared_expert_dim": 24, "expert_groups": 4, "expert_groups_per_tok": 2,
    "routing": "sigmoid_bias", "tie_head": 0, "expert_apply": "grouped",
    "remat_layers": 1, "remat_kept_recurrences": 3,
    # one a ``k`` layer: its forward pass's, and none in the backward
    "triangular_solves": 3,
}  # fmt: skip


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    from elasticdl_tpu.data.example import encode_example
    from elasticdl_tpu.data.recordio import create_recordio

    out = tmp_path_factory.mktemp("ling_job")
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
    sizes = {**TOY, "remat_layers": True, "expert_bias_rate": 0.1}
    got = subprocess.run(
        [
            sys.executable, "-m", "elasticdl_tpu.cli", "train",
            "--job_name", "ling",
            "--distribution_strategy", "AllreduceStrategy",
            "--num_workers", "1",
            "--model_zoo", os.path.join(REPO, "model_zoo"),
            "--model_def", "transformer_lm.hybrid_moe_lm.custom_model",
            "--model_params", ",".join("%s=%s" % kv for kv in sizes.items()),
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
        assert w["moe_rows_routed"] == w["steps"] * 3 * MINIBATCH * LENGTH * 4
        assert 0 < w["moe_rows_here"] < w["moe_rows_routed"]
        # the selection bias is state, moved 0.1 a step
        assert 0 < w["expert_bias_abs_max"] <= 0.1 * STEPS + 1e-6
    assert windows[-1]["last_loss"] < windows[0]["first_loss"]
    # 64 positions: under the policy's 1,024, so XLA attention in the
    # latent layer; the dispatching expert layers' grouped products
    # are interpreted (forward, recomputed forward, backward)
    assert built["attention"] == "xla" and built["mesh"] == "data=1"
    assert built["pallas_calls"] == built["pallas_interpreted"] == 3 * 8
