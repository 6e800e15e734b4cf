"""The zoo's hybrid LM with attention over selected keys, softmax
routing and an untied head (model_zoo/transformer_lm/hybrid_moe_lm.py,
ops/sparse_select.py, ops/flash_attention.py's kernels under a
selection) against the plain reference the benchmark keeps
(benchmark/reference/keye_sparse_moe_reference.py, loaded by path as
``benchmark/spec.load_reference`` loads it): float32, toy widths, on the
CPU; and one toy job through ``edl train`` whose events carry the
selection's counters and facts."""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu.common import model_utils
from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.ops import sparse_select
from elasticdl_tpu.parallel import expert

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOPK, LENGTH = 16, 64
TOY = dict(
    vocab_size=256, layer_pattern="ss", num_dense_layers=0, embed_dim=64,
    num_heads=4, num_kv_heads=2, head_dim=16, expert_dim=32, num_experts=16,
    experts_held=4, first_expert_held=4, num_experts_per_tok=2,
    routing="softmax", select_topk=TOPK, indexer_heads=4, indexer_dim=8,
    tie_head=False, expert_apply="masked",
    rope_theta=1e7, norm_eps=1e-6,
)  # fmt: skip
TOL = 1e-5


def _pairs(length, topk):
    """(kept, causal) pairs of one sequence: query t reads
    min(t + 1, topk) keys of its t + 1."""
    reads = np.minimum(np.arange(length) + 1, topk)
    return int(reads.sum()), length * (length + 1) // 2


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(REPO, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _load(
        "benchmark/reference/keye_sparse_moe_reference.py",
        "keye_sparse_moe_reference",
    )


@pytest.fixture(scope="module")
def zoo():
    return model_utils.load_module(
        os.path.join(REPO, "model_zoo", "transformer_lm", "hybrid_moe_lm.py")
    )


TRAINED = ["embed", "head", "final_norm"] + [
    "L%d.%s" % (i, name)
    for i in range(len(TOY["layer_pattern"]))
    for name in (
        "operator_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo",
        "ffn_norm", "router", "expert_w1", "expert_w3", "expert_w2",
    )
]  # fmt: skip


@pytest.fixture(scope="module")
def both_sides(reference, zoo):
    """Loss and gradients of the program and of the reference on the
    same seeded weights and tokens. (Tokens that repeat give the first
    layer's indexer runs of EQUAL scores, which both sides break alike,
    and the second layer's runs of scores one rounding apart, which two
    orders of summation do not: the tie rule has a test of its own.)"""
    model = zoo.custom_model(**TOY)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, LENGTH), 0, 256)
    params = model.init(jax.random.PRNGKey(0), {"tokens": tokens})["params"]

    def objective(params):
        logits = model.apply({"params": params}, {"tokens": tokens}, training=True)
        return zoo.loss(logits, tokens)

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(objective)(params)
        ref_loss, ref_grads = reference.loss_and_grads(
            reference.from_program(params, TOY), tokens, TOY
        )
    return loss, reference.from_program(grads, TOY), ref_loss, ref_grads


def test_loss_matches_the_reference(both_sides):
    loss, _, ref_loss, ref_grads = both_sides
    assert abs(float(loss) - float(ref_loss)) <= TOL * float(ref_loss)
    # the reference returns the trained leaves, all of them, and no other
    assert sorted(ref_grads) == sorted(TRAINED)


@pytest.mark.parametrize("leaf", TRAINED)
def test_gradient_leaf_matches_the_reference(both_sides, leaf):
    _, grads, _, ref_grads = both_sides
    error = jnp.linalg.norm((grads[leaf] - ref_grads[leaf]).ravel()) / (
        jnp.linalg.norm(ref_grads[leaf].ravel())
    )
    assert float(jnp.linalg.norm(ref_grads[leaf].ravel())) > 0
    assert float(error) <= 10 * TOL, (leaf, float(error))


@pytest.fixture(scope="module")
def grouped_and_masked(zoo):
    """Loss and gradients of the toy model with its expert layers
    computed both ways, from the same weights."""
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, LENGTH), 0, 256)
    got = {}
    for apply in zoo.EXPERT_APPLIES:
        model = zoo.custom_model(**dict(TOY, expert_apply=apply))
        params = model.init(jax.random.PRNGKey(0), {"tokens": tokens})["params"]

        def objective(params):
            logits = model.apply({"params": params}, {"tokens": tokens}, training=True)
            return zoo.loss(logits, tokens)

        with jax.default_matmul_precision("highest"):
            got[apply] = jax.value_and_grad(objective)(params)
        got[apply] += (str(jax.make_jaxpr(objective)(params)),)
    return got


def test_every_held_expert_over_every_token_sorts_and_gathers_nothing(
    grouped_and_masked,
):
    """``expert_apply=masked`` is shapes alone: no sort of the
    assignments, no gather of rows, where ``grouped`` has both."""
    grouped, masked = grouped_and_masked["grouped"][2], grouped_and_masked["masked"][2]
    assert " sort[" in grouped and " sort[" not in masked
    np.testing.assert_allclose(
        grouped_and_masked["grouped"][0], grouped_and_masked["masked"][0], rtol=1e-6
    )


@pytest.mark.parametrize("leaf", ["router", "experts_w13", "experts_w2", "ffn_norm", "embed"])
def test_masked_experts_give_the_gradients_grouped_experts_give(
    grouped_and_masked, leaf
):
    """How the share is computed changes no result: the routers' and
    the experts' own gradients and what flows on beneath them."""
    found = 0
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(grouped_and_masked["grouped"][1]),
        jax.tree_util.tree_leaves(grouped_and_masked["masked"][1]),
    ):
        if leaf in jax.tree_util.keystr(path):
            np.testing.assert_allclose(a, b, atol=10 * TOL)
            found += float(jnp.abs(a).max()) > 0
    assert found


@pytest.mark.parametrize("leaf", ["indexer_wq", "indexer_wk", "indexer_ww"])
def test_the_indexer_gets_no_gradient_from_the_lm_loss(both_sides, leaf):
    _, grads, _, ref_grads = both_sides
    for i in range(len(TOY["layer_pattern"])):
        name = "L%d.%s" % (i, leaf)
        assert name not in ref_grads
        assert not np.asarray(grads[name]).any()


def test_a_step_hands_the_indexers_leaves_back_bit_for_bit(zoo):
    """No moments, no decay, no update: AdamW's decay alone would
    shrink a leaf that never gets a gradient."""
    model = zoo.custom_model(**TOY)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, LENGTH), 0, 256)
    params = model.init(jax.random.PRNGKey(0), {"tokens": tokens})["params"]
    tx = zoo.optimizer()
    state = tx.init(params)
    trained = sum(
        x.size
        for path, x in jax.tree_util.tree_leaves_with_path(params)
        if not any(getattr(k, "key", None) == zoo.INDEXER for k in path)
    )
    moments = sum(
        x.size for x in jax.tree_util.tree_leaves(state) if x.ndim
    )
    assert moments == 2 * trained
    for _ in range(2):
        grads = jax.grad(
            lambda p: zoo.loss(model.apply({"params": p}, {"tokens": tokens}), tokens)
        )(params)
        updates, state = tx.update(grads, state, params)
        after = optax.apply_updates(params, updates)
        for i in range(len(TOY["layer_pattern"])):
            name = "layer_%d_attention" % i
            for a, b in zip(
                jax.tree_util.tree_leaves(params[name][zoo.INDEXER]),
                jax.tree_util.tree_leaves(after[name][zoo.INDEXER]),
            ):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert not np.array_equal(
                np.asarray(params[name]["query"]["kernel"]),
                np.asarray(after[name]["query"]["kernel"]),
            )
        params = after


def test_a_model_without_an_indexer_keeps_the_siblings_optimizer(zoo):
    """Nothing to spare: the state is the sibling LM's own tree (the
    accepted cell's step holds what it held), and so are the updates."""
    model = zoo.custom_model(vocab_size=64)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    params = model.init(jax.random.PRNGKey(0), {"tokens": tokens})["params"]
    ours, theirs = zoo.optimizer(), zoo._lm.optimizer(3e-3)
    state, want = ours.init(params), theirs.init(params)
    assert jax.tree_util.tree_structure(state) == jax.tree_util.tree_structure(want)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    got, state = ours.update(grads, state, params)
    expected, want = theirs.update(grads, want, params)
    assert jax.tree_util.tree_structure(state) == jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(expected)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_softmax_route_matches_the_reference(reference):
    """No bias anywhere: the largest probabilities are selected, the
    gates are those probabilities renormalised over the selected with
    nothing beside the sum, and the route is the zoo's older
    ``topk_gate``."""
    w = _layer_weights()
    tokens = w["h"].reshape(-1, w["h"].shape[-1])
    logits = tokens @ w["router"]
    product = reference._product(lambda x: x)
    want = reference.route(tokens, w["router"], dict(num_experts_per_tok=4), product)
    selected, gates = expert.softmax_topk_route(logits, 4)
    got = jnp.zeros_like(want).at[jnp.arange(len(tokens))[:, None], selected].set(gates)
    np.testing.assert_allclose(got, want, atol=TOL)
    np.testing.assert_allclose(gates.sum(-1), 1.0, atol=1e-6)
    old_selected, old_gates = expert.topk_gate(logits, 4)
    np.testing.assert_array_equal(selected, old_selected)
    np.testing.assert_allclose(gates, old_gates, atol=TOL)
    np.testing.assert_allclose(
        expert.softmax_topk_route(logits, 4, scaling=2.5)[1], 2.5 * gates, atol=TOL
    )
    # a router whose logits lie hundreds apart: the largest probability
    # is among the selected, so the sum is one and not 0 / 0; ties
    # between probabilities that have underflowed go to the lower index
    far = jnp.zeros((5, 16)).at[:, 3].set(1000.0)
    selected, gates = expert.softmax_topk_route(far, 4)
    np.testing.assert_array_equal(selected, np.broadcast_to([3, 0, 1, 2], (5, 4)))
    np.testing.assert_array_equal(gates, np.broadcast_to([1.0, 0, 0, 0], (5, 4)))
    grads = jax.grad(lambda x: expert.softmax_topk_route(x, 4)[1].sum())(far)
    assert np.isfinite(np.asarray(grads)).all()


def _layer_weights(seed=3, d=64, width=32, experts=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    return dict(
        h=jax.random.normal(keys[0], (2, 24, d)),
        router=jax.random.normal(keys[1], (d, experts)) * d**-0.5,
        w1=jax.random.normal(keys[2], (experts, d, width)) * d**-0.5,
        w3=jax.random.normal(keys[3], (experts, d, width)) * d**-0.5,
        w2=jax.random.normal(keys[4], (experts, width, d)) * width**-0.5,
    )


@pytest.mark.parametrize(
    "apply", [expert.held_experts_apply, expert.held_experts_apply_masked],
    ids=["grouped", "masked"],
)  # fmt: skip
def test_the_eight_shares_add_up_to_the_uncut_layer(reference, apply):
    """What all eight toy shares give (2 of 16 experts each) under
    softmax top-k, added, equals what the uncut reference layer gives:
    each selected expert's gated output is in exactly one share, the
    gates are normalised over all the selected on every chip alike, and
    nothing is computed on every chip and so counted once. The
    program's layer gives the same shares, by dispatch and grouped
    products and by every held expert over every token alike."""
    w = _layer_weights()
    sizes = dict(num_experts_per_tok=4)
    product = reference._product(lambda x: x)
    with jax.default_matmul_precision("highest"):
        whole = reference.expert_share(
            w["h"], w["router"], w["w1"], w["w3"], w["w2"], 0, sizes, product
        )
        tokens = w["h"].reshape(-1, w["h"].shape[-1])
        selected, gates = expert.softmax_topk_route(tokens @ w["router"], 4)
        np.testing.assert_allclose(gates.sum(-1), 1.0, atol=1e-4)
        shares, program_shares = [], []
        for first in range(0, 16, 2):
            held = slice(first, first + 2)
            shares.append(
                reference.expert_share(
                    w["h"], w["router"], w["w1"][held], w["w3"][held],
                    w["w2"][held], first, sizes, product,
                )  # fmt: skip
            )
            program_shares.append(
                apply(
                    tokens, selected, gates,
                    jnp.concatenate([w["w1"][held], w["w3"][held]], axis=-1),
                    w["w2"][held], first,
                ).reshape(w["h"].shape)  # fmt: skip
            )
    assert len(shares) == 8 and float(jnp.abs(whole).max()) > 0.1
    np.testing.assert_allclose(sum(shares), whole, atol=TOL)
    np.testing.assert_allclose(sum(program_shares), whole, atol=TOL)
    for mine, theirs in zip(program_shares, shares):
        np.testing.assert_allclose(mine, theirs, atol=TOL)


# ---------------------------------------------------------------------------
# the selection
# ---------------------------------------------------------------------------


def _indexer_inputs(length, ids, seed=0, batch=2, heads=4, dim=8):
    """Indexer queries, keys and weights made from token ids the way a
    first layer makes them: equal tokens, equal keys."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    tokens = jax.random.randint(keys[0], (batch, length), 0, ids)
    h = jax.random.normal(keys[1], (ids, 32))[tokens]
    return (
        (h @ jax.random.normal(keys[2], (32, heads * dim))).reshape(
            batch, length, heads, dim
        ),
        h @ jax.random.normal(keys[3], (32, dim)),
        h @ jax.random.normal(keys[4], (32, heads)),
    )


@pytest.mark.parametrize("ids", [5, 4096], ids=["ties", "distinct"])
@pytest.mark.parametrize("topk", [1, 16, 40, 64, 100])
def test_every_key_below_the_topk_and_exactly_k_above_it(reference, topk, ids):
    q, k, w = _indexer_inputs(LENGTH, ids)
    got = np.asarray(sparse_select.select_keys(q, k, w, topk, block=16))
    assert got.dtype == np.int8 and got.shape == (2, LENGTH, LENGTH)
    t = np.arange(LENGTH)
    causal = t[None, :] <= t[:, None]
    assert not (got != 0)[:, ~causal].any()
    np.testing.assert_array_equal(got.sum(-1), np.broadcast_to(np.minimum(t + 1, topk), (2, LENGTH)))
    # every s <= t while t < topk
    below = t < topk
    np.testing.assert_array_equal(got[:, below], np.broadcast_to(causal[below], (2,) + causal[below].shape))
    # and lax.top_k's choice, ties to the lower index: the reference's
    want = reference.selection(q, k, w, 0, topk, reference._product(lambda x: x))
    np.testing.assert_array_equal(got != 0, np.asarray(want))
    kept, of = _pairs(LENGTH, topk)
    assert (kept, of) == (got[0].sum(), causal.sum())


@pytest.mark.parametrize("block, topk", [(16, TOPK), (8, TOPK), (64, TOPK), (32, 40)])
def test_the_blocking_of_the_selection_changes_no_result(block, topk):
    q, k, w = _indexer_inputs(LENGTH, 5, seed=2)
    whole = sparse_select.select_keys(q, k, w, topk, block=LENGTH)
    cut = sparse_select.select_keys(q, k, w, topk, block=block)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(cut))


def _scored_inputs(scores):
    """Indexer operands of one head of one dimension, so that the test
    writes the scores down: that of (t, s) is ``weight * relu(query[t]
    * key[s])``."""
    query, key, weight = scores
    length = len(key)
    return (
        jnp.asarray(query, jnp.float32).reshape(1, length, 1, 1),
        jnp.asarray(key, jnp.float32).reshape(1, length, 1),
        jnp.full((1, length, 1), weight, jnp.float32),
    )


def _tied_scores(case, length):
    """Rows of scores that the tie rule decides, as :func:`_scored_inputs`
    takes them."""
    ones = np.ones(length, np.float32)
    position = np.arange(length)
    if case == "all_equal":
        return ones, ones, 1.0
    if case == "all_zero":  # relu of a negative product: 0.0 everywhere
        return ones, -ones, 1.0
    if case == "zeros_and_minus_infinity":
        # -inf (an infinite product under a negative weight), -0.0 and
        # -1.0 mixed; the keys after a row are -inf too
        key = np.where(position % 4 == 1, np.inf, np.where(position % 4 == 2, 0.0, 1.0))
        return ones, key, -1.0
    if case == "runs_at_the_threshold":
        # runs of eight equal keys: the threshold falls inside a run
        return ones, (position // 8 % 5).astype(np.float32), 1.0
    raise ValueError(case)


@pytest.mark.parametrize(
    "case", ["all_equal", "all_zero", "zeros_and_minus_infinity", "runs_at_the_threshold"]
)
@pytest.mark.parametrize("length, topk, block", [(64, 16, 16), (64, 40, 16), (32, 48, 16), (96, 20, 32)])
def test_ties_at_the_threshold_go_to_the_lower_index(reference, case, length, topk, block):
    """Whole rows of equal scores, zeros of both signs and runs of
    equal keys across the threshold, at lengths on both sides of the
    top-k and with the top-k inside a block: the reference's
    ``lax.top_k`` bit for bit."""
    q, k, w = _scored_inputs(_tied_scores(case, length))
    got = np.asarray(sparse_select.select_keys(q, k, w, topk, block=block))
    want = reference.selection(q, k, w, 0, topk, reference._product(lambda x: x))
    np.testing.assert_array_equal(got != 0, np.asarray(want))
    np.testing.assert_array_equal(
        got[0].sum(-1), np.minimum(np.arange(length) + 1, topk)
    )


@pytest.mark.parametrize("radix_bits", [1, 2, 4, 8, 16])
def test_the_kth_largest_counts_duplicates_and_signed_zeros(monkeypatch, radix_bits):
    """The answer is the bits' and not the passes': 32, 16, 8, 4 or 2
    passes find the same threshold (2 bits a pass is the module's)."""
    assert sparse_select._RADIX_BITS == 2
    monkeypatch.setattr(sparse_select, "_RADIX_BITS", radix_bits)
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 50))
    x = x.at[0, :10].set(0.0).at[0, 10:20].set(-0.0).at[1, :5].set(-jnp.inf)
    keys = sparse_select._ordered_bits(x)
    for k in (1, 7, 25, 50):
        want = jnp.sort(x + 0.0, axis=-1)[:, ::-1][:, k - 1]
        np.testing.assert_array_equal(
            # a row of x a column: the search runs down the major axis
            np.asarray(sparse_select.kth_largest(keys.T, k)),
            np.asarray(sparse_select._ordered_bits(want)),
        )


def test_the_kth_largest_takes_a_k_a_row_and_keys_of_few_bits():
    """The tie rule's use: small integers (a key's place from the
    row's end, 0 where it does not count), a k of each row's own."""
    places = jnp.asarray([[0, 9, 0, 7, 6, 0, 4, 0, 2, 1], [10, 9, 8, 0, 0, 0, 0, 3, 2, 1]], jnp.uint32)
    got = sparse_select.kth_largest(places.T, jnp.asarray([3, 5]), bits=4)
    np.testing.assert_array_equal(np.asarray(got), [6, 2])


PARENT_RUNS = [(0, 4), (4, 8), (8, 12), (12, 16)]  # four runs over every block


@pytest.mark.parametrize(
    "length, topk, block, runs, scores, pairs",
    [
        # the layout until PR 32: 512 x 512 x 4 x (4 + 8 + 12 + 16)
        (8192, 2048, 512, PARENT_RUNS, 41943040, 31460352),
        # rows 0..2047 unscored, six runs of two: 2 x (6 + 8 + .. + 16)
        (8192, 2048, 512, None, 34603008, 31460352),
        # a row a run scores its own keys and no other
        (64, 16, 1, [(t, t + 1) for t in range(16, 64)], 1992, 1992),
        # the top-k inside a block: rows 32..39 are scored and not needed
        (64, 40, 16, None, 2 * 16 * 64, 24 * (41 + 64) // 2),
        # nothing to score
        (64, 64, 16, None, 0, 0),
    ],
    ids=["parent", "cell", "row_a_run", "topk_inside_a_block", "under_the_topk"],
)
def test_the_selections_work_ratio_against_hand_counts(length, topk, block, runs, scores, pairs):
    ratio = sparse_select.select_work_ratio(length, topk, block, runs)
    assert ratio == (scores / pairs if pairs else 1.0)
    if runs is None and pairs:
        assert ratio <= 1.15 or length // block - topk // block < 4


@pytest.mark.parametrize(
    "length, topk, block, free, runs",
    [
        (8192, 2048, 512, 4, [(4, 6), (6, 8), (8, 10), (10, 12), (12, 14), (14, 16)]),
        (16384, 2048, 512, 4, [(4, 9), (9, 14), (14, 19), (19, 24), (24, 28), (28, 32)]),
        (4096, 2048, 512, 4, [(4, 6), (6, 8)]),
        (64, 40, 16, 2, [(2, 4)]),
        (64, 20, 16, 1, [(1, 4)]),
        (2048, 2048, 512, 4, []),
        (1024, 2048, 512, 2, []),
    ],
)
def test_the_runs_of_blocks_follow_length_topk_and_block(length, topk, block, free, runs):
    """Whole blocks under the top-k are not scored; the others go in at
    most six runs of two blocks or more, each up to its own end."""
    assert sparse_select.block_runs(length, min(topk, length), block) == (free, runs)
    assert len(runs) <= sparse_select.MAX_RUNS


def test_rows_under_the_topk_are_made_without_a_loop():
    """At a length within the top-k the whole selection is the causal
    triangle: no product, no search, no loop in the program."""
    q, k, w = _indexer_inputs(LENGTH, 5)
    text = jax.jit(lambda q, k, w: sparse_select.select_keys(q, k, w, LENGTH, block=16)).lower(q, k, w).as_text()
    assert "while" not in text and "dot_general" not in text
    got = np.asarray(sparse_select.select_keys(q, k, w, LENGTH, block=16))
    np.testing.assert_array_equal(got[0], np.tril(np.ones((LENGTH, LENGTH), np.int8)))


# ---------------------------------------------------------------------------
# the kernels under a selection
# ---------------------------------------------------------------------------


def _attention_inputs(length, topk, seed=0, heads=4, dim=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, g = (
        jax.random.normal(key, (2, length, heads, dim)) for key in keys
    )
    selection = sparse_select.select_keys(
        *_indexer_inputs(length, 7, seed=seed), topk, block=16
    )
    return q, k, v, g, selection


@pytest.mark.parametrize(
    "length, topk, tile",
    [
        pytest.param(64, 16, 16, id="over-the-topk-16-tiles"),
        pytest.param(64, 16, 64, id="over-the-topk-one-tile"),
        pytest.param(32, 48, 16, id="under-the-topk"),
        pytest.param(128, 8, 32, id="rows-with-empty-first-tiles"),
    ],
)
def test_the_three_kernels_under_a_selection_match_masked_dense_attention(
    length, topk, tile
):
    """Interpret mode, forward and both backward kernels. A row of a
    tile with no selected key (the last case: 8 keys of up to 128) is
    masked whole, which the online softmax has to survive."""
    q, k, v, g, selection = _attention_inputs(length, topk)
    want, vjp = jax.vjp(
        lambda q, k, v: fa.selected_reference_attention(q, k, v, selection), q, k, v
    )
    out, lse = fa._flash_fwd(q, k, v, True, tile, tile, True, selection=selection)
    np.testing.assert_allclose(out, want, atol=2e-6)
    got = fa._flash_bwd(
        q, k, v, out, lse, g, True, tile, tile, True,
        selection_t=selection.transpose(0, 2, 1),
    )  # fmt: skip
    for mine, theirs in zip(got, vjp(g)):
        np.testing.assert_allclose(mine, theirs, atol=1e-5)
    # and through the public function's own differentiation rule
    mine = jax.grad(
        lambda q, k, v: jnp.sum(
            g * fa.flash_attention_selected(q, k, v, selection, tile, tile)
        ),
        (0, 1, 2),
    )(q, k, v)
    for a, b in zip(mine, got):
        np.testing.assert_allclose(a, b, atol=1e-6)


def _kernel_calls(fn, *args):
    """(name, operands) of every pallas_call in ``fn``'s jaxpr."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"], len(eqn.invars)))
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) else [value]:
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_a_call_without_a_selection_is_the_call_it_was():
    """The three kernels under their old names with their old operands
    (since PR 41 behind the two tables of the grid's tiles); under a
    selection three others, one operand more each."""
    q, k, v, g, selection = _attention_inputs(64, 16)

    def plain(q, k, v):
        return jax.vjp(lambda *a: fa.flash_attention(*a, True, 16, 16), q, k, v)[1](g)

    def selected(q, k, v):
        return jax.vjp(
            lambda *a: fa.flash_attention_selected(*a, selection, 16, 16), q, k, v
        )[1](g)

    assert _kernel_calls(plain, q, k, v) == [
        (fa.FWD_KERNEL, 2 + 3), (fa.BWD_DQ_KERNEL, 2 + 6),
        (fa.BWD_DKV_KERNEL, 2 + 6),
    ]  # fmt: skip
    assert _kernel_calls(selected, q, k, v) == [
        ("edl_flash_sel_fwd", 2 + 4), ("edl_flash_sel_bwd_dq", 2 + 7),
        ("edl_flash_sel_bwd_dkv", 2 + 7),
    ]  # fmt: skip
    # what the plan of a plain call holds is what hbm_traffic walks
    for kernel, operands in ((fa.FWD_KERNEL, 3), (fa.BWD_DKV_KERNEL, 6)):
        _, _, inputs, _ = fa._plan(kernel, 8, 64, 64, 16, 16, 16, True)
        assert len(inputs) == operands
        _, _, inputs, _ = fa._plan(kernel, 8, 64, 64, 16, 16, 16, True, heads=4)
        assert len(inputs) == operands + 1
        assert inputs[-1][1].block_shape == (1, 16, 16)
    for facts in (
        {"mosaic_kernels": list(fa.SELECTED.values()), "pallas_kernels": []},
        {"mosaic_kernels": list(fa.SELECTED), "pallas_kernels": []},
    ):
        assert fa.attention_in_step(facts) == "pallas"
    assert fa.attention_in_step(
        {"mosaic_kernels": [], "pallas_kernels": list(fa.SELECTED.values())}
    ) == "pallas-interpret"
    assert fa.attention_in_step({"mosaic_kernels": [], "pallas_kernels": []}) == "xla"


def test_a_selections_tile_follows_both_axes_and_is_clamped_like_its_operands():
    """dkv's transposed tile goes with its k tile and the q tile its q
    block goes with; the forward's goes with its q tile and its k tile;
    one selection serves the ``heads`` grid rows of a sequence. Since
    PR 41 nothing is clamped: the grid has a step for each tile under
    the diagonal and no other, and a step's tiles come from the two
    tables the call prefetches."""
    _, tables, inputs, _ = fa._plan(fa.FWD_KERNEL, 8, 64, 64, 16, 16, 16, True, heads=4)
    q_tiles, k_tiles = tables
    assert list(zip(q_tiles, k_tiles)) == [(a, b) for a in range(4) for b in range(a + 1)]
    index = dict(inputs)["sel"].index_map
    step = list(zip(q_tiles, k_tiles)).index
    assert index(5, step((2, 1)), *tables) == (1, 2, 1)
    assert index(5, step((1, 1)), *tables) == (1, 1, 1)
    _, tables, inputs, _ = fa._plan(fa.BWD_DKV_KERNEL, 8, 64, 64, 16, 16, 16, True, heads=4)
    q_tiles, k_tiles = tables
    assert list(zip(k_tiles, q_tiles)) == [(a, b) for a in range(4) for b in range(a, 4)]
    index = dict(inputs)["sel_t"].index_map
    step = list(zip(k_tiles, q_tiles)).index
    assert index(3, step((2, 3)), *tables) == (0, 2, 3)
    assert index(3, step((2, 2)), *tables) == (0, 2, 2)
    _, tables, inputs, _ = fa._plan(fa.BWD_DQ_KERNEL, 8, 64, 64, 16, 16, 16, True, heads=4)
    index = dict(inputs)["sel_t"].index_map
    step = list(zip(*tables)).index
    assert index(4, step((2, 1)), *tables) == (1, 1, 2)
    assert index(4, step((1, 1)), *tables) == (1, 1, 1)


def test_the_model_takes_the_kernels_from_the_policys_length(zoo):
    model = zoo.custom_model(**dict(TOY, layer_pattern="s", select_topk=128))
    tokens = jnp.zeros((1, 1024), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), {"tokens": tokens})
    )["params"]
    calls = _kernel_calls(
        lambda p: model.apply({"params": p}, {"tokens": tokens}), params
    )
    assert [name for name, _ in calls if name.startswith("edl_flash")] == [
        "edl_flash_sel_fwd"
    ]
    assert fa.pick_selected_attention(LENGTH) is fa.selected_reference_attention


# ---------------------------------------------------------------------------
# layouts refused, facts, counters
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "sizes, message",
    [
        (dict(layer_pattern="sx"), r"holds \['x'\]"),
        (dict(layer_pattern=""), "no layer"),
        (dict(routing="tanh"), "routing 'tanh' is not one of sigmoid_bias, softmax"),
        (dict(expert_apply="dense"), "expert_apply 'dense' is not one of grouped, masked"),
        (dict(select_topk=0), "select_topk=0"),
        (dict(indexer_heads=0), "indexer_heads=0"),
        (dict(num_experts_per_tok=17), "num_experts_per_tok=17"),
        (dict(num_experts_per_tok=0), "num_experts_per_tok=0"),
    ],
)
def test_a_layout_it_does_not_know_is_refused_by_name(zoo, sizes, message):
    with pytest.raises(ValueError, match=message):
        zoo.custom_model(**dict(TOY, **sizes))


def test_step_facts_cover_the_letter_the_head_and_the_routing(zoo):
    facts = zoo.custom_model(**TOY).step_facts()
    assert facts == {
        "expert_layers": 2, "experts_held": 4, "experts_routed": 16,
        "first_expert_held": 4, "routing": "softmax", "tie_head": 0,
        "expert_apply": "masked", "conv_layers": 0, "attention_layers": 0, "sparse_layers": 2,
        "select_topk": TOPK, "indexer_heads": 4,
    }  # fmt: skip
    plain = zoo.custom_model(vocab_size=64).step_facts()
    assert plain["routing"] == "sigmoid_bias" and plain["tie_head"] == 1
    assert plain["expert_apply"] == "grouped"
    assert "sparse_layers" not in plain and "select_topk" not in plain


def test_window_counters_of_a_selecting_model():
    top = np.iinfo(np.int32).max
    before = {
        "m": {"assignments": np.array([1, 5, 0, 0], np.int32)},
        "a0": {"sel_pairs_kept": np.int32(top - 2), "sel_pairs_causal": np.int32(10)},
        "a1": {"sel_pairs_kept": np.int32(7), "sel_pairs_causal": np.int32(10)},
    }
    wrapped = np.array([top - 2], np.int32) + np.array([9], np.int32)
    after = {
        "m": {"assignments": np.array([2, 6, 7, 1], np.int32)},
        "a0": {"sel_pairs_kept": wrapped[0], "sel_pairs_causal": np.int32(30)},
        "a1": {"sel_pairs_kept": np.int32(16), "sel_pairs_causal": np.int32(30)},
    }  # fmt: skip
    with np.errstate(over="ignore"):
        got = expert.window_routing_counters(before, after, 1, 2)
    assert got == {
        "moe_rows_here": 8, "moe_rows_routed": 10, "moe_rows_max_expert": 7,
        "moe_rows_mean_expert": 4.0, "sel_pairs_kept": 18, "sel_pairs_causal": 40,
    }  # fmt: skip
    # softmax routing keeps no bias, so no field speaks of one; the
    # sigmoid kind's state has it, and its field
    biased = {"m": dict(after["m"], expert_bias=np.array([0.0, -0.2, 0.1, 0.0], np.float32))}
    assert expert.window_routing_counters(None, biased, 1, 2)[
        "expert_bias_abs_max"
    ] == pytest.approx(0.2)
    first = expert.window_routing_counters(None, after, 1, 2)
    assert first["sel_pairs_causal"] == 60 and first["sel_pairs_kept"] == wrapped[0] + 16


# ---------------------------------------------------------------------------
# a toy job through ``edl train``: the events carry counters and facts
# ---------------------------------------------------------------------------

STEPS, MINIBATCH, SYNC_EVERY = 8, 2, 4
FACTS = {
    "expert_layers": 2, "experts_held": 4, "experts_routed": 16,
    "sparse_layers": 2, "select_topk": TOPK, "indexer_heads": 4,
    "routing": "softmax", "tie_head": 0, "expert_apply": "masked",
}  # fmt: skip


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    from elasticdl_tpu.data.example import encode_example
    from elasticdl_tpu.data.recordio import create_recordio

    out = tmp_path_factory.mktemp("keye_job")
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
            "--job_name", "keye",
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


def test_every_train_window_carries_the_selections_counters(job):
    windows, _ = job
    kept, causal = _pairs(LENGTH, TOPK)
    for w in windows:
        # steps x selecting layers x sequences, not times the heads
        assert w["sel_pairs_kept"] == w["steps"] * 2 * MINIBATCH * kept
        assert w["sel_pairs_causal"] == w["steps"] * 2 * MINIBATCH * causal
        assert w["moe_rows_routed"] == w["steps"] * 2 * MINIBATCH * LENGTH * 2
        assert 0 < w["moe_rows_here"] < w["moe_rows_routed"]
        assert "expert_bias_abs_max" not in w  # softmax: no bias state
    assert windows[-1]["last_loss"] < windows[0]["first_loss"]


@pytest.mark.parametrize("fact", sorted(FACTS))
def test_step_built_carries_the_models_fact(job, fact):
    _, built = job
    assert built[fact] == FACTS[fact]


def test_a_masked_share_names_no_dispatch_chunk(zoo, job):
    """``moe_dispatch_chunk_rows`` is the chunk of the dispatching
    layer's loops (``expert_apply=grouped``): every held expert over
    every token has no dispatch, and its ``step_built`` no such
    field; nor has a model without an expert layer."""
    _, built = job
    assert "moe_dispatch_chunk_rows" not in built
    grouped = zoo.custom_model(**dict(TOY, expert_apply="grouped")).step_facts()
    assert grouped["moe_dispatch_chunk_rows"] == expert.DISPATCH_CHUNK_ROWS == 1024
    dense = dict(TOY, expert_apply="grouped", num_dense_layers=len(TOY["layer_pattern"]))
    assert "moe_dispatch_chunk_rows" not in zoo.custom_model(**dense).step_facts()


def test_the_job_ran_the_selecting_attention_it_was_asked_for(job):
    _, built = job
    # 64 positions: under the policy's 1,024, so XLA's masked attention;
    # and every held expert over every token has no grouped product:
    # no kernel at all in this toy step
    assert built["attention"] == "xla"
    assert built["mesh"] == "data=1" and built["donated_inputs"] > 0
    assert built["pallas_calls"] == built["pallas_interpreted"] == 0
