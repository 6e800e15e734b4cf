"""The zoo's hybrid sparse LM (model_zoo/transformer_lm/hybrid_moe_lm.py)
and the held-share expert layer (parallel/expert.py) against the plain
reference the benchmark keeps (benchmark/reference/lfm2_moe_reference.py,
loaded by path as ``benchmark/spec.load_reference`` loads it): float32,
toy widths, on the CPU; and one toy job through ``edl train`` whose
events carry the routing counters."""

import dataclasses
import functools
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
from elasticdl_tpu.parallel import expert

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = dict(
    vocab_size=256, layer_pattern="caccc", num_dense_layers=1, embed_dim=64,
    num_heads=4, num_kv_heads=2, head_dim=16, mlp_dim=128, expert_dim=32,
    num_experts=16, experts_held=4, first_expert_held=4, num_experts_per_tok=2,
)  # fmt: skip
TOL = 1e-5


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(REPO, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _load("benchmark/reference/lfm2_moe_reference.py", "lfm2_moe_reference")


@pytest.fixture(scope="module")
def zoo():
    return model_utils.load_module(
        os.path.join(REPO, "model_zoo", "transformer_lm", "hybrid_moe_lm.py")
    )


def _leaves(pattern, dense):
    names = ["embed", "final_norm"]
    for i, kind in enumerate(pattern):
        layer = ["operator_norm", "ffn_norm"]
        layer += (
            ["conv_in", "conv_taps", "conv_out"]
            if kind == "c"
            else ["wq", "wk", "wv", "q_norm", "k_norm", "wo"]
        )
        layer += (
            ["w1", "w3", "w2"]
            if i < dense
            else ["router", "expert_w1", "expert_w3", "expert_w2"]
        )
        names += ["L%d.%s" % (i, name) for name in layer]
    return names


LEAVES = _leaves(TOY["layer_pattern"], TOY["num_dense_layers"])


@pytest.fixture(scope="module")
def both_sides(reference, zoo):
    """Loss and gradients of the program and of the reference on the
    same seeded weights, tokens and NON-ZERO selection bias."""
    model = zoo.custom_model(**TOY)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 256)
    variables = model.init(jax.random.PRNGKey(0), {"tokens": tokens})
    params = variables["params"]
    state = {
        name: dict(
            layer,
            expert_bias=0.05
            * jax.random.normal(jax.random.PRNGKey(7 + i), (16,)),
        )
        for i, (name, layer) in enumerate(
            sorted(variables[expert.MOE_STATE_COLLECTION].items())
        )
    }

    def objective(params):
        logits = model.apply(
            {"params": params, expert.MOE_STATE_COLLECTION: state},
            {"tokens": tokens},
            training=True,
        )
        return zoo.loss(logits, tokens)

    loss, grads = jax.value_and_grad(objective)(params)
    weights = reference.from_program(params, TOY)
    for name, layer in state.items():
        weights["L%s.expert_bias" % name.split("_")[1]] = layer["expert_bias"]
    ref_loss, ref_grads = reference.loss_and_grads(weights, tokens, TOY)
    return loss, reference.from_program(grads, TOY), ref_loss, ref_grads


def test_loss_matches_the_reference(both_sides):
    loss, _, ref_loss, ref_grads = both_sides
    assert abs(float(loss) - float(ref_loss)) <= TOL * abs(float(ref_loss))
    # the names the reference returns are the leaves compared: every
    # parameter, and no selection bias (its gradient is zero)
    assert sorted(ref_grads) == sorted(LEAVES)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(both_sides, leaf):
    _, grads, _, ref_grads = both_sides
    error = jnp.linalg.norm(grads[leaf] - ref_grads[leaf]) / jnp.linalg.norm(
        ref_grads[leaf]
    )
    assert float(error) <= TOL, (leaf, float(error))


def _layer_weights(seed=3, d=64, width=32, experts=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        h=jax.random.normal(keys[0], (2, 24, d)),
        router=jax.random.normal(keys[1], (d, experts)) * d**-0.5,
        w1=jax.random.normal(keys[2], (experts, d, width)) * d**-0.5,
        w3=jax.random.normal(keys[3], (experts, d, width)) * d**-0.5,
        w2=jax.random.normal(keys[4], (experts, width, d)) * width**-0.5,
        bias=0.05 * jax.random.normal(keys[5], (experts,)),
    )


def test_the_shares_add_up_to_the_uncut_layer(reference):
    """What all four toy shares give (4 of 16 experts each), added,
    equals what the uncut reference layer gives: each selected expert's
    gated output is in exactly one share, the gates are normalised over
    all the selected on every chip alike, and nothing (no shared
    expert, no bias term) is computed on every chip and so counted
    once. The program's layer gives the same shares."""
    w = _layer_weights()
    sizes = dict(reference.DEFAULTS, num_experts_per_tok=2)
    product = reference._product(lambda x: x)
    with jax.default_matmul_precision("highest"):
        whole = reference.expert_share(
            w["h"], w["router"], w["w1"], w["w3"], w["w2"], w["bias"], 0,
            sizes, product,
        )  # fmt: skip
        shares, program_shares = [], []
        tokens = w["h"].reshape(-1, w["h"].shape[-1])
        selected, gates = expert.sigmoid_topk_route(
            tokens @ w["router"], w["bias"], 2
        )
        for first in (0, 4, 8, 12):
            held = slice(first, first + 4)
            shares.append(
                reference.expert_share(
                    w["h"], w["router"], w["w1"][held], w["w3"][held],
                    w["w2"][held], w["bias"], first, sizes, product,
                )  # fmt: skip
            )
            program_shares.append(
                expert.held_experts_apply(
                    tokens,
                    selected,
                    gates,
                    jnp.concatenate([w["w1"][held], w["w3"][held]], axis=-1),
                    w["w2"][held],
                    first,
                ).reshape(w["h"].shape)
            )
    assert float(jnp.abs(whole).max()) > 0.1
    np.testing.assert_allclose(sum(shares), whole, atol=TOL)
    np.testing.assert_allclose(sum(program_shares), whole, atol=TOL)
    for mine, theirs in zip(program_shares, shares):
        np.testing.assert_allclose(mine, theirs, atol=TOL)
    # with every expert held the share is the whole layer
    everything = expert.held_experts_apply(
        tokens, selected, gates,
        jnp.concatenate([w["w1"], w["w3"]], axis=-1), w["w2"], 0,
    ).reshape(w["h"].shape)  # fmt: skip
    np.testing.assert_allclose(everything, whole, atol=TOL)


# ---------------------------------------------------------------------------
# the dispatch moves the held rows only: the layer against the repo's
# other exact form of it, every held expert over every token
# ---------------------------------------------------------------------------

# the tests' chunk: 48 tokens x 2 assignments are six of them
CHUNK, TOKENS, CHOICES = 16, 48, 2


def _routing(held_rows):
    """(selected, first_expert_held, experts_held) of 16 experts with
    ``held_rows`` of the 96 assignments routed to held experts;
    ``"pairs"``: BOTH of every even token's and neither of any odd
    token's; ``"all"``: every expert held, a router's own top-2."""
    if held_rows == "all":
        logits = jax.random.normal(jax.random.PRNGKey(5), (TOKENS, 16))
        return expert.sigmoid_topk_route(logits, jnp.zeros(16), CHOICES)[0], 0, 16
    token = np.arange(TOKENS)[:, None]
    choice = np.arange(CHOICES)[None, :]
    # absent: 0-3 and 8-15; held: 4-7; distinct within a token
    absent = np.where(choice == 0, token % 4, 8 + token % 8)
    held = 4 + (token + choice) % 4
    if held_rows == "pairs":
        here = np.broadcast_to(token % 2 == 0, absent.shape)
    else:
        here = np.zeros(TOKENS * CHOICES, bool)
        here[np.random.default_rng(11).permutation(here.size)[:held_rows]] = True
        here = here.reshape(absent.shape)
    return jnp.asarray(np.where(here, held, absent), jnp.int32), 4, 4


HELD_ROWS = {
    "no-row": (0, 0),
    "one-row": (1, 1),
    "a-chunk": (CHUNK, CHUNK),
    "a-chunk-and-a-row": (CHUNK + 1, CHUNK + 1),
    "both-of-a-token-none-of-the-next": ("pairs", TOKENS),
    "every-row": ("all", TOKENS * CHOICES),
}


@functools.lru_cache(maxsize=None)
def _both_forms(case):
    """Output and gradients (x, gates, w_in, w_out) of the dispatching
    layer and of the masked one, in float32, with the chunk at
    ``CHUNK`` rows and every buffer a loop fills part of holding NaN
    first (on the chip: whatever the memory held), so that a pass that
    read a row it did not write would show."""
    held_rows, count = HELD_ROWS[case]
    selected, first, held = _routing(held_rows)
    local = selected - first
    assert int(((local >= 0) & (local < held)).sum()) == count
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(keys[0], (TOKENS, 64))
    gates = jax.nn.softmax(jax.random.normal(keys[1], (TOKENS, CHOICES)))
    w_in = jax.random.normal(keys[2], (held, 64, 64)) / 8
    w_out = jax.random.normal(keys[3], (held, 32, 64)) / 6
    cotangent = jax.random.normal(keys[4], (TOKENS, 64))

    def both(apply):
        def objective(x, gates, w_in, w_out):
            out = apply(x, selected, gates, w_in, w_out, first)
            return jnp.sum(out * cotangent), out

        (_, out), grads = jax.value_and_grad(
            objective, argnums=(0, 1, 2, 3), has_aux=True
        )(x, gates, w_in, w_out)
        return dict(zip(("out", "x", "gates", "w_in", "w_out"), (out, *grads)))

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(expert, "DISPATCH_CHUNK_ROWS", CHUNK)
        patch.setattr(
            jax.lax, "empty", lambda shape, dtype: jnp.full(shape, jnp.nan, dtype)
        )
        assert expert.dispatch_chunk_rows(TOKENS * CHOICES) == CHUNK
        # the layer is jitted: a trace of these shapes made with the
        # program's own chunk must not answer for this one, nor this
        # one for a later test
        jax.clear_caches()
        try:
            with jax.default_matmul_precision("highest"):
                return (
                    both(expert.held_experts_apply),
                    both(expert.held_experts_apply_masked),
                )
        finally:
            jax.clear_caches()


@pytest.mark.parametrize("what", ["out", "x", "gates", "w_in", "w_out"])
@pytest.mark.parametrize("case", sorted(HELD_ROWS))
def test_the_dispatch_over_the_held_rows_is_exact_for_any_routing(case, what):
    """No capacity and no dropped assignment: with no row routed here,
    one, a chunk of them, a chunk and one, both assignments of some
    tokens and none of their neighbours', and every row (every expert
    held: the loops run their whole length), the result and each
    gradient are those of every held expert over every token."""
    dispatched, masked = _both_forms(case)
    assert np.isfinite(np.asarray(dispatched[what])).all()
    np.testing.assert_allclose(dispatched[what], masked[what], atol=TOL)
    if what == "out" and case != "no-row":
        assert float(jnp.abs(masked["out"]).max()) > 0.01


@pytest.mark.parametrize(
    "rows, chunk",
    [(32768, 1024), (96, 96), (3000, 1000), (2048, 1024), (1024, 1024), (7, 7)],
)
def test_a_chunk_divides_its_buffer(rows, chunk):
    assert expert.dispatch_chunk_rows(rows) == chunk
    # a multiple of the grouped products' row tile where the buffer is
    assert rows % 512 or chunk % 512 == 0


def test_no_pass_of_the_step_moves_the_whole_buffer(zoo, monkeypatch):
    """The lowered step of a small model (as the chip would be handed
    it: kernels not interpreted, lowered for the TPU from here) gathers
    no array of ``T * k`` rows, and holds the parent's kernels and no
    other: 4 expert layers x 6 grouped products (attention at 128
    positions is XLA's), which is what ``tpu_custom_calls`` of a
    benchmark configuration counts."""
    import re

    from elasticdl_tpu.ops import flash_attention, grouped_matmul

    monkeypatch.setattr(flash_attention, "kernel_interpret_mode", lambda: False)
    monkeypatch.setattr(grouped_matmul, "kernel_interpret_mode", lambda: False)
    monkeypatch.setattr(expert, "DISPATCH_CHUNK_ROWS", 128)
    jax.clear_caches()  # the jitted layer's traces at another chunk
    model = zoo.custom_model(
        **dict(TOY, embed_dim=128, head_dim=64, expert_dim=128, dtype="bfloat16")
    )
    tokens = jnp.zeros((2, 128), jnp.int32)
    buffer_rows = tokens.size * TOY["num_experts_per_tok"]
    state = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), {"tokens": tokens})
    )
    params = state.pop("params")

    def objective(params, state):
        logits = model.apply(
            dict(state, params=params), {"tokens": tokens}, training=True
        )
        return zoo.loss(logits, tokens)

    text = (
        jax.jit(jax.value_and_grad(objective))
        .trace(params, state)
        .lower(lowering_platforms=("tpu",))
        .as_text()
    )
    assert text.count("@tpu_custom_call") == 4 * 6
    gathered = re.findall(r"stablehlo\.gather.*-> tensor<(\d+)x(\d+)x", text)
    assert ("%d" % tokens.size, "128") in gathered  # a token's sum, brought out
    assert ("128", "128") in gathered  # a chunk
    assert not [shape for shape in gathered if int(shape[0]) >= buffer_rows]


def test_short_convolution_is_causal_and_matches_the_reference(reference, zoo):
    conv = zoo.ShortConv(kernel_size=3, dtype=jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 64))
    params = conv.init(jax.random.PRNGKey(1), h)["params"]
    out = conv.apply({"params": params}, h)
    # position t ignores t+1 and everything after
    later = h.at[:, 17:].add(jax.random.normal(jax.random.PRNGKey(2), (2, 15, 64)))
    changed = conv.apply({"params": params}, later)
    np.testing.assert_array_equal(out[:, :17], changed[:, :17])
    assert float(jnp.abs(out[:, 17:] - changed[:, 17:]).max()) > 0.01
    with jax.default_matmul_precision("highest"):
        want = reference.short_conv(
            h,
            params["in_proj"]["kernel"],
            params["conv_kernel"],
            params["out_proj"]["kernel"],
            reference._product(lambda x: x),
        )
        np.testing.assert_allclose(out, want, atol=TOL)


def test_repeated_kv_attention_through_the_kernel_matches_the_reference(
    reference, zoo
):
    """Grouped KV heads, repeated in front of the (interpreted) flash
    kernels at L = 1,024, against the reference's attention, which
    never repeats a head."""
    attention = zoo.GroupedAttention(
        num_heads=4, num_kv_heads=2, head_dim=16, rope_theta=1e6,
        norm_eps=1e-5, dtype=jnp.float32, use_flash=True,
    )  # fmt: skip
    h = jax.random.normal(jax.random.PRNGKey(0), (1, 1024, 64))
    positions = jnp.arange(1024, dtype=jnp.int32)[None]
    params = attention.init(jax.random.PRNGKey(1), h, positions)["params"]
    jaxpr = str(
        jax.make_jaxpr(lambda h: attention.apply({"params": params}, h, positions))(h)
    )
    assert "edl_flash_fwd" in jaxpr
    out = attention.apply({"params": params}, h, positions)
    w = {
        "wq": params["query"]["kernel"], "wk": params["key"]["kernel"],
        "wv": params["value"]["kernel"], "wo": params["out"]["kernel"],
        "q_norm": 1.0 + 0.0 * params["q_norm"]["scale"],
        "k_norm": params["k_norm"]["scale"],
    }  # fmt: skip
    with jax.default_matmul_precision("highest"):
        want = reference.attention(
            h, w, reference.DEFAULTS, reference._product(lambda x: x)
        )
    np.testing.assert_allclose(out, want, atol=2e-5)


def test_the_bias_follows_the_load_and_takes_no_gradient(zoo):
    rate = 1e-3
    load = jnp.array([10, 0, 5, 5], jnp.int32)  # mean 5
    bias = jnp.array([0.2, -0.1, 0.0, 0.3])
    after = expert.expert_bias_update(bias, load, rate)
    # falls for the over-loaded expert, rises for the under-loaded one
    np.testing.assert_allclose(after - bias, [-rate, rate, 0.0, 0.0], atol=1e-7)
    # through a training apply of the model: one step's assignments
    # move each bias by the rate, and are counted
    model = zoo.custom_model(**TOY)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 256)
    variables = model.init(jax.random.PRNGKey(0), {"tokens": tokens})
    state = variables[expert.MOE_STATE_COLLECTION]
    assert all(not np.asarray(leaf).any() for leaf in jax.tree_util.tree_leaves(state))
    _, new = model.apply(variables, {"tokens": tokens}, training=True, mutable=[expert.MOE_STATE_COLLECTION])
    for layer in new[expert.MOE_STATE_COLLECTION].values():
        made = np.asarray(layer["assignments"])
        assert made.sum() == 2 * 64 * 2  # every assignment, held or not
        moved = np.asarray(layer["expert_bias"])
        np.testing.assert_allclose(moved, rate * np.sign(made.mean() - made), atol=1e-7)
    # an evaluation forward leaves the state alone, and a forward
    # without the collection runs with a zero bias (its initial value)
    no_state = model.apply({"params": variables["params"]}, {"tokens": tokens})
    with_state = model.apply(variables, {"tokens": tokens})
    np.testing.assert_array_equal(no_state, with_state)

    def loss_of_bias(bias):
        layers = {
            name: dict(layer, expert_bias=bias) for name, layer in state.items()
        }
        logits = model.apply(
            {"params": variables["params"], expert.MOE_STATE_COLLECTION: layers},
            {"tokens": tokens},
        )
        return zoo.loss(logits, tokens)

    gradient = jax.grad(loss_of_bias)(0.05 * jnp.ones((16,)))
    assert not np.asarray(gradient).any()


def test_window_counters_are_differences_that_survive_the_wrap():
    top = np.iinfo(np.int32).max
    before = {"a": {"assignments": np.array([top - 1, 5, 0, 0], np.int32),
                    "expert_bias": np.zeros(4, np.float32)}}  # fmt: skip
    after = {"a": {"assignments": np.array([top, 6, 7, 1], np.int32) + np.int32(3),
                   "expert_bias": np.array([0.0, -0.004, 0.002, 0.0], np.float32)}}  # fmt: skip
    with np.errstate(over="ignore"):
        got = expert.window_routing_counters(before, after, 1, 2)
    assert got == {
        "moe_rows_here": 4 + 10,
        "moe_rows_routed": 4 + 4 + 10 + 4,
        "moe_rows_max_expert": 10,
        "moe_rows_mean_expert": 7.0,
        "expert_bias_abs_max": pytest.approx(0.004),
    }
    first = expert.window_routing_counters(None, before, 1, 2)
    assert first["moe_rows_here"] == 5 and first["moe_rows_routed"] == top + 4


def test_the_dense_lm_keeps_its_rotary_base(zoo):
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 16))
    positions = jnp.arange(8)[None]
    np.testing.assert_array_equal(
        zoo._lm._rotary(x, positions), zoo._lm._rotary(x, positions, 10000.0)
    )
    assert float(jnp.abs(zoo._lm._rotary(x, positions) - zoo._lm._rotary(x, positions, 1e6)).max()) > 1e-3


@pytest.mark.parametrize(
    "sizes, message",
    [
        ({"layer_pattern": "cxc"}, "layer_pattern"),
        ({"layer_pattern": ""}, "layer_pattern"),
        ({"num_dense_layers": 6}, "num_dense_layers"),
        ({"num_heads": 3}, "num_kv_heads"),
        ({"first_expert_held": 14}, "experts held"),
    ],
)
def test_a_layout_that_cannot_be_built_is_refused(zoo, sizes, message):
    with pytest.raises(ValueError, match=message):
        zoo.custom_model(**dict(TOY, **sizes))


# ---------------------------------------------------------------------------
# the records: every kind of layer, walked by the one rule
# ---------------------------------------------------------------------------

SMALL = dict(vocab_size=64, embed_dim=32, num_heads=4, num_kv_heads=2, head_dim=8)
# a stack that holds the kind (its sizes admissible), and what no other
# stack of these shares a size with: one that holds none of it
WITH = {
    "c": dict(layer_pattern="c"),
    "m": dict(
        layer_pattern="m", ssm_heads=4, ssm_head_dim=8, ssm_state=8, ssm_groups=2,
        ssm_conv_kernel=4, ssm_chunk=8,
    ),
    "a": dict(layer_pattern="a"),
    "s": dict(layer_pattern="s", select_topk=4),
    "w": dict(layer_pattern="w", attention_window=4),
    "k": dict(
        layer_pattern="k", kda_heads=2, kda_head_dim=16, kda_conv_kernel=4,
        kda_gate_lower_bound=-5.0, kda_chunk=16,
    ),
    "l": dict(
        layer_pattern="l", mla_kv_rank=8, mla_nope_dim=8, mla_rope_dim=4, mla_v_dim=8
    ),
    "dense": dict(layer_pattern="c", num_dense_layers=1),
    "expert": dict(layer_pattern="c", num_dense_layers=0),
}  # fmt: skip
WITHOUT = dict(
    WITH, c=WITH["a"], a=WITH["c"], s=WITH["c"], w=WITH["c"], m=WITH["c"],
    k=WITH["c"], l=WITH["c"], dense=WITH["expert"], expert=WITH["dense"],
)  # fmt: skip


def _record(zoo, kind):
    return {"dense": zoo.DENSE_FF, "expert": zoo.EXPERT_FF}.get(kind) or zoo.KINDS[kind]


def _another(value):
    """A value other than the default ``value``, of its type."""
    if isinstance(value, bool):
        return not value
    return "another" if isinstance(value, str) else value + 1


def _inadmissible(value):
    if isinstance(value, (bool, str)):
        return "neither"
    return -1 if isinstance(value, int) else float("nan")


def test_every_letter_and_every_field_has_its_place(zoo):
    """A record a letter, and of the 58 values ``custom_model`` takes
    (PR 46: ``mla_q_rank``, the ``l`` record's own, and the prediction
    module's ``mtp_layers`` and ``mtp_loss_weight``, shared) each is one
    record's own or shared: by the three kinds of grouped attention, or
    by everything."""
    assert list(WITH) == list(zoo.KINDS) + ["dense", "expert"]
    assert zoo.LETTERS == {letter: kind.says for letter, kind in zoo.KINDS.items()}
    assert all(zoo.LETTERS.values())
    fields = {f.name for f in dataclasses.fields(zoo.HybridMoELM)} - {"parent", "name"}
    assert len(fields) == 58
    owners = {}
    for kind in WITH:
        for size in _record(zoo, kind).sizes:
            owners.setdefault(size.name, []).append(kind)
    assert set(owners) <= fields
    assert {name: kinds for name, kinds in owners.items() if len(kinds) > 1} == {
        "rope": ["a", "s"], "qk_norm": ["a", "s", "w"], "attention_scale": ["a", "s", "w"],
    }  # fmt: skip
    shared = {size.name for size in zoo.SHARED}
    for kind in WITH:
        shared |= set(_record(zoo, kind).reads)
    assert shared <= fields and not shared & set(owners)


@pytest.mark.parametrize("kind", list(WITH))
def test_a_kind_builds_is_checked_and_says_nothing_where_it_is_not(zoo, kind):
    record = _record(zoo, kind)
    model = zoo.custom_model(**SMALL, **WITH[kind])
    tokens = jnp.zeros((1, 16), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), {"tokens": tokens})
    assert any(key.endswith("_" + record.name) for key in variables["params"])
    assert model.apply(variables, {"tokens": tokens}).shape == (1, 16, 64)
    assert record.sizes
    for size in record.sizes:
        default = getattr(zoo.HybridMoELM, size.name)
        with pytest.raises(ValueError, match=size.name + ".* say nothing"):
            zoo.custom_model(**SMALL, **WITHOUT[kind], **{size.name: _another(default)})
        if size.check:
            given = dict(WITH[kind], **{size.name: _inadmissible(default)})
            with pytest.raises(ValueError, match="holds .*: .*" + size.name):
                zoo.custom_model(**SMALL, **given)


# ---------------------------------------------------------------------------
# the benchmark's count of operations against the hand count
# ---------------------------------------------------------------------------

PUBLISHED_SHARE = dict(
    vocab_size=8192, layer_pattern="caccc", num_dense_layers=1, embed_dim=2048,
    num_heads=32, num_kv_heads=8, head_dim=64, mlp_dim=11776, expert_dim=1536,
    num_experts=64, experts_held=8, first_expert_held=0, num_experts_per_tok=4,
)  # fmt: skip


def test_cost_module_against_the_hand_count():
    cost = _load("benchmark/cost/lfm2_moe_share.py", "lfm2_moe_share")
    d, f, F = 2048, 1536, 11776
    conv, attention = 4 * d * d, 2 * d * d + 2 * d * 512
    always = 4 * conv + attention + 3 * d * F + 4 * d * 64 + 8192 * d
    assert cost.matmul_params(PUBLISHED_SHARE) == always == 167_247_872
    # 4 a token, an eighth of them here, in each of 4 expert layers
    assert cost.expert_params_per_token(PUBLISHED_SHARE) == 4 * 4 / 8 * 3 * d * f
    flops = cost.train_flops_per_token(PUBLISHED_SHARE, 2048)
    assert flops == 6 * (always + 2 * 3 * d * f) + 6 * 2048 * 32 * 64
    assert round(flops / 1e6) == 1142
    # the configuration file asks for this module with these sizes
    with open(os.path.join(REPO, "benchmark/configs/lfm2-24b-a2b-ep8.json")) as f:
        config = json.load(f)
    assert config["cost"] == "lfm2_moe_share"
    assert {k: config["model_params"][k] for k in PUBLISHED_SHARE} == PUBLISHED_SHARE


# ---------------------------------------------------------------------------
# a toy job through ``edl train``: the events carry counters and facts
# ---------------------------------------------------------------------------

STEPS, MINIBATCH, SYNC_EVERY = 16, 4, 8
COUNTERS = (
    "moe_rows_here", "moe_rows_routed", "moe_rows_max_expert",
    "moe_rows_mean_expert", "expert_bias_abs_max",
)  # fmt: skip
FACTS = {
    "expert_layers": 4, "experts_held": 4, "experts_routed": 16,
    "first_expert_held": 4, "conv_layers": 4, "attention_layers": 1,
    "moe_dispatch_chunk_rows": 1024,
}  # fmt: skip


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    from elasticdl_tpu.data.example import encode_example
    from elasticdl_tpu.data.recordio import create_recordio

    out = tmp_path_factory.mktemp("hybrid_job")
    data = out / "data"
    data.mkdir()
    rng = np.random.default_rng(0)
    with create_recordio(str(data / "tokens.edlr")) as w:
        for _ in range(STEPS * MINIBATCH):
            w.write(encode_example({"tokens": rng.integers(0, 64, size=64).astype(np.int64)}))
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", EDL_DIST_PLATFORM="cpu",
        EDL_LOCAL_DEVICES="1", XLA_FLAGS="", PYTHONPATH=REPO,
    )  # fmt: skip
    env.pop("EDL_PROFILE_DIR", None)
    events_path = out / "events.jsonl"
    got = subprocess.run(
        [
            sys.executable, "-m", "elasticdl_tpu.cli", "train",
            "--job_name", "hybrid",
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


@pytest.mark.parametrize("counter", COUNTERS)
def test_every_train_window_carries_the_routing_counter(job, counter):
    windows, _ = job
    # a window ends one step behind its aligned sync; the job's end
    # validates the step left in flight
    assert [w["steps"] for w in windows] == [SYNC_EVERY - 1, SYNC_EVERY, 1]
    for w in windows:
        assert w[counter] >= 0, (counter, w)


def test_the_routing_counters_add_up(job):
    windows, _ = job
    for w in windows:
        # steps x expert layers x tokens x assignments a token
        assert w["moe_rows_routed"] == w["steps"] * 4 * MINIBATCH * 64 * 2
        assert 0 < w["moe_rows_here"] < w["moe_rows_routed"]
        assert w["moe_rows_mean_expert"] == w["moe_rows_here"] / 16
        assert w["moe_rows_max_expert"] >= w["moe_rows_mean_expert"]
        assert 0 < w["expert_bias_abs_max"] <= 1e-3 * STEPS + 1e-6
        assert w["last_loss"] < windows[0]["first_loss"]


@pytest.mark.parametrize("fact", sorted(FACTS))
def test_step_built_carries_the_models_fact(job, fact):
    _, built = job
    assert built[fact] == FACTS[fact]


def test_an_untraced_jobs_step_built_is_what_it_was(job):
    """Without ``EDL_PROFILE_DIR`` the event carries no field of the
    traced run's (the compiled step's ops by class, the compiler's
    memory account): exactly the keys it had before they existed."""
    _, built = job
    assert set(built) == {
        "kind", "id", "ts", "src_id", "src_ts", "worker",
        "platform", "device_kind", "device_count", "mesh", "attention",
        "pallas_calls", "pallas_interpreted", "tpu_custom_calls",
        "triangular_solves", "mosaic_kernels", "donated_inputs", "record_reader",
        "compile_cache_dir",
        # the model's own (``step_facts``)
        "routing", "expert_apply", "tie_head", *FACTS,
    }  # fmt: skip
    assert built["triangular_solves"] == 0  # a ``k`` layer's alone


def test_step_built_names_the_grouped_matmul_kernels(job):
    _, built = job
    # interpreted here; on the chip the same names are mosaic_kernels
    assert built["mesh"] == "data=1" and built["donated_inputs"] > 0
    assert built["pallas_calls"] == built["pallas_interpreted"] == 4 * 6


def test_a_model_without_experts_reports_no_routing():
    """Fields absent, not zero: the dense LM's worker says nothing."""
    from types import SimpleNamespace

    from elasticdl_tpu.worker.elastic_allreduce_worker import ElasticAllReduceWorker

    worker = ElasticAllReduceWorker.__new__(ElasticAllReduceWorker)
    worker._model_facts, worker._routing_seen = {}, None
    worker.trainer = SimpleNamespace(routing_state=lambda: None)
    assert worker._window_routing() == {}


@pytest.mark.parametrize("devices", [1, 2])
def test_routing_state_is_kept_on_a_mesh_of_one_device_only(
    zoo, monkeypatch, devices
):
    """The layer counts its own device's tokens and the step leaves
    integer state as each device has it: on two devices the replicas'
    biases and counters would drift apart unseen, so establish refuses
    the model there, before a step is built."""
    import optax
    from jax.sharding import Mesh

    import elasticdl_tpu.parallel.distributed as dist_mod
    from elasticdl_tpu.parallel import elastic

    monkeypatch.setattr(dist_mod, "ensure_world", lambda s, **k: None)
    monkeypatch.setattr(
        elastic,
        "build_world_mesh",
        lambda axes_fn=None: Mesh(
            np.asarray(jax.devices()[:devices]), ("data",)
        ),
    )
    trainer = elastic.ElasticDPTrainer(
        zoo.custom_model(**TOY), zoo.loss, optax.sgd(0.05)
    )
    trainer.default_minibatch_size = 2
    tokens = np.zeros((2, 64), np.int32)
    world = dist_mod.WorldSpec(
        coordinator="", num_processes=1, process_id=0, epoch=0
    )
    try:
        if devices == 1:
            trainer.establish(world, example_batch=({"tokens": tokens}, tokens))
            # nothing validated yet; then the first step's own receipt
            assert trainer.routing_state() is None
            trainer.train_step({"tokens": tokens}, tokens, 2, sync=True)
            assert len(trainer.routing_state()) == 4  # the expert layers
        else:
            with pytest.raises(NotImplementedError, match="2 devices"):
                trainer.establish(
                    world, example_batch=({"tokens": tokens}, tokens)
                )
    finally:
        trainer.close()
