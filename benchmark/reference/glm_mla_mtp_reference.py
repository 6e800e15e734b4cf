"""GLM-4.7-Flash's language model, one chip's share, written out
plainly: forward, both losses, gradients.

float32 throughout, matrix products at ``highest`` precision, no flax
module, no kernel, no tile, no sorting or grouping of rows: attention is
a softmax over every key, every held expert runs every token, and the
prediction module runs the ``L - 1`` positions it has and no other. This
is what ``correct`` compares the program's
``hybrid_moe_lm.custom_model`` + ``loss`` against, on the same weights
and the same batch. It imports nothing of the program and nothing of
the other references.

The equations, from the published configuration (``config.json`` of
zai-org/GLM-4.7-Flash, ``model_type`` ``glm4_moe_lite``) and the papers
its keys come from (each reading is in the configuration file's
``assumed``). With ``rms(x; g) = x / sqrt(mean(x^2) + eps) * g`` (eps
1e-5, weight only; no bias anywhere), every layer:

    x_0 = E[tok]
    x' = x + Mixer(rms(x; g_op));   x'' = x' + FF(rms(x'; g_ff))

Mixer, latent attention with a low-rank query (DeepSeek-V2,
arXiv:2405.04434 section 2.1, in the expanded form it is trained in),
H heads, h the mixer's normed input:
    c_q = rms(h W_qa; g_q)                      (q_lora_rank wide)
    q = c_q W_qb -> H x [nope | rope]
    [c | k_rope] = h W_kva;  c <- rms(c; g_c)   (kv_lora_rank | rope)
    [k_nope | v] = c W_kvb -> H x [nope | v]
    q_rope and k_rope rotated by position (base theta, the halves of
    the rotated part paired), k_rope ONE head that all H read
    o_h = causal softmax(q_h . [k_nope_h | k_rope] / sqrt(nope + rope)) v_h
    Mixer = concat_h(o_h) W_o
FF of the first ``num_dense_layers`` layers: W_2 (silu(u W_1) * (u W_3)).
FF of the others, with u its normed input (DeepSeek-V3,
arXiv:2412.19437 section 2.1.2, ``noaux_tc`` with one group):
    s = sigmoid(u W_r)               (E scores)
    the k largest s are selected     (the selection bias is state, zero here)
    gate_e = scaling * s_e / sum over the selected of s
    FF = Shared(u) + sum over e selected that this chip holds of gate_e Expert_e(u)
    Shared, Expert_e: W_2 (silu(u W_1) * (u W_3))
The gates are over all the selected, held or not; what the absent
experts would have added is left out, and x'' is what goes on: in the
program and here alike. The shared expert is no share: it is added
whole.

    logits_i = rms(x_last_i; g_f) W_head          (untied, over the slice)
    L_lm = mean over i = 0..L-2 of CE(logits_i, tok_{i+1})

The prediction module (DeepSeek-V3 section 2.2, depth 1; embedding and
head are the trunk's own), with x the trunk's output in FRONT of its
final norm:
    u_i = [rms(x_i; g_h) ; rms(E[tok_{i+1}]; g_e)] W_M     i = 0..L-2
    y = Layer(u)       one more layer as above, expert FF, its own
                       weights, positions 0..L-2, causal
    logits1_i = rms(y_i; g_m) W_head
    L_mtp = mean over i = 0..L-3 of CE(logits1_i, tok_{i+2})
    loss = L_lm + lambda * L_mtp

How it is computed, which changes when values exist and not which:
layer by layer, each rematerialised, and inside a layer one sequence
after the other; attention HEAD_GROUP heads at a time, in blocks of
QUERY_BLOCK queries against every key (20 heads x 8,192 x 8,192 scores
in float32 are 5.4 GB whole);
a feed-forward half in blocks of FF_ROWS positions, the held experts
one after the other, each over every token; a head and its softmax in
chunks of LOSS_ROWS positions.

``model_params`` read here: ``layer_pattern`` (its length), ``num_dense_layers``,
``mla_kv_rank``, ``mla_nope_dim``, ``first_expert_held``,
``num_experts_per_tok``, ``routed_scaling_factor``, ``rope_theta``,
``norm_eps``, ``mtp_loss_weight``.
"""

import jax
import jax.numpy as jnp

# The tolerances, and why; every reading is in PERF.md section 2 (PR 46).
#
# The configuration computes in bfloat16 with f32 parameters, f32
# accumulation and an f32 router; the reference is f32 at highest
# precision. Each gradient leaf is compared by its relative L2 error
# over the whole leaf. Two things set the error of a sound bf16 run:
# rounding, as in the dense LM, and, by far the most, the routers'
# choice of experts, as in ``lfm2_moe_reference.py`` (the same 4 of 64
# sigmoid scores): the 4th and 5th scores lie a rounding apart for some
# tokens, and a token that swaps an expert changes a gate and an
# expert's rows, here and in every later layer, which the backward pass
# carries to every earlier leaf. Five routers lie one behind the other
# (four in the trunk, then the module's, which reads the trunk's last
# state).
#
# Measured on the v5e at the cell's sizes (2 x 8,192 tokens, published
# widths; my chip runs, PR 46). Sound program, the worst leaf of each
# seed a router every time, ``L4.router`` or ``mtp.router``: 0.310 /
# 0.329 / 0.310 / 0.332 / 0.321 / 0.344 / 0.322 / 0.299 (seeds
# 2147484001, 3100000007, 2500000001, 2200000033, 2850000037,
# 3000000019, 2450000043, 3333333337); by group: routers 0.21-0.34,
# the routed experts' matrices 0.16-0.24, every other leaf of the
# layers 0.045-0.11, the module's leaves outside its expert layer
# 0.02-0.10, ``embed``, ``head`` and ``final_norm`` 0.02-0.09. The
# float8 control
# (compare.py --control float8_e4m3fn: the reference with every matmul
# operand rounded to 8 bits, the router's among them), seeds 2147484001
# and 2350000027: its worst leaf 0.880 and 0.884 (``L4.router``),
# routers 0.73-0.88, routed experts 0.58-0.71, the other leaves
# 0.09-0.44.
#
# One number has to hold every leaf (compare.py's interface), and the
# deep routers set it: between the sound runs' largest leaf, 0.344, and
# the control's smallest worst, 0.880, at their geometric middle: 1.60
# times the first, and the control's worst leaf is 1.60 times it; the
# control is refused by 15 of its 89 leaves (the five routers and the
# routed experts' ten matrices). It is ``lfm2_moe_reference``'s number,
# whose experts and router these are. What it cannot see: a fault that
# moves only a leaf outside the expert layers by less than 0.45, which
# a per-leaf limit would catch at 0.15 (PERF.md section 7, "one
# tolerance for every leaf").
GRAD_REL_L2_TOL = 0.55
# The loss: the program's LM part comes out in bf16 (the untied head's
# logits are in the module's dtype), so it is held to one bf16 spacing
# at the bottom of a binade, 2^-7 = 0.0078, as the other references
# hold theirs: seven times the sound runs' largest (0.0001-0.0011 over
# the eight seeds). The control does not move it (0.00005-0.00015): it
# is there for a loss over other positions or with another
# normalisation, not for the precision.
LOSS_REL_TOL = 2.0**-7

# a block's scores are (HEAD_GROUP, QUERY_BLOCK, L) float32, alive
# twice in the backward pass: 17 MB each at 4 heads and 8,192 keys
QUERY_BLOCK = 128
# heads whose q, k, v and result (L, HEAD_GROUP, 256) float32 are alive
# at a time: 34 MB each at 8,192 positions, where all 20 heads' were
# 168 MB each and a dozen of them alive in a layer's backward pass
HEAD_GROUP = 4
# positions whose logits (LOSS_ROWS, V) float32 are alive at a time
LOSS_ROWS = 2048
# positions whose hidden rows of a feed-forward half are alive at a
# time: (FF_ROWS, 10,240) float32 is 42 MB, a few of them in a backward
# pass, where a sequence's 8,192 rows whole were 335 MB each
FF_ROWS = 1024

_MIXER = {
    "wqa": ("q_down", "kernel"), "q_norm": ("q_norm", "scale"),
    "wqb": ("query", "kernel"), "wkva": ("kv_down", "kernel"),
    "latent_norm": ("kv_norm", "scale"), "wkvb": ("kv_up", "kernel"),
    "wo": ("out", "kernel"),
}  # fmt: skip


def _layer_from_program(params, prefix, dense):
    layer = {
        "operator_norm": params[prefix + "operator_norm"]["scale"],
        "ffn_norm": params[prefix + "ffn_norm"]["scale"],
    }
    for name, (module, leaf) in _MIXER.items():
        layer[name] = params[prefix + "mla"][module][leaf]
    if dense:
        mlp = params[prefix + "mlp"]
        layer.update({w: mlp[w]["kernel"] for w in ("w1", "w3", "w2")})
        return layer
    moe = params[prefix + "moe"]
    layer.update(
        router=moe["router"],
        expert_w13=moe["experts_w13"],
        expert_w2=moe["experts_w2"],
        shared_w13=moe["shared_w13"],
        shared_w2=moe["shared_w2"],
    )
    return layer


def from_program(params, model_params):
    """The program's flax parameter tree -> the reference's: one flat
    dict of float32 arrays named ``L<i>.<leaf>``, the prediction
    module's ``mtp.<leaf>``. Works on parameters and on gradients alike
    (they share the tree). No leaf is cut or copied: the ``W_1 | W_3``
    that the program keeps side by side, of the held experts and of the
    shared one, stay one leaf (``expert_w13``, ``shared_w13``) and
    ``swiglu`` reads the two halves where it uses them (apart they
    would be a second copy of two thirds of the experts, 1.2 GB at the
    published sizes, and as much again of the gradients, in a
    comparison that has no room to spare)."""
    out = {
        "embed": params["embed"]["embedding"],
        "head": params["head"]["kernel"],
        "final_norm": params["final_norm"]["scale"],
        "mtp.hidden_norm": params["mtp_0_hidden_norm"]["scale"],
        "mtp.embed_norm": params["mtp_0_embed_norm"]["scale"],
        "mtp.proj": params["mtp_0_proj"]["kernel"],
        "mtp.final_norm": params["mtp_0_final_norm"]["scale"],
    }
    layers = [
        ("L%d." % i, "layer_%d_" % i, i < model_params["num_dense_layers"])
        for i in range(len(model_params["layer_pattern"]))
    ] + [("mtp.", "mtp_0_", False)]
    for ours, theirs, dense in layers:
        for name, value in _layer_from_program(params, theirs, dense).items():
            out[ours + name] = value
    return {k: jnp.asarray(v, jnp.float32) for k, v in out.items()}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotary(x, theta):
    """x: (L, H, D). Rotates the two halves of D by position."""
    length, half = x.shape[0], x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _product(operand):
    """Every matrix product goes through here: ``operand`` is applied
    to both of its operands."""

    def product(subscripts, a, b):
        return jnp.einsum(subscripts, operand(a), operand(b))

    return product


def _attend(q, k, v, product):
    """Causal softmax attention of some heads of one sequence: q, k
    (L, heads, nope + rope), v (L, heads, v) -> (L, heads, v), block
    of queries by block, each against every key. ``L`` need not be
    whole blocks: the last block's spare rows are computed and
    dropped."""
    length = q.shape[0]
    block = min(QUERY_BLOCK, length)
    spare = -length % block
    q = jnp.pad(q, ((0, spare), (0, 0), (0, 0)))
    keys = jnp.arange(length)

    def one_block(first):
        rows = jax.lax.dynamic_slice_in_dim(q, first, block, axis=0)
        scores = product("qhk,mhk->hqm", rows, k) * (q.shape[-1] ** -0.5)
        seen = first + jnp.arange(block)[:, None] >= keys[None, :]
        scores = jnp.where(seen, scores, -jnp.inf)
        return product("hqm,mhk->qhk", jax.nn.softmax(scores, axis=-1), v)

    # rematerialised block by block: one block's scores alive at a time
    blocks = jax.lax.map(
        jax.checkpoint(one_block), jnp.arange(0, length + spare, block)
    )  # (blocks, block, heads, Dv)
    return blocks.reshape((-1,) + blocks.shape[2:])[:length]


def latent_attention(h, w, sizes, product):
    """Mixer(h) of one sequence: (L, d) -> (L, d). The two latents and
    the one rotated key head are made once; the heads come up out of
    them HEAD_GROUP at a time, each group rematerialised and its share
    of ``W_o``'s product added to the others' (a head's result depends
    on no other head's)."""
    rank, nope = sizes["mla_kv_rank"], sizes["mla_nope_dim"]
    eps, theta = sizes["norm_eps"], sizes["rope_theta"]
    c_q = _rms(product("ld,dr->lr", h, w["wqa"]), w["q_norm"], eps)
    down = product("ld,dc->lc", h, w["wkva"])
    latent = _rms(down[:, :rank], w["latent_norm"], eps)
    k_rope = _rotary(down[:, None, rank:], theta)  # one head

    @jax.checkpoint
    def some_heads(c_q, latent, k_rope, wqb, wkvb, wo):
        q = product("lr,rhk->lhk", c_q, wqb)
        up = product("lc,chk->lhk", latent, wkvb)
        k_nope, v = up[..., :nope], up[..., nope:]
        q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], theta)], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:2] + k_rope.shape[2:])],
            axis=-1,
        )
        return product("qhk,hkd->qd", _attend(q, k, v, product), wo)

    heads = w["wqb"].shape[1]
    group = min(HEAD_GROUP, heads)
    if heads % group:
        raise ValueError("%d heads are not in groups of %d" % (heads, group))
    # the head axis of each weight, in front and in groups
    by_group = lambda weight, axis: jnp.moveaxis(
        weight.reshape(
            weight.shape[:axis] + (heads // group, group) + weight.shape[axis + 1 :]
        ),
        axis,
        0,
    )

    def add(y, weights):
        return y + some_heads(c_q, latent, k_rope, *weights), None

    y, _ = jax.lax.scan(
        add,
        jnp.zeros_like(h),
        (by_group(w["wqb"], 1), by_group(w["wkvb"], 1), by_group(w["wo"], 0)),
    )
    return y


def swiglu(u, w1, w3, w2, product):
    """``W_2 (silu(u W_1) * (u W_3))``, FF_ROWS positions at a time,
    each block rematerialised: one block's hidden rows alive at a
    time."""

    @jax.checkpoint
    def rows(u):
        gate = jax.nn.silu(product("ld,df->lf", u, w1))
        return product("lf,fd->ld", gate * product("ld,df->lf", u, w3), w2)

    length = u.shape[0]
    block = min(FF_ROWS, length)
    spare = -length % block
    blocks = jnp.pad(u, ((0, spare), (0, 0))).reshape(-1, block, u.shape[-1])
    return jax.lax.map(rows, blocks).reshape(-1, u.shape[-1])[:length]


def _halves(w13):
    """``[W_1 | W_3]`` side by side -> ``(W_1, W_3)``."""
    width = w13.shape[-1] // 2
    return w13[..., :width], w13[..., width:]


def route(u, router, sizes, product):
    """(L, E) gates: ``scaling * s_e / sum over the selected of s``
    where expert e is among the ``num_experts_per_tok`` largest scores
    (``lax.top_k``'s rule for ties), else 0."""
    scores = jax.nn.sigmoid(product("ld,de->le", u, router))
    _, selected = jax.lax.top_k(scores, sizes["num_experts_per_tok"])
    chosen = jnp.sum(
        jax.nn.one_hot(selected, scores.shape[-1], dtype=scores.dtype), axis=-2
    )
    picked = chosen * scores
    return (
        sizes["routed_scaling_factor"]
        * picked
        / jnp.sum(picked, axis=-1, keepdims=True)
    )


def expert_share(u, gates, w13, w2, first_expert_held, product):
    """The part of the routed experts' result that experts
    ``first_expert_held ..`` (the leading dim of ``w13``) give: one
    held expert after another, each over every token."""
    held = w13.shape[0]
    gates = gates[:, first_expert_held : first_expert_held + held]

    def add(y, expert):
        gate, w13, w2 = expert
        return y + gate[:, None] * swiglu(u, *_halves(w13), w2, product), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(u), (gates.T, w13, w2))
    return y


def layer(x, w, sizes, product):
    """One sequence through one layer, (L, d) -> (L, d); the weights
    say which feed-forward half it has."""
    eps = sizes["norm_eps"]
    x = x + latent_attention(_rms(x, w["operator_norm"], eps), w, sizes, product)
    u = _rms(x, w["ffn_norm"], eps)
    if "router" not in w:
        return x + swiglu(u, w["w1"], w["w3"], w["w2"], product)
    return (
        x
        + swiglu(u, *_halves(w["shared_w13"]), w["shared_w2"], product)
        + expert_share(
            u, route(u, w["router"], sizes, product), w["expert_w13"],
            w["expert_w2"], sizes["first_expert_held"], product,
        )
    )  # fmt: skip


def _under(weights, prefix):
    return {
        name[len(prefix) :]: value
        for name, value in weights.items()
        if name.startswith(prefix)
    }


def _each_sequence(x, w, sizes, product):
    """``layer`` over a batch (B, L, d). Rematerialised: one layer's
    activations alive at a time, and of them one sequence's."""
    return jax.lax.map(jax.checkpoint(lambda row: layer(row, w, sizes, product)), x)


def trunk(weights, tokens, model_params, product):
    """The last layer's output, in front of the final norm: (B, L, d)."""
    x = weights["embed"][tokens]
    for i in range(len(model_params["layer_pattern"])):
        x = _each_sequence(x, _under(weights, "L%d." % i), model_params, product)
    return x


def module_hidden(weights, x, tokens, model_params, product):
    """The prediction module's output in front of its final norm, at
    positions 0..L-2: (B, L - 1, d)."""
    eps = model_params["norm_eps"]
    joined = jnp.concatenate(
        [
            _rms(x[:, :-1], weights["mtp.hidden_norm"], eps),
            _rms(weights["embed"][tokens[:, 1:]], weights["mtp.embed_norm"], eps),
        ],
        axis=-1,
    )
    u = product("blc,cd->bld", joined, weights["mtp.proj"])
    return _each_sequence(u, _under(weights, "mtp."), model_params, product)


def forward(weights, tokens, model_params, operand=None):
    """The trunk's logits (B, L, V) and the module's (B, L - 1, V),
    float32: for the tests' toy sizes (the losses below never hold them
    whole)."""
    product = _product(operand or (lambda x: x))
    eps = model_params["norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = trunk(weights, tokens, model_params, product)
        y = module_hidden(weights, x, tokens, model_params, product)
        return tuple(
            product("bld,dv->blv", _rms(z, weights[norm], eps), weights["head"])
            for z, norm in ((x, "final_norm"), (y, "mtp.final_norm"))
        )


def _mean_cross_entropy(x, targets, head, product):
    """The mean over the rows of ``x`` (rows, d) of the cross entropy
    of ``x head`` against ``targets``, the head and the softmax in
    chunks of LOSS_ROWS rows."""
    rows = x.shape[0]
    chunk = min(LOSS_ROWS, rows)
    # the last chunk is padded with rows that weigh nothing
    pad = -rows % chunk
    x = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, chunk, x.shape[-1])
    targets = jnp.pad(targets, (0, pad)).reshape(-1, chunk)
    counted = (jnp.arange(rows + pad) < rows).reshape(-1, chunk)

    @jax.checkpoint
    def one_chunk(x, targets, counted, head):
        logits = product("ld,dv->lv", x, head)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(counted, logz - picked, 0.0))

    def add(total, chunk_of):
        return total + one_chunk(*chunk_of, head), None

    total, _ = jax.lax.scan(add, jnp.zeros(()), (x, targets, counted))
    return total / rows


def losses(weights, tokens, model_params, operand=None):
    """``(L_lm, L_mtp)``: the next-token cross entropy over positions
    0..L-2, and the module's, of the token after the next, over
    positions 0..L-3; each a mean over the batch's predicted
    positions."""
    product = _product(operand or (lambda x: x))
    eps = model_params["norm_eps"]
    width = weights["embed"].shape[-1]
    with jax.default_matmul_precision("highest"):
        x = trunk(weights, tokens, model_params, product)
        y = module_hidden(weights, x, tokens, model_params, product)
        lm = _mean_cross_entropy(
            _rms(x[:, :-1], weights["final_norm"], eps).reshape(-1, width),
            tokens[:, 1:].reshape(-1),
            weights["head"],
            product,
        )
        mtp = _mean_cross_entropy(
            _rms(y[:, :-1], weights["mtp.final_norm"], eps).reshape(-1, width),
            tokens[:, 2:].reshape(-1),
            weights["head"],
            product,
        )
    return lm, mtp


def loss(weights, tokens, model_params, operand=None):
    """``L_lm + mtp_loss_weight * L_mtp``, what a step descends."""
    lm, mtp = losses(weights, tokens, model_params, operand)
    return lm + model_params["mtp_loss_weight"] * mtp


def loss_and_grads(weights, tokens, model_params, operand=None):
    """The loss and, leaf by leaf, its gradients with respect to
    ``weights``.

    The whole of it is one branch of a conditional that is always
    taken (a token id is never negative, which the compiler cannot
    know): a unit of the compiled comparison, run from its first
    operation to its last with nothing of the program's own forward
    and backward pass between them. Written out flat, the compiler is
    free to interleave the two, which share nothing but the weights,
    and to hold both sides' activations at once. It changes when
    values exist and not which."""
    return jax.lax.cond(
        tokens[0, 0] >= 0,
        jax.value_and_grad(
            lambda weights: loss(weights, tokens, model_params, operand)
        ),
        lambda weights: (
            jnp.zeros(()),
            jax.tree_util.tree_map(jnp.zeros_like, weights),
        ),
        weights,
    )
