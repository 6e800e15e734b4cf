"""SmallThinker-21BA3B-Instruct (``smallthinker``), one chip's share,
written out plainly: forward, loss, gradients.

float32 throughout, matrix products at ``highest`` precision, no flax
module, no kernel, no sorting or grouping of rows, no tile and no band:
attention is a softmax over every key with the keys outside the window
masked. This is what ``correct`` compares the program's
``hybrid_moe_lm.custom_model`` + ``loss`` against, on the same weights
and the same batch. It imports nothing of the program and nothing of the
other references.

The equations, from the published configuration (``config.json`` of
PowerInfer/SmallThinker-21BA3B-Instruct) and, for what no key states,
the catalog's description of the family (``described_as``: "NoPE
global", "sparse ReGLU", "router placed before attention"). With
``rms(x; g) = x / sqrt(mean(x^2) + eps) * g`` (eps 1e-6, weight only; no
bias anywhere, no norm over q or k), layer i of kind ``layer_pattern[i]``:

    x_0 = E[tokens]
    h = rms(x; g_op)
    r = h W_r  (E logits);  S = the k largest;  g_e = softmax over S of r
    x' = x + A(h);   u = rms(x'; g_ff)
    x'' = x' + sum over e in S that this chip holds of
               g_e W_2e (relu(u W_1e) * (u W_3e))
    logits = rms(x_last; g_f) W_head        (untied, over the slice)
    loss = mean over positions 0..L-2 of CE(logits_t, tokens_{t+1})

The router reads the ATTENTION's normed input ``h`` and not the expert
layer's ``u``; the expert is a ReGLU. The softmax over the k selected
logits is the published order (``moe_primary_router_apply_softmax``,
``norm_topk_prob``): select, then normalise. (The program takes the
softmax over all E first and renormalises over the selected: the same
numbers, ``exp(r_e) / sum_{S} exp(r)`` either way.) g is over all the
selected, held or not; what the absent experts would have added is left
out, and x'' is what goes on: in the program and here alike.

A, H query heads over H_kv key/value heads of size hd, KV head j
serving query heads j*H/H_kv ..:
    q = h W_q, k = h W_k, v = h W_v
    ``a`` (global, NO positions):  o_t = sum_{s <= t} softmax(q_t k_s / sqrt(hd)) v_s
    ``w`` (window W, rotary of base theta on q and k, halves rotated):
                                   o_t = sum_{t-W < s <= t} softmax(q_t k_s / sqrt(hd)) v_s
    A = concat(heads) W_o

How it is computed, which changes when values exist and not which: one
sequence after the other (``lax.map`` over the batch), each layer
rematerialised, attention in blocks of QUERY_BLOCK queries against
every key (its (heads, block, L) scores whole, masked by position), the
held experts one after the other each over every token (a token that
did not select it weighs zero: no dispatch), and the head and the loss
in chunks of LOSS_ROWS positions. At 2 x 16,384 tokens beside the
program's own forward and backward this is what fits the chip (PERF.md
section 7).

Departures from the published model, each in the configuration file's
``assumed``: the router's input, the ReGLU and the absence of secondary
experts are the description's, not a config key's; the rotary
convention (which halves pair) is the zoo's; LM loss only.

``model_params`` here: ``layer_pattern``, ``attention_window``,
``num_heads``, ``num_kv_heads``, ``head_dim``, ``expert_dim``,
``num_experts``, ``experts_held``, ``first_expert_held``,
``num_experts_per_tok``, ``rope_theta``, ``norm_eps``.
"""

import jax
import jax.numpy as jnp

# The tolerances, and why; every reading is in PERF.md section 2 (PR 39).
#
# The configuration computes in bfloat16 with f32 parameters, f32
# accumulation and an f32 router; the reference is f32 at highest
# precision. Each gradient leaf is compared by its relative L2 error
# over the whole leaf. Two things set the error of a sound bf16 run:
# rounding, as in the dense LM, and the routers' choice of experts, as
# in ``lfm2_moe_reference.py``: the router's input carries the bf16
# roundings of everything before it, the 6th and 7th of 64 logits lie
# close, and a token that swaps one expert changes a gate and an
# expert's rows, here and in every later layer. Nothing here selects
# keys: a window is positions, the same on both sides.
#
# Measured on the v5e at the cell's sizes (2 x 16,384 tokens, published
# widths; my chip runs, PR 39). Sound program, 11 seeds (7 runs'
# comparison children and 4 more through compare.py alone), the worst
# leaf of each: 0.118 / 0.119 / 0.124 / 0.124 / 0.149 / 0.159 / 0.165 /
# 0.167 / 0.171 / 0.219 / 0.241, a router in 7 of them and an expert's
# W_1 in 4, in layer 2 or 3 in all but one; by group over the seeds:
# routers 0.06-0.22, the experts' matrices and the norm in front of
# them 0.04-0.24, q/k projections 0.03-0.09, v/o projections and the
# norm in front of attention 0.01-0.04, embedding 0.03-0.04, head
# 0.012-0.015, the final norm 0.006-0.007. The float8 control
# (compare.py --control float8_e4m3fn: the reference with every matmul
# operand rounded to 8 bits, the router's among them), 3 seeds: its
# worst leaf 0.428 / 0.447 / 0.545 (a router twice, an expert's W_1
# once), routers 0.34-0.54, experts and their norm 0.19-0.45, q/k
# 0.18-0.36, v/o and their norm 0.11-0.18, embedding 0.15-0.17, head
# 0.11-0.12, the final norm 0.07-0.08: every leaf 2 to 12 times the
# sound program's largest reading of that leaf, the dense leaves by the
# most.
#
# One number has to hold every leaf (compare.py's interface), and the
# deep routers set it: between the sound runs' largest leaf, 0.241, and
# the control's smallest worst leaf, 0.428, at their geometric middle
# and a little over (fresh seeds read higher, and a sound run refused
# costs more than a control let through): 1.37 times the first, the
# second 1.30 times it. The control is refused by 11, 12 and 11 of its
# 43 leaves. What it cannot see: a fault that moves only a dense leaf
# (head, embedding, v/o, the norms) by less than 0.3, which a per-leaf
# limit would catch at 0.05 (PERF.md section 7).
GRAD_REL_L2_TOL = 0.33
# The loss: the program returns it in bf16 (the untied head's logits
# come out in the module's dtype), so it is held to one bf16 spacing at
# the bottom of a binade, 2^-7 = 0.0078, as the other references hold
# theirs: 2.6 times the sound runs' largest (0.0016-0.0030 over the 11
# seeds). The control does not move it (0.00004-0.00017): it is there
# for a part of the batch or of the positions left out of the loss, not
# for the precision.
LOSS_REL_TOL = 2.0**-7

# a block's scores are (heads, QUERY_BLOCK, L) float32, alive twice in
# the backward pass: 235 MB each at 28 heads and 16,384 keys
QUERY_BLOCK = 128
# positions whose logits (LOSS_ROWS, V) float32 are alive at a time
LOSS_ROWS = 2048
GLOBAL, WINDOW = "a", "w"


def from_program(params, model_params):
    """The program's flax parameter tree -> the reference's: one flat
    dict of float32 arrays named ``L<i>.<leaf>``. Works on parameters
    and on gradients alike (they share the tree). The experts' ``W_1 |
    W_3``, which the program keeps side by side, come apart."""
    width = model_params["expert_dim"]
    out = {
        "embed": params["embed"]["embedding"],
        "head": params["head"]["kernel"],
        "final_norm": params["final_norm"]["scale"],
    }
    for i in range(len(model_params["layer_pattern"])):
        attn = params["layer_%d_attention" % i]
        moe = params["layer_%d_moe" % i]
        layer = {
            "operator_norm": params["layer_%d_operator_norm" % i]["scale"],
            "wq": attn["query"]["kernel"],
            "wk": attn["key"]["kernel"],
            "wv": attn["value"]["kernel"],
            "wo": attn["out"]["kernel"],
            "ffn_norm": params["layer_%d_ffn_norm" % i]["scale"],
            "router": moe["router"],
            "expert_w1": moe["experts_w13"][..., :width],
            "expert_w3": moe["experts_w13"][..., width:],
            "expert_w2": moe["experts_w2"],
        }
        for name, value in layer.items():
            out["L%d.%s" % (i, name)] = value
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


def attention(h, w, kind, sizes, product):
    """A(h) of one sequence: (L, d) -> (L, d), block of queries by
    block, each against every key."""
    length = h.shape[0]
    q = product("ld,dhk->lhk", h, w["wq"])
    k = product("ld,dhk->lhk", h, w["wk"])
    v = product("ld,dhk->lhk", h, w["wv"])
    if kind == WINDOW:
        q, k = _rotary(q, sizes["rope_theta"]), _rotary(k, sizes["rope_theta"])
        reach = sizes["attention_window"]
    else:
        reach = length  # every earlier key
    group = q.shape[1] // k.shape[1]
    # query head i reads KV head i // group
    q = q.reshape(length, k.shape[1], group, q.shape[-1])
    block = min(QUERY_BLOCK, length)
    if length % block:
        raise ValueError("length %d is not in blocks of %d" % (length, block))
    keys = jnp.arange(length)

    def one_block(first):
        rows = jax.lax.dynamic_slice_in_dim(q, first, block, axis=0)
        scores = product("qjgk,mjk->jgqm", rows, k) * (q.shape[-1] ** -0.5)
        behind = first + jnp.arange(block)[:, None] - keys[None, :]
        scores = jnp.where((behind >= 0) & (behind < reach), scores, -jnp.inf)
        out = product("jgqm,mjk->qjgk", jax.nn.softmax(scores, axis=-1), v)
        return out.reshape(block, -1, out.shape[-1])

    # rematerialised block by block: one block's scores alive at a time
    blocks = jax.lax.map(
        jax.checkpoint(one_block), jnp.arange(0, length, block)
    )
    attn = blocks.reshape((length,) + blocks.shape[2:])
    return product("qhk,hkd->qd", attn, w["wo"])


def reglu(u, w1, w3, w2, product):
    gate = jax.nn.relu(product("...d,df->...f", u, w1))
    return product("...f,fd->...d", gate * product("...d,df->...f", u, w3), w2)


def route(h, router, sizes, product):
    """(..., E) gates: the softmax over the selected logits where
    expert e is selected, else 0. Ties to the lower index
    (``lax.top_k``'s rule)."""
    logits = product("...d,de->...e", h, router)
    picked, selected = jax.lax.top_k(logits, sizes["num_experts_per_tok"])
    gates = jax.nn.softmax(picked, axis=-1)
    return jnp.sum(
        jax.nn.one_hot(selected, logits.shape[-1], dtype=gates.dtype)
        * gates[..., None],
        axis=-2,
    )


def expert_share(u, gates, w1, w3, w2, first_expert_held, product):
    """The part of the expert layer's result that experts
    ``first_expert_held ..`` (the leading dim of ``w1``) give: one held
    expert after another, each over every token."""
    held = w1.shape[0]
    gates = gates[..., first_expert_held : first_expert_held + held]

    # rematerialised expert by expert: one expert's hidden rows alive
    # at a time
    @jax.checkpoint
    def one_expert(u, gate, w1, w3, w2):
        return gate[..., None] * reglu(u, w1, w3, w2, product)

    def add(y, expert):
        return y + one_expert(u, *expert), None

    y, _ = jax.lax.scan(
        add, jnp.zeros_like(u), (jnp.moveaxis(gates, -1, 0), w1, w3, w2)
    )
    return y


def _layer(x, w, kind, sizes, product):
    eps = sizes["norm_eps"]
    h = _rms(x, w["operator_norm"], eps)
    gates = route(h, w["router"], sizes, product)  # before the attention
    x = x + attention(h, w, kind, sizes, product)
    return x + expert_share(
        _rms(x, w["ffn_norm"], eps), gates, w["expert_w1"], w["expert_w3"],
        w["expert_w2"], sizes["first_expert_held"], product,
    )  # fmt: skip


def hidden(weights, tokens, model_params, product):
    """The last layer's output of ONE sequence, normed: (L, d)."""
    x = weights["embed"][tokens]
    for i, kind in enumerate(model_params["layer_pattern"]):
        if kind not in (GLOBAL, WINDOW):
            raise ValueError("no layer %r in this model" % kind)
        prefix = "L%d." % i
        w = {
            name[len(prefix) :]: value
            for name, value in weights.items()
            if name.startswith(prefix)
        }
        # rematerialised: one layer's activations alive at a time
        x = jax.checkpoint(
            lambda x, w, kind=kind: _layer(x, w, kind, model_params, product)
        )(x, w)
    return _rms(x, weights["final_norm"], model_params["norm_eps"])


def forward(weights, tokens, model_params, operand=None):
    """Logits (B, L, V), float32: for the tests' toy sizes (the loss
    below never holds them whole)."""
    product = _product(operand or (lambda x: x))
    with jax.default_matmul_precision("highest"):
        return jnp.stack(
            [
                product(
                    "ld,dv->lv",
                    hidden(weights, row, model_params, product),
                    weights["head"],
                )
                for row in tokens
            ]
        )


def _sequence_nll(weights, tokens, model_params, product):
    """The sum over positions 0..L-2 of one sequence's next-token cross
    entropy, the head and the softmax in chunks of LOSS_ROWS rows."""
    x = hidden(weights, tokens, model_params, product)[:-1]
    targets = tokens[1:]
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
        return total + one_chunk(*chunk_of, weights["head"]), None

    total, _ = jax.lax.scan(add, jnp.zeros(()), (x, targets, counted))
    return total


def loss(weights, tokens, model_params, operand=None):
    """Next-token cross entropy, mean over the B (L-1) predicted
    positions, one sequence after the other."""
    product = _product(operand or (lambda x: x))
    with jax.default_matmul_precision("highest"):
        # rematerialised sequence by sequence: the backward pass of one
        # starts from its tokens, and keeps nothing of the other
        totals = jax.lax.map(
            jax.checkpoint(
                lambda row: _sequence_nll(weights, row, model_params, product)
            ),
            tokens,
        )
    return jnp.sum(totals) / (tokens.shape[0] * (tokens.shape[1] - 1))


def loss_and_grads(weights, tokens, model_params, operand=None):
    """The loss and, leaf by leaf, its gradients with respect to
    ``weights``."""
    return jax.value_and_grad(
        lambda weights: loss(weights, tokens, model_params, operand)
    )(weights)
