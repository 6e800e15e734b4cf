"""Keye-VL-2.0-30B-A3B's language model (``KeyeVL2``), one chip's share,
written out plainly: forward, loss, gradients.

float32 throughout, matrix products at ``highest`` precision, no flax
module, no kernel, no sorting or grouping of rows, no threshold search.
This is what ``correct`` compares the program's
``hybrid_moe_lm.custom_model`` + ``loss`` against, on the same weights
and the same batch. It imports nothing of the program and nothing of the
other references.

The equations, from the published configuration (``config.json`` of
Kwai-Keye/Keye-VL-2.0-30B-A3B) and, for the indexer, equation 1 of the
method its description names (DeepSeek Sparse Attention). With
``rms(x; g) = x / sqrt(mean(x^2) + eps) * g`` (eps 1e-6, weight only; no
bias anywhere), every layer alike:

    x_0 = E[tokens]
    h = rms(x; g_op);   x <- x + A(h);   x <- x + M(rms(x; g_ff))
    logits = rms(x_last; g_f) W_head        (untied, over the slice)
    loss = mean over positions 0..L-2 of CE(logits_t, tokens_{t+1})

A, attention over selected keys, H query heads over H_kv key/value heads:
    q = h W_q, k = h W_k, v = h W_v;  q, k <- rms over each head (a
    learned weight of head size each);  rotary of base theta on q, k
    (halves rotated);  KV head j serves query heads j*H/H_kv ..
    o_t = sum_{s in S_t} softmax_{s in S_t}(q_t k_s / sqrt(hd)) v_s
    A = concat(heads) W_o
the indexer, on hb = stop_gradient(h), J heads of size D_I, ONE key head:
    qI = hb W_qI,  kI = hb W_kI,  w = hb W_w
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])
    S_t = the s <= t with the K largest I[t, s]: every s <= t while
    t < K; ties to the lower index (``lax.top_k``'s rule)
M, the expert layer, E experts routed over, k a token:
    p = softmax(h W_r);  sel = top_k(p)  (no bias; ties to the lower
    index)
    g_e = p_e / (sum of p over sel)
    THE SHARE: y = sum over e in sel that this chip holds of
        g_e W_2e (silu(h W_1e) * (h W_3e))
    g is normalised over all the selected, held or not. What the
    absent experts would have added is left out, and y is what goes on
    to the next layer: in the program and here alike.

Each held expert here runs every token, and a token that did not select
it weighs zero: no dispatch. Attention runs in blocks of QUERY_BLOCK
queries, each against every key, its (heads, block, L) scores whole and
masked by membership in ``lax.top_k``'s indices: nothing to share with
the program's tiles, threshold or kernels. The blocking, and the loops
over blocks and experts, change when values are computed, not which.

Under this loss the indexer's three matrices get no gradient (they
reach the loss only through a discrete choice). ``from_program`` names
every leaf; ``loss_and_grads`` returns the gradients of the trained
leaves only, so nothing is compared against a gradient of zero.

Departures from the published model, each in the configuration file's
``assumed``: the indexer has no rotation, norm or scale inside it;
positions are text positions (``mrope_section`` is then 1-D rotary);
the rotary convention (which halves pair) is the zoo's; LM loss only.

``model_params`` here: ``layer_pattern`` (its length: the layers),
``num_heads``, ``num_kv_heads``, ``head_dim``, ``expert_dim``,
``num_experts``, ``experts_held``, ``first_expert_held``,
``num_experts_per_tok``, ``select_topk``, ``indexer_heads``,
``indexer_dim``, ``rope_theta``, ``norm_eps``.
"""

import jax
import jax.numpy as jnp

# The tolerances, and why; every reading is in PERF.md section 2 (PR 32).
#
# The configuration computes in bfloat16 with f32 parameters, f32
# accumulation, an f32 router and an f32 indexer; the reference is f32
# at highest precision. Each gradient leaf is compared by its relative
# L2 error over the whole leaf. Three things set the error of a sound
# bf16 run. Rounding, as in the dense LM. The routers' choice of
# experts, as in ``lfm2_moe_reference.py``: the router's input carries
# the bf16 roundings of everything before it, and the 8th and 9th of
# 128 probabilities lie close. And, new here, the indexers' choice of
# keys: the indexer is f32 on both sides but its input is the same
# rounded stream, the 2,048th and 2,049th of up to 8,192 scores lie
# closer still, so every query swaps some of its keys at the margin;
# a swapped key changes that query's attention in all 32 heads and
# every later layer's input.
#
# Measured on the v5e at the cell's sizes (2 x 8,192 tokens, published
# widths; my chip runs, PR 32). Sound program, 12 seeds (9 runs'
# comparison children and 3 more through compare.py alone), the worst
# leaf of each: 0.193 / 0.198 / 0.205 / 0.210 / 0.219 / 0.222 / 0.229 /
# 0.251 / 0.269 / 0.293 / 0.375 / 0.383, a router in 8 of them (the
# three largest the deepest, L3), else a q/k projection or its norm in
# layer 1 or an expert matrix; by group over the seeds: routers
# 0.06-0.38, the attention's q/k projections and their norms
# 0.06-0.23, the experts' matrices and the norm in front of them
# 0.07-0.29, v/o projections and the norm in front of attention
# 0.02-0.07, embedding 0.05-0.07, head 0.03-0.04, the final norm
# 0.011-0.014. The float8 control
# (compare.py --control float8_e4m3fn: the reference with every matmul
# operand rounded to 8 bits, the indexer's and the router's among
# them), 3 seeds: its worst leaf 2.13 / 2.17 / 3.17 (a router each
# time), routers 0.64-3.17, q/k 0.63-1.66, experts 0.69-1.55, v/o
# 0.72-1.34, embedding 0.98-1.06, head 0.11-0.12, the final norm
# 0.07-0.08: every leaf but the last two is 2 to 10 times the sound
# program's largest reading of that leaf.
#
# One number has to hold every leaf (compare.py's interface), so it
# sits between the sound runs' largest leaf, 0.383, and the control's
# smallest worst leaf, 2.13, with room on both sides: 1.96 times the
# first (fresh seeds read higher; the two largest of twelve are 0.375
# and 0.383), the second 2.8 times it. The control is refused by 41,
# 42 and 46 of its 51 leaves. What it cannot see: a fault that moves
# only the head or the final norm, or any other single leaf by less
# than 0.4 (a per-leaf limit would hold the v/o projections at about
# 0.15; PERF.md section 7).
GRAD_REL_L2_TOL = 0.75
# The loss: the program returns it in bf16 (the head's logits come out
# in the module's dtype), so it is held to one bf16 spacing at the
# bottom of a binade, 2^-7 = 0.0078, as the other two references hold
# theirs: 2.6 times the sound runs' largest (0.0014-0.0030 over the 12
# seeds). The control does not move it (0.000001-0.00005): it is there
# for a part of the batch or of the positions left out of the loss, not
# for the precision.
LOSS_REL_TOL = 2.0**-7

# a block's scores are (B, heads, QUERY_BLOCK, L) float32, alive twice
# in the backward pass: 256 MiB each at 2 x 8,192 tokens. At 512 they
# were a GiB each, and the comparison no longer fitted the chip beside
# a program side that keeps its expert layers' products (PR 32)
QUERY_BLOCK = 128
INDEXER_LEAVES = ("indexer_wq", "indexer_wk", "indexer_ww")


def from_program(params, model_params):
    """The program's flax parameter tree -> the reference's: one flat
    dict of float32 arrays named ``L<i>.<leaf>``, EVERY leaf, the
    indexers' among them. Works on parameters and on gradients alike
    (they share the tree). The experts' ``W_1 | W_3``, which the
    program keeps side by side, come apart."""
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
            "q_norm": attn["q_norm"]["scale"],
            "k_norm": attn["k_norm"]["scale"],
            "wo": attn["out"]["kernel"],
            "indexer_wq": attn["indexer"]["query"]["kernel"],
            "indexer_wk": attn["indexer"]["key"]["kernel"],
            "indexer_ww": attn["indexer"]["weights"]["kernel"],
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
    """x: (B, L, H, D). Rotates the two halves of D by position."""
    length, half = x.shape[1], x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _product(operand):
    """Every matrix product goes through here: ``operand`` is applied
    to both of its operands."""

    def product(subscripts, a, b):
        return jnp.einsum(subscripts, operand(a), operand(b))

    return product


def selection(index_q, index_k, index_w, first, topk, product):
    """(B, n, L) bool: the keys queries ``first .. first + n - 1`` read.
    ``index_q`` (B, n, J, D) and ``index_w`` (B, n, J) are the block's,
    ``index_k`` (B, L, D) every position's."""
    n, length = index_q.shape[1], index_k.shape[1]
    products = product("bqjd,bsd->bqjs", index_q, index_k)
    scores = jnp.sum(index_w[..., None] * jax.nn.relu(products), axis=2)
    rows = first + jnp.arange(n)[:, None]
    causal = jnp.arange(length)[None, :] <= rows
    _, chosen = jax.lax.top_k(
        jnp.where(causal, scores, -jnp.inf), min(topk, length)
    )
    # key s is read if it is among the chosen: membership, index by index
    picked = jnp.any(chosen[..., None] == jnp.arange(length), axis=-2)
    # a query with fewer than topk keys before it was handed some of
    # the keys after it (their score is -inf): they are not read
    return picked & causal


def attention(h, w, sizes, product):
    """A(h): (B, L, d) -> (B, L, d), block of queries by block: first
    every block's selection (no gradient passes), then its attention."""
    batch, length = h.shape[:2]
    eps, theta = sizes["norm_eps"], sizes["rope_theta"]
    q = product("bld,dhk->blhk", h, w["wq"])
    k = product("bld,dhk->blhk", h, w["wk"])
    v = product("bld,dhk->blhk", h, w["wv"])
    q = _rotary(_rms(q, w["q_norm"], eps), theta)
    k = _rotary(_rms(k, w["k_norm"], eps), theta)
    group = q.shape[2] // k.shape[2]
    # query head i reads KV head i // group
    q = q.reshape(q.shape[:2] + (k.shape[2], group, q.shape[-1]))
    hb = jax.lax.stop_gradient(h)
    index_q = product("bld,djk->bljk", hb, w["indexer_wq"])
    index_k = product("bld,dk->blk", hb, w["indexer_wk"])
    index_w = product("bld,dj->blj", hb, w["indexer_ww"])
    block = min(QUERY_BLOCK, length)
    if length % block:
        raise ValueError("length %d is not in blocks of %d" % (length, block))
    firsts = jnp.arange(0, length, block)

    def rows(x, first):
        return jax.lax.dynamic_slice_in_dim(x, first, block, axis=1)

    keep = jax.lax.map(
        lambda first: selection(
            rows(index_q, first), index_k, rows(index_w, first), first,
            sizes["select_topk"], product,
        ),  # fmt: skip
        firsts,
    )  # (blocks, B, block, L)

    def one_block(args):
        first, keep = args
        scores = product("bqjgk,bmjk->bjgqm", rows(q, first), k) * (
            q.shape[-1] ** -0.5
        )
        scores = jnp.where(keep[:, None, None], scores, -jnp.inf)
        out = product("bjgqm,bmjk->bqjgk", jax.nn.softmax(scores, axis=-1), v)
        return out.reshape(batch, block, -1, out.shape[-1])

    # rematerialised block by block: one block's scores alive at a time
    blocks = jax.lax.map(jax.checkpoint(one_block), (firsts, keep))
    attn = blocks.transpose(1, 0, 2, 3, 4).reshape(
        (batch, length) + blocks.shape[3:]
    )
    return product("bqhk,hkd->bqd", attn, w["wo"])


def swiglu(h, w1, w3, w2, product):
    gate = jax.nn.silu(product("...d,df->...f", h, w1))
    return product("...f,fd->...d", gate * product("...d,df->...f", h, w3), w2)


def route(h, router, sizes, product):
    """(..., E) gates: ``g_e`` where expert e is selected, else 0."""
    probs = jax.nn.softmax(product("...d,de->...e", h, router), axis=-1)
    _, selected = jax.lax.top_k(probs, sizes["num_experts_per_tok"])
    chosen = jnp.sum(
        jax.nn.one_hot(selected, probs.shape[-1], dtype=probs.dtype), axis=-2
    )
    picked = probs * chosen
    return picked / jnp.sum(picked, axis=-1, keepdims=True)


def expert_share(h, router, w1, w3, w2, first_expert_held, sizes, product):
    """The part of the expert layer's result that experts
    ``first_expert_held ..`` (the leading dim of ``w1``) give: one held
    expert after another, each over every token."""
    held = w1.shape[0]
    gates = route(h, router, sizes, product)
    gates = gates[..., first_expert_held : first_expert_held + held]

    # rematerialised expert by expert: one expert's hidden rows alive
    # at a time
    @jax.checkpoint
    def one_expert(h, gate, w1, w3, w2):
        return gate[..., None] * swiglu(h, w1, w3, w2, product)

    def add(y, expert):
        return y + one_expert(h, *expert), None

    y, _ = jax.lax.scan(
        add, jnp.zeros_like(h), (jnp.moveaxis(gates, -1, 0), w1, w3, w2)
    )
    return y


def _layer(x, w, sizes, product):
    eps = sizes["norm_eps"]
    x = x + attention(_rms(x, w["operator_norm"], eps), w, sizes, product)
    return x + expert_share(
        _rms(x, w["ffn_norm"], eps), w["router"], w["expert_w1"],
        w["expert_w3"], w["expert_w2"], sizes["first_expert_held"],
        sizes, product,
    )  # fmt: skip


def forward(weights, tokens, model_params, operand=None):
    """Logits (B, L, V), float32."""
    product = _product(operand or (lambda x: x))
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens]
        for i in range(len(model_params["layer_pattern"])):
            prefix = "L%d." % i
            w = {
                name[len(prefix) :]: value
                for name, value in weights.items()
                if name.startswith(prefix)
            }
            # rematerialised: one layer's activations alive at a time
            x = jax.checkpoint(
                lambda x, w: _layer(x, w, model_params, product)
            )(x, w)
        x = _rms(x, weights["final_norm"], model_params["norm_eps"])
        return product("bld,dv->blv", x, weights["head"])


def loss(weights, tokens, model_params, operand=None):
    """Next-token cross entropy, mean over the L-1 predicted positions."""
    logits = forward(weights, tokens, model_params, operand)[:, :-1]
    targets = tokens[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


def loss_and_grads(weights, tokens, model_params, operand=None):
    """The loss and, leaf by leaf, its gradients with respect to the
    TRAINED leaves of ``weights``: every one but the indexers', whose
    gradient under this loss is zero by construction."""
    fixed = {k: v for k, v in weights.items() if k.endswith(INDEXER_LEAVES)}
    free = {k: v for k, v in weights.items() if k not in fixed}
    return jax.value_and_grad(
        lambda free: loss(dict(free, **fixed), tokens, model_params, operand)
    )(free)
