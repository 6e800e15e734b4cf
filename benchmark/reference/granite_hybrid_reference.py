"""Granite 4.0-H (``granitemoehybrid`` with no routed expert), one
pipeline stage's layers over a slice of the vocabulary, written out
plainly: forward, loss, gradients.

float32 throughout, matrix products at ``highest`` precision, no flax
module, no kernel, no chunked scan: the state-space layer is the
recurrence itself, a ``lax.scan`` over positions. This is what
``correct`` compares the program's ``hybrid_moe_lm.custom_model`` +
``loss`` against, on the same weights and the same batch. It imports
nothing of the program and nothing of another reference.

The equations, from the published configuration (``config.json`` of
ibm-granite/granite-4.0-h-micro). With ``rms(x; g) = x / sqrt(mean(x^2)
+ eps) * g`` (eps 1e-5, weight only), ``r = residual_multiplier`` and
one letter of ``layer_pattern`` a layer:

    x_0 = embedding_multiplier * E[tokens]
    x <- x + r * Op(rms(x; g_op));   x <- x + r * W_2 (silu(h W_1) * (h W_3)),  h = rms(x; g_ff)
    logits = rms(x_last; g_f) E^T / logits_scaling      (tied head over the slice)
    loss = mean over positions 0..L-2 of CE(logits_t, tokens_{t+1})

``m``, Mamba-2, H heads of size P, state N, G groups, K taps:
    [z | xBC | dt] = h W_in                  (H P | H P + 2 G N | H)
    xBC = silu(conv(xBC) + b_conv),  conv_t = sum_{j<K} k_j * xBC_{t-j}  (depthwise, causal)
    [X | B | C] = xBC                        (H heads x P | G x N | G x N)
    delta_t = softplus(dt_t + dt_bias);  A = -exp(A_log)   (a head each; no clamp)
    S_t = exp(delta_t A) S_{t-1} + (delta_t X_t) (x) B_t   (a head: P x N, S_{-1} = 0;
                                                            group g's B, C serve heads g H/G ..)
    y_t = S_t C_t + D X_t
    Op  = (rms(y * silu(z); w)) W_out        (one norm over all H P)
``a``, attention, H query heads over H_kv key/value heads, no bias, no
positions, no norm of q or k:
    q = h W_q, k = h W_k, v = h W_v
    causal softmax(attention_scale * q k^T) v, KV head j serving query
    heads j*H/H_kv ..;  Op = concat(heads) W_o

Departures from the published model, each in the configuration file's
``assumed``: the depthwise taps are indexed by delay (``k_j`` multiplies
``xBC_{t-j}``), the mirror image of a ``Conv1d``'s weight, which random
weights do not tell apart; ``head_dim`` is ``hidden_size /
num_attention_heads``.

What changes no arithmetic and is here so that 2 x 2,048 tokens at the
published widths fit beside three float32 trees of the parameters: each
layer is rematerialised (``jax.checkpoint``), the recurrence keeps its
state once a block of ``SCAN_BLOCK`` positions for the backward pass
and recomputes inside the block, and attention takes the sequences of
the batch one after the other.

``model_params`` here: ``layer_pattern`` (``m`` and ``a`` only, every
layer with the dense MLP), ``num_heads``, ``num_kv_heads``,
``head_dim``, ``ssm_heads``, ``ssm_head_dim``, ``ssm_state``,
``ssm_groups``, ``attention_scale``, ``embedding_multiplier``,
``residual_multiplier``, ``logits_scaling``, ``norm_eps``.
"""

import jax
import jax.numpy as jnp

# The tolerances, and why; every reading is in PERF.md section 2 (PR 34).
#
# The configuration computes in bfloat16 with f32 parameters, f32
# accumulation and f32 decays; the reference is f32 at highest
# precision. Each gradient leaf is compared by its relative L2 error
# over the whole leaf. Nothing in this model is discrete (no router, no
# selection), so a sound run's error is rounding alone, as the dense
# LM's is. It is larger than the dense LM's (0.5-1.9% a leaf) because
# a Mamba-2 mixer is a longer chain of bf16 roundings than an
# attention block (projection, four taps, SiLU, the scan's operands,
# skip, gate, norm, projection) and nine layers of ten are that; the
# three multipliers damp it (on the CPU at toy widths the same ten
# layers read 2% a matrix as published and 7% with all three at 1).
# The per-head scalars (``dt_bias``, ``A_log``, ``D``: 64 numbers a
# layer) sum their gradient over every position and head entry, terms
# of both signs, so their relative error is the largest of a sound run
# and moves most with the seed.
#
# Readings on the v5e at the published widths, 2 x 2,048 tokens (my chip
# runs, PR 34; PERF.md section 2 has every one). Sound, 16 seeds (six
# through compare.py alone, twelve cells' comparison children): the
# worst leaf of a run 0.041-0.077, a per-head scalar every time
# (``A_log`` in nine, ``dt_bias`` in seven), the largest 0.0765 (``L8.A_log``) and
# 0.0761 (``L9.A_log``); every matrix 0.024-0.034, the norms
# 0.019-0.035, ``D`` 0.021-0.042, ``embed`` 0.030-0.031,
# ``final_norm`` 0.019-0.021. The control (every operand of every
# product rounded to float8_e4m3fn), 3 seeds: worst leaf 0.393 / 0.449
# / 0.502 (an ``A_log`` each time), every matrix 0.25-0.30,
# ``final_norm`` 0.168-0.172, and under the limit only some of the
# per-head scalars (smallest 0.111). One number has to hold all 128
# leaves (compare.py's interface), so it stands where the noisiest
# leaves need it: 1.96 x the largest sound reading, and the control's
# smallest worst leaf is 2.6 x it; every matrix and norm of the control
# is over it by itself (1.1-2 x), a sound matrix 4.4 x under it.
GRAD_REL_L2_TOL = 0.15
# The loss: the program returns it in bf16 (the tied head's logits come
# out in the module's dtype), so it is held to one bf16 spacing at the
# bottom of a binade, 2^-7 = 0.0078, as the other configurations' is.
# It is there for a part of the batch or of the positions left out of
# the loss, not for the precision.
LOSS_REL_TOL = 2.0**-7

# positions a block of the recurrence: the state is kept once a block
SCAN_BLOCK = 64
LETTERS = {"m": "mamba", "a": "attention"}


def from_program(params, model_params):
    """The program's flax parameter tree -> the reference's: one flat
    dict of float32 arrays named ``L<i>.<leaf>``, each the program's
    own array (nothing is cut or transposed). Works on parameters and
    on gradients alike (they share the tree)."""
    out = {
        "embed": params["embed"]["embedding"],
        "final_norm": params["final_norm"]["scale"],
    }
    for i, kind in enumerate(model_params["layer_pattern"]):
        layer = {
            "operator_norm": params["layer_%d_operator_norm" % i]["scale"],
            "ffn_norm": params["layer_%d_ffn_norm" % i]["scale"],
        }
        if LETTERS[kind] == "mamba":
            mamba = params["layer_%d_mamba" % i]
            layer.update(
                in_proj=mamba["in_proj"]["kernel"],
                conv_taps=mamba["conv_kernel"],
                conv_bias=mamba["conv_bias"],
                dt_bias=mamba["dt_bias"],
                A_log=mamba["A_log"],
                D=mamba["D"],
                gated_norm=mamba["norm"]["scale"],
                out_proj=mamba["out_proj"]["kernel"],
            )
        else:
            attn = params["layer_%d_attention" % i]
            layer.update(
                wq=attn["query"]["kernel"],
                wk=attn["key"]["kernel"],
                wv=attn["value"]["kernel"],
                wo=attn["out"]["kernel"],
            )
        mlp = params["layer_%d_mlp" % i]
        for name in ("w1", "w3", "w2"):
            layer[name] = mlp[name]["kernel"]
        for name, value in layer.items():
            out["L%d.%s" % (i, name)] = value
    return {k: jnp.asarray(v, jnp.float32) for k, v in out.items()}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _product(operand):
    """Every matrix product goes through here: ``operand`` is applied
    to both of its operands."""

    def product(subscripts, a, b):
        return jnp.einsum(subscripts, operand(a), operand(b))

    return product


def selective_scan(x, delta, a, b, c, operand=lambda t: t):
    """``y_t = S_t c_t`` of ``S_t = exp(delta_t a) S_{t-1} + (delta_t
    x_t) (x) b_t``, position by position. ``x``: (B, L, H, P);
    ``delta``: (B, L, H); ``a``: (H,); ``b``, ``c``: (B, L, G, N).
    Returns (B, L, H, P). The two products of a position, the outer
    product into the state and the state against ``c``, take their
    operands through ``operand``."""
    batch, length, heads, p = x.shape
    per_group = heads // b.shape[2]
    decay = jnp.exp(delta * a)
    pushed = operand(delta[..., None] * x)
    b = jnp.repeat(operand(b), per_group, axis=2)
    c = jnp.repeat(operand(c), per_group, axis=2)
    block = next(d for d in range(min(SCAN_BLOCK, length), 0, -1) if length % d == 0)

    def position(state, at):
        decay_t, pushed_t, b_t, c_t = at
        state = (
            decay_t[..., None, None] * state
            + pushed_t[..., :, None] * b_t[..., None, :]
        )
        return state, jnp.sum(operand(state) * c_t[..., None, :], axis=-1)

    @jax.checkpoint
    def positions(state, at):
        return jax.lax.scan(position, state, at)

    def in_blocks(t):
        """(B, L, ...) -> (L / block, block, B, ...)"""
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((length // block, block) + t.shape[1:])

    first = jnp.zeros((batch, heads, p, b.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(
        positions, first, tuple(in_blocks(t) for t in (decay, pushed, b, c))
    )
    return jnp.moveaxis(y.reshape((length,) + y.shape[2:]), 0, 1)


def mamba(h, w, sizes, product, operand):
    heads, p = sizes["ssm_heads"], sizes["ssm_head_dim"]
    groups, n = sizes["ssm_groups"], sizes["ssm_state"]
    inner, shared = heads * p, groups * n
    projected = product("bld,de->ble", h, w["in_proj"])
    z = projected[..., :inner]
    xbc = projected[..., inner : 2 * inner + 2 * shared]
    dt = projected[..., 2 * inner + 2 * shared :]
    length = xbc.shape[1]
    conv = jnp.zeros_like(xbc)
    for j in range(w["conv_taps"].shape[0]):
        # xBC delayed by j positions, zeros shifted in
        delayed = jnp.pad(xbc, ((0, 0), (j, 0), (0, 0)))[:, :length]
        conv = conv + w["conv_taps"][j] * delayed
    xbc = jax.nn.silu(conv + w["conv_bias"])
    x = xbc[..., :inner].reshape(xbc.shape[:2] + (heads, p))
    b = xbc[..., inner : inner + shared].reshape(xbc.shape[:2] + (groups, n))
    c = xbc[..., inner + shared :].reshape(xbc.shape[:2] + (groups, n))
    delta = jax.nn.softplus(dt + w["dt_bias"])
    y = selective_scan(x, delta, -jnp.exp(w["A_log"]), b, c, operand)
    y = y + w["D"][:, None] * x
    gated = y.reshape(z.shape) * jax.nn.silu(z)
    return product(
        "ble,ed->bld", _rms(gated, w["gated_norm"], sizes["norm_eps"]), w["out_proj"]
    )


def attention(h, w, sizes, product):
    length = h.shape[1]
    q = product("bld,dhk->blhk", h, w["wq"])
    k = product("bld,dhk->blhk", h, w["wk"])
    v = product("bld,dhk->blhk", h, w["wv"])
    group = q.shape[2] // k.shape[2]
    # query head i reads KV head i // group
    q = q.reshape(q.shape[:2] + (k.shape[2], group, q.shape[-1]))
    causal = jnp.tril(jnp.ones((length, length), bool))

    def one_sequence(qkv):
        q, k, v = qkv
        scores = product("qjgk,mjk->jgqm", q, k) * sizes["attention_scale"]
        scores = jnp.where(causal, scores, -jnp.inf)
        return product("jgqm,mjk->qjgk", jax.nn.softmax(scores, axis=-1), v)

    attn = jax.lax.map(one_sequence, (q, k, v))
    attn = attn.reshape(attn.shape[:2] + (-1, attn.shape[-1]))
    return product("bqhk,hkd->bqd", attn, w["wo"])


def swiglu(h, w1, w3, w2, product):
    gate = jax.nn.silu(product("...d,df->...f", h, w1))
    return product("...f,fd->...d", gate * product("...d,df->...f", h, w3), w2)


def _layer(x, w, kind, sizes, product, operand):
    eps, r = sizes["norm_eps"], sizes["residual_multiplier"]
    h = _rms(x, w["operator_norm"], eps)
    if LETTERS[kind] == "mamba":
        x = x + r * mamba(h, w, sizes, product, operand)
    else:
        x = x + r * attention(h, w, sizes, product)
    h = _rms(x, w["ffn_norm"], eps)
    return x + r * swiglu(h, w["w1"], w["w3"], w["w2"], product)


def forward(weights, tokens, model_params, operand=None):
    """Logits (B, L, V), float32."""
    sizes = model_params
    if sizes["num_dense_layers"] != len(sizes["layer_pattern"]):
        raise ValueError("this reference has no expert layer")
    operand = operand or (lambda x: x)
    product = _product(operand)
    with jax.default_matmul_precision("highest"):
        x = sizes["embedding_multiplier"] * weights["embed"][tokens]
        for i, kind in enumerate(sizes["layer_pattern"]):
            prefix = "L%d." % i
            w = {
                name[len(prefix) :]: value
                for name, value in weights.items()
                if name.startswith(prefix)
            }
            # rematerialised: changes when values are computed, not
            # which, and keeps one layer's activations alive at a time
            x = jax.checkpoint(
                lambda x, w, kind=kind: _layer(x, w, kind, sizes, product, operand)
            )(x, w)
        x = _rms(x, weights["final_norm"], sizes["norm_eps"])
        logits = product("bld,vd->blv", x, weights["embed"])
        return logits / sizes["logits_scaling"]


def loss(weights, tokens, model_params, operand=None):
    """Next-token cross entropy, mean over the L-1 predicted positions."""
    logits = forward(weights, tokens, model_params, operand)[:, :-1]
    targets = tokens[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


def loss_and_grads(weights, tokens, model_params, operand=None):
    """The loss and, leaf by leaf of ``weights``, its gradients."""
    return jax.value_and_grad(
        lambda weights: loss(weights, tokens, model_params, operand)
    )(weights)
