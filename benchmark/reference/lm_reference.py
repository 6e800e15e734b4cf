"""The zoo transformer LM, written out plainly: forward, loss, gradients.

float32 throughout, matrix products at ``highest`` precision, no flax
module, no kernel, no cache. This is what ``correct`` compares the
program's ``custom_model`` + ``loss`` against, on the same weights and
the same batch. It follows ``model_zoo/transformer_lm`` as the program
documents it, equation by equation:

    x_0   = E[tokens]
    h     = rmsnorm(x; g1)                 y = x / sqrt(mean(x^2) + 1e-6) * g
    q,k,v = h Wq, h Wk, h Wv               (heads x head_dim, no bias)
    q,k   = rotary(q), rotary(k)           halves rotated, base 10000
    a     = softmax(q k^T / sqrt(hd) + causal mask) v
    x     = x + a Wo                       (no bias)
    h     = rmsnorm(x; g2)
    x     = x + gelu_tanh(h W1 + b1) W2 + b2
    logits = rmsnorm(x_L; gf) E^T          (tied head)
    loss  = mean over positions 0..L-2 of CE(logits_t, tokens_{t+1})

The layers run under one ``lax.scan`` over weights stacked on a leading
layer axis, so the whole thing compiles in seconds at any depth; the
scan body is rematerialised (``jax.checkpoint``), which changes when
values are computed, not which, and keeps 24 layers of L x L f32
attention scores out of memory at once.

Departures from the program, on purpose: everything is f32 where the
configuration computes in bf16 (that difference is what the tolerance
in ``benchmark/compare.py`` is sized for).
"""

import jax
import jax.numpy as jnp

RMS_EPS = 1e-6
ROTARY_BASE = 10000.0
_LAYER_LEAVES = (
    "g1", "wq", "wk", "wv", "wo", "g2", "w1", "b1", "w2", "b2",
)  # fmt: skip


def stack_program_params(params, num_layers):
    """The program's flax parameter tree -> the reference's: one dict of
    arrays, per-layer leaves stacked on axis 0. Works on arrays and on
    gradients alike (they share the tree)."""
    blocks = [params["block_%d" % i] for i in range(num_layers)]

    def stacked(get):
        return jnp.stack([get(b) for b in blocks]).astype(jnp.float32)

    return {
        "embed": params["embed"]["embedding"].astype(jnp.float32),
        "gf": params["RMSNorm_0"]["scale"].astype(jnp.float32),
        "g1": stacked(lambda b: b["RMSNorm_0"]["scale"]),
        "wq": stacked(lambda b: b["query"]["kernel"]),
        "wk": stacked(lambda b: b["key"]["kernel"]),
        "wv": stacked(lambda b: b["value"]["kernel"]),
        "wo": stacked(lambda b: b["out"]["kernel"]),
        "g2": stacked(lambda b: b["RMSNorm_1"]["scale"]),
        "w1": stacked(lambda b: b["mlp_up"]["kernel"]),
        "b1": stacked(lambda b: b["mlp_up"]["bias"]),
        "w2": stacked(lambda b: b["mlp_down"]["kernel"]),
        "b2": stacked(lambda b: b["mlp_down"]["bias"]),
    }


def _rmsnorm(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + RMS_EPS) * g


def _rotary(x):
    """x: (B, L, H, D). Rotates the two halves of D by position."""
    length, half = x.shape[1], x.shape[-1] // 2
    freqs = 1.0 / (ROTARY_BASE ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * freqs  # (L, half)
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


def _layer(x, w):
    length = x.shape[1]
    h = _rmsnorm(x, w["g1"])
    q = _rotary(jnp.einsum("bld,dhk->blhk", h, w["wq"]))
    k = _rotary(jnp.einsum("bld,dhk->blhk", h, w["wk"]))
    v = jnp.einsum("bld,dhk->blhk", h, w["wv"])
    scores = jnp.einsum("bqhk,bmhk->bhqm", q, k) * (q.shape[-1] ** -0.5)
    causal = jnp.tril(jnp.ones((length, length), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = jnp.einsum("bhqm,bmhk->bqhk", jax.nn.softmax(scores, axis=-1), v)
    x = x + jnp.einsum("bqhk,hkd->bqd", attn, w["wo"])
    h = _rmsnorm(x, w["g2"])
    h = _gelu_tanh(h @ w["w1"] + w["b1"])
    return x + h @ w["w2"] + w["b2"]


def forward(weights, tokens):
    """Logits (B, L, V), float32."""
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens]
        layers = {name: weights[name] for name in _LAYER_LEAVES}
        x, _ = jax.lax.scan(
            jax.checkpoint(lambda x, w: (_layer(x, w), None)), x, layers
        )
        return _rmsnorm(x, weights["gf"]) @ weights["embed"].T


def loss(weights, tokens):
    """Next-token cross entropy, mean over the L-1 predicted positions."""
    logits = forward(weights, tokens)[:, :-1]
    targets = tokens[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


loss_and_grads = jax.value_and_grad(loss)
