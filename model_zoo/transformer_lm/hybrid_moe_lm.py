"""Hybrid sparse decoder LM: gated short convolutions beside grouped-head
attention, a dense SwiGLU MLP in the leading layers and a mixture of
experts in the rest, of which this device holds a share.

The layer stack is built from one pattern string, a letter a layer:
``c`` a gated short convolution, ``a`` full causal attention
(``layer_pattern=caccc``: no comma, it travels in ``--model_params``).
The first ``num_dense_layers`` layers have the dense MLP, the others the
expert layer. With ``h = RMSNorm(x)`` (weight only, no bias anywhere):

    x <- x + Op(RMSNorm(x));  x <- x + FF(RMSNorm(x))
    logits = RMSNorm(x_last) E^T                      (tied head)

- ``c``: ``[B, C, X] = split(h W_in, 3)``; ``u = B * X``;
  ``v_t = sum_{j<K} k_j * u_{t-j}`` (depthwise, causal, ``u_{<0} = 0``);
  ``out = (C * v) W_out``.
- ``a``: ``num_heads`` query heads over ``num_kv_heads`` key/value
  heads, a learned RMSNorm over each head of q and of k, rotary
  positions of base ``rope_theta``, causal softmax attention; KV head
  ``j`` serves query heads ``j*g .. j*g+g-1``. The KV heads are repeated
  in front of ops/flash_attention.py (grouped heads inside the kernel
  are not built).
- dense FF: ``W_2 (silu(h W_1) * (h W_3))``.
- expert FF: parallel/expert.py's held-share, dropless layer: sigmoid
  scores over ``num_experts`` in float32, ``num_experts_per_tok``
  selected with a bias that only steers the selection, gates
  normalised over the selected; this device computes the part
  experts ``first_expert_held .. first_expert_held + experts_held - 1``
  give, and that partial result goes on to the next layer.

``expert_bias`` and the ``assignments`` counters are state, not
parameters (collection ``moe_state``): written only where the
collection is mutable (a training step), and with the collection
absent the bias is zero, its initial value.

What the two LMs of this directory share comes from the sibling module
(a zoo module is loaded by path, not as a package): ``_rotary``,
``loss``, ``optimizer``, ``dataset_fn``, ``eval_metrics_fn``, and the
one attention policy ``pick_causal_attention``.
"""

import os
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.common.model_utils import load_module
from elasticdl_tpu.ops.flash_attention import pick_causal_attention
from elasticdl_tpu.parallel import expert

_lm = load_module(
    os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "transformer_lm.py"
    )
)
loss = _lm.loss
optimizer = _lm.optimizer
dataset_fn = _lm.dataset_fn
eval_metrics_fn = _lm.eval_metrics_fn

CONV, ATTENTION = "c", "a"


def _per_expert_init():
    """lecun-normal over each expert's own (in, out) matrix of a
    stacked (G, in, out) parameter."""
    return nn.initializers.variance_scaling(
        1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1,
        batch_axis=(0,),
    )  # fmt: skip


class ShortConv(nn.Module):
    """The gated short convolution operator."""

    kernel_size: int
    dtype: Any

    @nn.compact
    def __call__(self, h):
        d = h.shape[-1]
        gates = nn.Dense(
            3 * d, use_bias=False, dtype=self.dtype, name="in_proj"
        )(h)
        b, c, x = jnp.split(gates, 3, axis=-1)
        u = b * x
        taps = self.param(
            "conv_kernel",
            nn.initializers.normal(self.kernel_size**-0.5),
            (self.kernel_size, d),
        ).astype(self.dtype)
        length, last = u.shape[1], self.kernel_size - 1
        padded = jnp.pad(u, ((0, 0), (last, 0), (0, 0)))
        # tap j multiplies u_{t-j}: the padded sequence from K-1-j on
        v = sum(
            taps[j] * padded[:, last - j : last - j + length]
            for j in range(self.kernel_size)
        )
        return nn.Dense(
            d, use_bias=False, dtype=self.dtype, name="out_proj"
        )(c * v)


class GroupedAttention(nn.Module):
    """The full-attention operator over grouped KV heads."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    norm_eps: float
    dtype: Any
    use_flash: bool

    @nn.compact
    def __call__(self, h, positions):
        def heads(n, name):
            return nn.DenseGeneral(
                features=(n, self.head_dim),
                axis=-1,
                use_bias=False,
                dtype=self.dtype,
                name=name,
            )(h)

        def head_norm(name):
            return nn.RMSNorm(
                epsilon=self.norm_eps, dtype=self.dtype, name=name
            )

        q = head_norm("q_norm")(heads(self.num_heads, "query"))
        k = head_norm("k_norm")(heads(self.num_kv_heads, "key"))
        v = heads(self.num_kv_heads, "value")
        q = _lm._rotary(q, positions, self.rope_theta)
        k = _lm._rotary(k, positions, self.rope_theta)
        group = self.num_heads // self.num_kv_heads
        attention_fn = pick_causal_attention(h.shape[1], self.use_flash)
        attn = attention_fn(
            q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        )
        return nn.DenseGeneral(
            features=h.shape[-1],
            axis=(-2, -1),
            use_bias=False,
            dtype=self.dtype,
            name="out",
        )(attn)


class SwiGLU(nn.Module):
    width: int
    dtype: Any

    @nn.compact
    def __call__(self, h):
        def dense(features, name):
            return nn.Dense(
                features, use_bias=False, dtype=self.dtype, name=name
            )

        gate = nn.silu(dense(self.width, "w1")(h)) * dense(self.width, "w3")(h)
        return dense(h.shape[-1], "w2")(gate)


class HeldExperts(nn.Module):
    """This device's share of one expert layer (parallel/expert.py)."""

    num_experts: int
    experts_held: int
    first_expert_held: int
    num_experts_per_tok: int
    expert_dim: int
    routed_scaling_factor: float
    expert_bias_rate: float
    dtype: Any

    @nn.compact
    def __call__(self, h):
        d = h.shape[-1]
        router = self.param(
            "router", nn.initializers.lecun_normal(), (d, self.num_experts)
        )
        w_in = self.param(
            "experts_w13",
            _per_expert_init(),
            (self.experts_held, d, 2 * self.expert_dim),
        )
        w_out = self.param(
            "experts_w2",
            _per_expert_init(),
            (self.experts_held, self.expert_dim, d),
        )
        tokens = h.reshape(-1, d)
        # logits and scores in float32: the selection is discrete, and
        # a bf16 logit would change some percent of fourth choices
        logits = jnp.dot(
            tokens.astype(jnp.float32),
            router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        collection = expert.MOE_STATE_COLLECTION
        has_state = self.is_initializing() or self.has_variable(
            collection, "expert_bias"
        )
        bias = jnp.zeros((self.num_experts,), jnp.float32)
        if has_state:
            bias_state = self.variable(
                collection, "expert_bias", lambda: bias
            )
            made = self.variable(
                collection,
                "assignments",
                lambda: jnp.zeros((self.num_experts,), jnp.int32),
            )
            bias = bias_state.value
        selected, gates = expert.sigmoid_topk_route(
            logits, bias, self.num_experts_per_tok, self.routed_scaling_factor
        )
        if (
            has_state
            and not self.is_initializing()
            and self.is_mutable_collection(collection)
        ):
            # after the step, outside the gradient: this step selected
            # with the bias as it was
            counts = expert.expert_assignments(selected, self.num_experts)
            bias_state.value = expert.expert_bias_update(
                bias, counts, self.expert_bias_rate
            )
            made.value = made.value + counts
        out = expert.held_experts_apply(
            tokens,
            selected,
            gates,
            w_in.astype(self.dtype),
            w_out.astype(self.dtype),
            self.first_expert_held,
        )
        return out.reshape(h.shape)


class HybridMoELM(nn.Module):
    vocab_size: int = 1024
    layer_pattern: str = "caccc"
    num_dense_layers: int = 1
    embed_dim: int = 64
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    mlp_dim: int = 256
    expert_dim: int = 64
    num_experts: int = 16
    experts_held: int = 4
    first_expert_held: int = 0
    num_experts_per_tok: int = 2
    conv_kernel: int = 3
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    routed_scaling_factor: float = 1.0
    expert_bias_rate: float = 1e-3
    dtype: Any = jnp.float32
    use_flash: bool = True

    def step_facts(self):
        """What the worker's ``step_built`` event says of this model's
        layout (scalars), and what its window counters are read with."""
        return {
            "expert_layers": len(self.layer_pattern) - self.num_dense_layers,
            "experts_held": self.experts_held,
            "experts_routed": self.num_experts,
            "first_expert_held": self.first_expert_held,
            "conv_layers": self.layer_pattern.count(CONV),
            "attention_layers": self.layer_pattern.count(ATTENTION),
        }

    @nn.compact
    def __call__(self, features, training=False):
        tokens = (
            features["tokens"] if isinstance(features, dict) else features
        )
        tokens = tokens.astype(jnp.int32)
        b, l = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32), (b, l))

        def norm(name):
            return nn.RMSNorm(
                epsilon=self.norm_eps, dtype=self.dtype, name=name
            )

        embed_layer = nn.Embed(
            self.vocab_size, self.embed_dim, dtype=self.dtype, name="embed"
        )
        x = embed_layer(tokens)
        for i, kind in enumerate(self.layer_pattern):
            layer = "layer_%d_" % i
            h = norm(layer + "operator_norm")(x)
            if kind == CONV:
                with jax.named_scope("edl/short_conv"):
                    x = x + ShortConv(
                        self.conv_kernel, self.dtype, name=layer + "conv"
                    )(h)
            else:
                x = x + GroupedAttention(
                    num_heads=self.num_heads,
                    num_kv_heads=self.num_kv_heads,
                    head_dim=self.head_dim,
                    rope_theta=self.rope_theta,
                    norm_eps=self.norm_eps,
                    dtype=self.dtype,
                    use_flash=self.use_flash,
                    name=layer + "attention",
                )(h, positions)
            h = norm(layer + "ffn_norm")(x)
            if i < self.num_dense_layers:
                x = x + SwiGLU(self.mlp_dim, self.dtype, name=layer + "mlp")(h)
            else:
                with jax.named_scope("edl/moe"):
                    x = x + HeldExperts(
                        num_experts=self.num_experts,
                        experts_held=self.experts_held,
                        first_expert_held=self.first_expert_held,
                        num_experts_per_tok=self.num_experts_per_tok,
                        expert_dim=self.expert_dim,
                        routed_scaling_factor=self.routed_scaling_factor,
                        expert_bias_rate=self.expert_bias_rate,
                        dtype=self.dtype,
                        name=layer + "moe",
                    )(h)
        x = norm("final_norm")(x)
        # weight-tied head over the slice of the vocabulary held here
        return embed_layer.attend(x.astype(jnp.float32))


def custom_model(dtype="float32", **sizes):
    """``HybridMoELM(**sizes)``; every size has the toy default of the
    class, and a name it does not know is refused."""
    pattern = str(sizes.get("layer_pattern", HybridMoELM.layer_pattern))
    if not pattern or set(pattern) - {CONV, ATTENTION}:
        raise ValueError(
            "layer_pattern %r: a letter a layer, %r a short convolution, "
            "%r full attention" % (pattern, CONV, ATTENTION)
        )
    model = HybridMoELM(dtype=jnp.dtype(dtype), **sizes)
    if not 0 <= model.num_dense_layers <= len(pattern):
        raise ValueError("num_dense_layers outside the pattern")
    if model.num_heads % model.num_kv_heads:
        raise ValueError("num_heads is not a multiple of num_kv_heads")
    if not (
        0 < model.experts_held
        and 0 <= model.first_expert_held
        and model.first_expert_held + model.experts_held <= model.num_experts
    ):
        raise ValueError("the experts held are not among num_experts")
    return model
