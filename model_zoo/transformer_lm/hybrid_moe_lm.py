"""Hybrid sparse decoder LM: gated short convolutions, Mamba-2
state-space layers and Kimi-Delta-Attention layers beside grouped-head
attention, full, within a window or over a learned selection of keys,
and latent attention (MLA), a dense SwiGLU MLP in the leading layers and
a mixture of experts in the rest, of which this device holds a share,
and, where asked for, one multi-token-prediction module behind the last
layer.

The layer stack is built from one pattern string, a letter a layer:
``c`` a gated short convolution, ``m`` a Mamba-2 state-space layer,
``k`` a Kimi-Delta-Attention layer, ``a`` full causal attention, ``w``
causal attention within the ``attention_window`` nearest keys, ``s``
attention over the ``select_topk`` keys an indexer picks for each
query, ``l`` full causal attention through a low-rank latent
(``layer_pattern=caccc``: no comma, it travels in ``--model_params``).
The first
``num_dense_layers`` layers (none is fine, and so is all of them: a
stack with no expert layer keeps no routing state) have the dense MLP,
the others the expert layer. With ``h = RMSNorm(x)`` (weight only) and
``r = residual_multiplier``:

    x_0 = embedding_multiplier * E[tok]
    x <- x + r * Op(RMSNorm(x));  x <- x + r * FF(RMSNorm(x))
    logits = RMSNorm(x_last) E^T / logits_scaling      (``tie_head``, the default)
    logits = RMSNorm(x_last) W_head / logits_scaling   (untied: a matrix of its own)

The three multipliers are 1 by default and then multiply nothing.
Embedding and head are over the slice of the vocabulary held here.

``mtp_layers=1`` adds the multi-token-prediction module of DeepSeek-V3
(arXiv:2412.19437 section 2.2) at depth 1. With ``x`` the last layer's
output IN FRONT of the final norm, ``tok`` the input ids and ``L`` the
length:

    u_i = [RMSNorm_h(x_i) ; RMSNorm_e(E[tok_{i+1}])] W_M     (W_M: 2 embed_dim x embed_dim, no bias)
    y = Layer(u)         one more layer of the kind of the pattern's LAST
                         (operator and feed-forward half), weights,
                         router, selection bias and counters its own
    logits1_i = RMSNorm_m(y_i) W_head / logits_scaling    (the trunk's own head; tied: E^T)
    L_mtp = mean over i = 0..L-3 of CE(logits1_i, tok_{i+2})

and a training step descends ``L_lm + mtp_loss_weight * L_mtp``. The
embedding and the head are SHARED with the trunk and nothing is
stopped: the module's loss reaches the trunk, the table and the head.
The module runs only where a loss is asked for (``training=True``, and
when the variables are made); a prediction or evaluation forward
returns the trunk's logits and computes nothing of it. Its weighted
loss goes where a step builder looks for it, the ``aux_loss``
collection (``training/step.py``: every builder adds the collection to
the loss it differentiates), under the name ``mtp_loss``; a training
forward that was handed no such collection (``model.apply({"params":
...}, ..., training=True)``, the comparison's and the tests') returns
``LogitsAndLoss(logits, mtp_loss)`` instead, which this module's
``loss`` adds up. The module's layer runs all ``L`` positions, so that
the attention kernels tile (``L - 1`` does not divide by 128): the last
position reads the embedding of the sequence's FIRST token where there
is no next one, causal attention and a per-token feed-forward keep it
from every other position, no loss reads it, and its assignments are
counted in the module's routing counters (one token in ``L``). The
module's logits are float32 and never exist whole: the head and the
softmax go ``2,048`` positions at a time (``_mean_cross_entropy``).
Under ``remat_layers`` the module is one more rematerialised unit, its
layer's products kept as any layer's, its projection and its pass
through the head recomputed. Modules chained one behind the other
(depth 2 and more) are not built.
``remat_layers``: the backward pass keeps a layer's input and the
results of its products against weight matrices, but for an operator's
input projection, and recomputes the rest (``jax.checkpoint`` a layer
that keeps what is named ``KEPT``; selective recomputation, Korthikanti
et al., arXiv:2205.05198, section 5). Kept: ``out_proj`` of a ``c`` or
``m`` layer, ``out`` of a ``k`` layer (its five input projections are
recomputed, as ``in_proj`` is), ``q``, ``k``, ``v`` and ``o`` of an
attention layer, the four products of an ``l`` layer (five with the
low-rank query: ``q_down``'s is kept like the others, 12.6 MB a layer
at 8,192 positions of 768, where recomputing it is a product of 26
GFLOP: the rule stays "a product against a weight matrix is kept"),
``W_1`` and ``W_3`` of the dense FF (``W_2``'s result is needed by
nothing): ``2 (embed_dim + 2 mlp_dim)`` bytes a token a ``c`` or ``m``
layer in bf16 (37 KB at granite-4.0-h-micro's widths). Recomputed:
both norms, ``in_proj`` of a ``c`` or ``m`` layer (kept, its result
lies in HBM for the forward's own convolution and gates to read, which
costs more than the product does: PERF.md section 6, PR 36), the
residual scalings, ``silu`` and the gates, the depthwise convolution,
``softplus``, the scan (ops/ssd.py), the gated norm, the attention
itself (the kernel's forward runs twice) and an expert FF whole. A
``k`` layer's recurrence is NOT recomputed: the policy also keeps what
ops/kda.py names (``kda.KEPT_NAMES``: the recurrence's output in the
model's dtype, the float32 states at its group boundaries and the
float32 inverse of every chunk's triangular matrix, 168 MB a layer at
2 x 4,096 positions of 32 heads of 128 in chunks of 64), so the
recomputed layer runs the five projections, convolutions and gates in
front of it and the gated norm behind it, and the recurrence's backward
pass rebuilds each group from its kept state and inverses: two passes
forward a step where there were three (PERF.md section 6, PR 43), and
one inversion of a chunk's matrix where there were three (PR 44). A
model parameter, since the worker's ``--remat`` wraps the whole
forward, which does not lower the peak.

- ``c``: ``[B, C, X] = split(h W_in, 3)``; ``u = B * X``;
  ``v_t = sum_{j<K} k_j * u_{t-j}`` (depthwise, causal, ``u_{<0} = 0``);
  ``out = (C * v) W_out``.
- ``m`` (ops/ssd.py has the scan), with ``H = ssm_heads``, ``P =
  ssm_head_dim``, ``N = ssm_state``, ``G = ssm_groups``:
  ``[z | xBC | dt] = h W_in`` (``H P | H P + 2 G N | H``);
  ``xBC = silu(causal_depthwise_conv(xBC) + b_conv)``
  (``ssm_conv_kernel`` taps); ``[X | B | C] = xBC``;
  ``delta = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` (a head
  each, float32, no clamp);
  ``S_t = exp(delta_t A) S_{t-1} + delta_t X_t (x) B_t``,
  ``y_t = S_t C_t + D X_t``; ``out = (RMSNorm(y * silu(z)) * w) W_out``
  (one norm over all ``H P``). Initial ``A_log = log(1..H)``, ``D =
  1``, ``dt_bias`` the inverse softplus of a log-uniform draw in
  [0.001, 0.1] (Mamba-2's published initialisation).
- ``k`` (ops/kda.py has the recurrence), with ``H = kda_heads``, ``D =
  kda_head_dim`` for keys and values alike:
  ``q = l2norm(silu(conv(h W_q)))``, ``k = l2norm(silu(conv(h W_k)))``,
  ``v = silu(conv(h W_v))`` (each ``H D`` wide, a causal depthwise
  convolution of ``kda_conv_kernel`` taps each, the norm over a head,
  ``x / sqrt(sum x^2 + 1e-6)``);
  ``g = kda_gate_lower_bound * sigmoid(exp(A_log) * (h W_f +
  dt_bias))``, the log-decay a CHANNEL, float32, between the bound and
  0 (``A_log`` a head, ``dt_bias`` a channel);
  ``beta = sigmoid(h W_beta)`` a head;
  ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t
  v_t^T``, ``o_t = D ** -0.5 * S_t^T q_t``;
  ``out = (sigmoid(h W_g) * RMSNorm(o)) W_o`` (one gate a head, one
  learned norm weight of ``D`` shared by the heads). Initial ``A_log``
  the log of a uniform draw in [1, 16], ``dt_bias`` as Mamba-2's.
- ``l``: ``q = h W_q`` (``num_heads`` x ``[mla_nope_dim |
  mla_rope_dim]``), or, with ``mla_q_rank`` R > 0, through a latent of
  the query's own: ``c_q = RMSNorm(h W_qa)`` (R wide, a learned
  weight), ``q = c_q W_qb``; ``[c | k_rope] = h W_kva`` (``mla_kv_rank |
  mla_rope_dim``), ``c <- RMSNorm(c)``; ``[k_nope | v] = c W_kvb``
  (``num_heads`` x ``[mla_nope_dim | mla_v_dim]``); ``q_rope`` and
  ``k_rope`` rotated with base ``rope_theta``, ``k_rope`` ONE head that
  every query head reads; causal softmax attention of ``q . [k_nope |
  k_rope] / sqrt(mla_nope_dim + mla_rope_dim)`` over ``v``; ``out =
  concat(heads) W_o``. The expanded form a latent attention is TRAINED
  in (DeepSeek-V2, arXiv:2405.04434 section 2.1): the kernels of
  ops/flash_attention.py take q and k of one head size and v of
  another; where the three are equally wide (192 + 64 and 256) the
  call is the plain kernels'. The absorbed form and a cache of latents
  belong to a serving path, which this model has none of.
- ``a``: ``num_heads`` query heads over ``num_kv_heads`` key/value
  heads, a learned RMSNorm over each head of q and of k (``qk_norm``,
  on by default), rotary positions of base ``rope_theta`` (``rope``, on
  by default; off, the layer has no positions at all), causal softmax
  attention of ``attention_scale * q k^T`` (``head_dim ** -0.5`` by
  default); KV head ``j`` serves query heads ``j*g .. j*g+g-1``. The KV
  heads are repeated in front of ops/flash_attention.py (grouped heads
  inside the kernel are not built).
- ``w``: ``a`` with ``o_t = sum_{t - W < s <= t} softmax(q_t k_s /
  sqrt(hd)) v_s``, ``W = attention_window`` keys, the query's own among
  them: the kernels compute the band and skip what lies outside it
  (``flash_attention(..., window=W)``), lengths under the policy's get
  the same mask in XLA. Positions are by layer kind: ``rope`` is the
  switch of the ``a`` and ``s`` layers, ``window_rope`` (on by default)
  that of the ``w`` layers, so a model may rotate inside its windows and
  give its global layers no positions at all (``rope=False``).
- ``s``: ``a`` with ``o_t = sum_{s in S_t} softmax_{s in S_t}(q_t k_s /
  sqrt(hd)) v_s``. The indexer, float32, on ``hb = stop_gradient(h)``:
  ``qI = hb W_qI`` (``indexer_heads`` x ``indexer_dim``), ``kI = hb
  W_kI`` (one key head), ``w = hb W_w``; ``I[t, s] = sum_j w[t, j]
  relu(qI[t, j] . kI[s])``; ``S_t`` the ``s <= t`` with the
  ``select_topk`` largest, every ``s <= t`` while ``t < select_topk``
  (ops/sparse_select.py). No rotation, norm or scale inside it. The
  selection is discrete, so under the LM loss the indexer's three
  matrices get no gradient; this module's ``optimizer`` keeps them out
  of AdamW altogether (no moments, no decay): a step hands them back
  bit for bit. The loss that would train them (alignment with the
  attention's own probabilities) is not built.
- dense FF: ``W_2 (silu(h W_1) * (h W_3))``.
- expert FF: parallel/expert.py's held-share, dropless layer: scores
  over ``num_experts`` in float32, ``num_experts_per_tok`` selected,
  gates normalised over the selected; this device computes the part
  experts ``first_expert_held .. first_expert_held + experts_held - 1``
  give, and that partial result goes on to the next layer. An expert
  is ``W_2 (act(u W_1) * (u W_3))`` with ``act`` ``expert_act``:
  ``silu`` (the default, a SwiGLU) or ``relu`` (a ReGLU). The router
  reads ``router_input``: ``ffn_norm`` (the default), the expert
  layer's own normed input ``u``, or ``operator_norm``, the normed
  input ``h`` of the operator in front of it (a router placed before
  the attention), while the experts read ``u`` either way. ``routing``
  names the scores: ``sigmoid_bias`` (the default), a sigmoid of each
  logit, selected with a bias added that only steers the selection and
  follows the load (``expert_bias_rate`` a step; no auxiliary loss),
  gates ``score_e / (sum of the scores selected + 1e-6)``;
  ``softmax``, ``p = softmax(h W_r)``, the largest selected, no bias
  and no bias state, gates ``p_e / sum of the p selected`` (which is
  the softmax over the selected logits alone: the k largest first and
  then a softmax over them is the same selection and the same gates).
  ``expert_apply`` names how the share is computed: ``grouped`` (the
  default), the assignments sorted by expert and three grouped
  products over the rows routed here, whose time follows those rows;
  ``masked``, every held expert over every token with its gate zero
  where the token did not select it, two plain products whose time
  follows from the shapes alone, at ``experts_held`` experts a token
  instead of the ``num_experts_per_tok * experts_held / num_experts``
  routed here in expectation. Both give the same result.
  ``num_expert_groups`` and ``expert_groups_per_tok`` (1 and 1 by
  default: no limit) limit the selection of ``sigmoid_bias`` routing
  to the best groups of consecutive experts
  (``expert.sigmoid_topk_route``). ``shared_expert_dim``, where set,
  adds ``Shared(u)``, one more expert of that width that EVERY token
  passes with no gate: not a share, every chip computes it alike.

``expert_bias`` (of ``sigmoid_bias`` routing only), the ``assignments``
counters and a selecting attention's ``sel_pairs_kept`` /
``sel_pairs_causal`` counters are state, not parameters (collection
``moe_state``): written only where the collection is mutable (a
training step), and with the collection absent the bias is zero, its
initial value.

All the model knows of a kind of layer is one record: ``KINDS`` holds
one a letter, ``DENSE_FF`` and ``EXPERT_FF`` are the feed-forward
halves', and ``Kind`` says how a kind is added. ``custom_model`` applies
one rule to every record: where a layer of the kind is in the pattern
each of the kind's own sizes passes its check, and where none is, a
size other than the class's default says nothing and is refused.

What the two LMs of this directory share comes from the sibling module
(a zoo module is loaded by path, not as a package): ``_rotary``,
``loss``, ``optimizer``, ``dataset_fn``, ``eval_metrics_fn``, and the
one attention policy ``pick_causal_attention``.
"""

import dataclasses
import math
import os
from contextlib import nullcontext
from typing import Any, Callable, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax.ad_checkpoint import checkpoint_name

from elasticdl_tpu.common.model_utils import load_module
from elasticdl_tpu.ops import kda, sparse_select, ssd
from elasticdl_tpu.ops.flash_attention import (
    pick_causal_attention,
    pick_selected_attention,
    window_pairs,
)
from elasticdl_tpu.parallel import expert
from elasticdl_tpu.training.step import AUX_LOSS_COLLECTION

_lm = load_module(
    os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "transformer_lm.py"
    )
)
dataset_fn = _lm.dataset_fn
eval_metrics_fn = _lm.eval_metrics_fn

ROUTINGS = ("sigmoid_bias", "softmax")
EXPERT_APPLIES = ("grouped", "masked")
# the norm whose output the router reads: the expert layer's own, or
# the operator's in front of it (the router "placed before attention")
ROUTER_INPUTS = ("ffn_norm", "operator_norm")
# the name of what ``remat_layers`` keeps for the backward pass
KEPT = "weight_product"
# every parameter of an indexer lies under a module of this name
INDEXER = "indexer"


class LogitsAndLoss(NamedTuple):
    """What a training forward of a model with a prediction module
    returns where it was handed no ``aux_loss`` collection to write
    (``model.apply({"params": ...}, ..., training=True)``, as the
    comparison with the plain reference and the tests make it)."""

    logits: jax.Array
    mtp_loss: jax.Array  # ``mtp_loss_weight`` times the module's loss


def loss(output, labels):
    """The sibling LM's next-token cross entropy, and with it what a
    prediction module's loss came back as beside the logits. (Inside a
    training step it comes through the ``aux_loss`` collection instead,
    and the step builder adds it.)"""
    if isinstance(output, LogitsAndLoss):
        return _lm.loss(output.logits, labels) + output.mtp_loss
    return _lm.loss(output, labels)


def optimizer(lr=3e-3):
    """The sibling LM's optimizer. Of a model whose attention selects
    its keys, over every leaf but the indexers': those get no gradient
    from the LM loss, and AdamW's decay alone would shrink them; they
    are left out of it (no moments, no decay, no update). A model
    without an indexer gets the sibling's optimizer and state as they
    are."""
    plain = _lm.optimizer(lr)

    def labels(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, _: "frozen"
            if any(getattr(key, "key", None) == INDEXER for key in path)
            else "trained",
            params,
        )

    sparing = optax.multi_transform(
        {"trained": plain, "frozen": optax.set_to_zero()}, labels
    )

    def pick(tree):
        held = jax.tree_util.tree_leaves(labels(tree))
        return sparing if "frozen" in held else plain

    return optax.GradientTransformation(
        lambda params: pick(params).init(params),
        lambda updates, state, params=None: pick(updates).update(
            updates, state, params
        ),
    )


def _per_expert_init():
    """lecun-normal over each expert's own (in, out) matrix of a
    stacked (G, in, out) parameter."""
    return nn.initializers.variance_scaling(
        1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1,
        batch_axis=(0,),
    )  # fmt: skip


def _kept(product):
    return checkpoint_name(product, KEPT)


def _mean_cross_entropy(y, head, scaling, targets, counted, rows=2048):
    """The mean, over the positions ``counted``, of the cross entropy of
    ``y head / scaling`` against ``targets``: ``y`` (B, L, d) in the
    model's dtype, ``head`` (d, V) a parameter as it is held. ``rows``
    positions at a time, each block rematerialised, so that of the
    (B L, V) logits one block is alive at a time, in the forward pass
    and in the backward (a length that ``rows`` does not divide, the
    tests', goes whole). The head is cast inside the block: its
    gradient adds up over the blocks in the parameter's own float32.
    The logits and the softmax are float32."""
    d = y.shape[-1]
    block = rows if (y.shape[0] * y.shape[1]) % rows == 0 else y.shape[0] * y.shape[1]

    @jax.checkpoint
    def summed(y, targets, counted):
        logits = jnp.dot(y, head.astype(y.dtype)).astype(jnp.float32)
        if scaling != 1.0:
            logits = logits / scaling
        each = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
        return jnp.sum(jnp.where(counted, each, 0.0))

    sums = jax.lax.map(
        lambda block_of: summed(*block_of),
        (y.reshape(-1, block, d), targets.reshape(-1, block), counted.reshape(-1, block)),
    )
    return jnp.sum(sums) / jnp.sum(counted)


def _causal_depthwise_conv(u, taps):
    """``v_t = sum_j taps[j] * u_{t-j}`` a channel, ``u_{<0} = 0``."""
    length, last = u.shape[1], taps.shape[0] - 1
    padded = jnp.pad(u, ((0, 0), (last, 0), (0, 0)))
    # tap j multiplies u_{t-j}: the padded sequence from K-1-j on
    return sum(
        taps[j] * padded[:, last - j : last - j + length]
        for j in range(taps.shape[0])
    )


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
        return _kept(
            nn.Dense(d, use_bias=False, dtype=self.dtype, name="out_proj")(
                c * _causal_depthwise_conv(u, taps)
            )
        )


def _dt_bias_init(key, shape, low=1e-3, high=1e-1, floor=1e-4):
    """The inverse softplus of a log-uniform draw in [low, high]."""
    dt = jnp.exp(
        jax.random.uniform(key, shape) * (math.log(high) - math.log(low))
        + math.log(low)
    )
    dt = jnp.maximum(dt, floor)
    return dt + jnp.log(-jnp.expm1(-dt))


class Mamba2(nn.Module):
    """The Mamba-2 state-space operator."""

    heads: int
    head_dim: int
    state: int
    groups: int
    conv_kernel: int
    chunk: int
    norm_eps: float
    dtype: Any

    @nn.compact
    def __call__(self, h):
        inner = self.heads * self.head_dim
        shared = self.groups * self.state
        projected = nn.Dense(
            2 * inner + 2 * shared + self.heads,
            use_bias=False,
            dtype=self.dtype,
            name="in_proj",
        )(h)
        z, xbc, dt = jnp.split(
            projected, (inner, 2 * inner + 2 * shared), axis=-1
        )
        taps = self.param(
            "conv_kernel",
            nn.initializers.normal(self.conv_kernel**-0.5),
            (self.conv_kernel, inner + 2 * shared),
        ).astype(self.dtype)
        conv_bias = self.param(
            "conv_bias", nn.initializers.zeros, (inner + 2 * shared,)
        ).astype(self.dtype)
        xbc = nn.silu(_causal_depthwise_conv(xbc, taps) + conv_bias)
        x, b, c = jnp.split(xbc, (inner, inner + shared), axis=-1)
        per_head = (self.heads,)
        dt_bias = self.param("dt_bias", _dt_bias_init, per_head)
        a_log = self.param(
            "A_log",
            lambda key, shape: jnp.log(jnp.arange(1.0, shape[0] + 1.0)),
            per_head,
        )
        skip = self.param("D", nn.initializers.ones, per_head)
        x = x.reshape(x.shape[:2] + (self.heads, self.head_dim))
        if self.is_initializing():
            # the variables are made, and no shape depends on the
            # state: an eager init (the trainer's) is spared the scan
            y = x
        else:
            by_group = b.shape[:2] + (self.groups, self.state)
            y = ssd.ssd_scan(
                x,
                jax.nn.softplus(dt.astype(jnp.float32) + dt_bias),
                -jnp.exp(a_log),
                b.reshape(by_group),
                c.reshape(by_group),
                self.chunk,
            )
        y = y + skip.astype(self.dtype)[:, None] * x
        gated = y.reshape(z.shape) * nn.silu(z)
        normed = nn.RMSNorm(
            epsilon=self.norm_eps, dtype=self.dtype, name="norm"
        )(gated)
        return _kept(
            nn.Dense(
                h.shape[-1], use_bias=False, dtype=self.dtype, name="out_proj"
            )(normed)
        )


@jax.custom_vjp
def _read_together(x, *others):
    """``(x, *others)`` as they are. In the backward pass ``x``'s
    gradient READS the others': zero times their sums is added to it (a
    float's product with zero is not folded away), so it cannot be
    handed on before they exist. ``others`` are parameters. Left to
    itself the compiler schedules a parameter's gradient last, behind
    whatever reads it (an optimizer's update, a norm), and keeps what it
    is computed from until then: for a projection's kernel the product's
    two operands, for a parameter a channel or a head (a convolution's
    taps, ``A_log``, ``dt_bias``) the arrays its reduction runs over;
    the cotangents of a KDA layer's five projections and its gates, 0.8
    GB a layer at 2 x 4,096 positions, of all six layers at once
    (compiled for a described v5e, PERF.md section 6, PR 42). What it
    buys is the comparison of this model with its reference at the
    published widths, which holds the reference's pass beside this one:
    14.27 GiB at the peak with it, 17.85 of the chip's 15.75 without
    (``tests/test_ling_linear_lm.py``, the slow case). The training
    step alone does not need it (13.96 GB with it, 13.81 without). A
    latent attention's products read their gradients together too
    (``_prompt_dot_general``, PR 46): six such layers at 2 x 8,192
    positions and their reference compile to 14.44 GiB with it and are
    refused without (16.03 GB of 15.75 GiB, with every other saving in
    place; ``tests/test_glm_mla_mtp_lm.py``, the slow case). An
    ``optimization_barrier`` over the pair does not help: it binds the
    optimizer's passes, not the scheduler."""
    return (x,) + others


def _read_together_bwd(_, gradients):
    d_x, *d_others = gradients
    read = 0.0 * sum(jnp.sum(g, dtype=jnp.float32) for g in d_others)
    return (d_x + read.astype(d_x.dtype),) + tuple(d_others)


_read_together.defvjp(
    lambda x, *others: ((x,) + others, None), _read_together_bwd
)


def _prompt_dot_general(lhs, rhs, dimension_numbers, precision=None, **more):
    """``lax.dot_general`` for a flax ``Dense`` or ``DenseGeneral``
    (their ``dot_general``) whose input's gradient waits for the
    kernel's (:func:`_read_together`)."""
    lhs, rhs = _read_together(lhs, rhs)
    return jax.lax.dot_general(
        lhs, rhs, dimension_numbers, precision=precision, **more
    )


class PromptDense(nn.Module):
    """``nn.Dense`` without a bias (the same ``kernel``, the same
    initial value) whose input's gradient waits for the kernel's
    (:func:`_read_together`)."""

    features: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (x.shape[-1], self.features),
        )
        return jnp.dot(
            *_read_together(x.astype(self.dtype), kernel.astype(self.dtype))
        )


class KimiDeltaAttention(nn.Module):
    """The Kimi-Delta-Attention operator: a gated delta rule whose
    decay is a vector over the key's channels."""

    heads: int
    head_dim: int
    conv_kernel: int
    gate_lower_bound: float
    chunk: int
    norm_eps: float
    dtype: Any

    @nn.compact
    def __call__(self, h):
        inner = self.heads * self.head_dim
        by_head = h.shape[:2] + (self.heads, self.head_dim)

        def dense(features, name):
            return PromptDense(features, self.dtype, name=name)(h)

        def mixed(name):
            taps = self.param(
                name + "_conv",
                nn.initializers.normal(self.conv_kernel**-0.5),
                (self.conv_kernel, inner),
            ).astype(self.dtype)
            projected, taps = _read_together(dense(inner, name), taps)
            return nn.silu(_causal_depthwise_conv(projected, taps)).reshape(
                by_head
            )

        def unit(x):
            x32 = x.astype(jnp.float32)
            return (
                x32
                * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + 1e-6)
            ).astype(x.dtype)

        q, k, v = unit(mixed("query")), unit(mixed("key")), mixed("value")
        a_log = self.param(
            "A_log",
            lambda key, shape: jnp.log(
                jax.random.uniform(key, shape, minval=1.0, maxval=16.0)
            ),
            (self.heads,),
        )
        dt_bias = self.param("dt_bias", _dt_bias_init, (inner,))
        raw, a_log, dt_bias = _read_together(
            dense(inner, "decay"), a_log, dt_bias
        )
        # the log-decay a channel, between the bound and 0, float32
        log_decay = self.gate_lower_bound * jax.nn.sigmoid(
            jnp.exp(a_log)[:, None]
            * (raw.astype(jnp.float32) + dt_bias).reshape(by_head)
        )
        beta = jax.nn.sigmoid(dense(self.heads, "beta").astype(jnp.float32))
        if self.is_initializing():
            # the variables are made, and no shape depends on the
            # state: an eager init (the trainer's) is spared the
            # recurrence's loops
            o = v
        else:
            o = kda.kda(q, k, v, log_decay, beta, self.chunk)
        normed = nn.RMSNorm(
            epsilon=self.norm_eps, dtype=self.dtype, name="norm"
        )(o)
        gate = nn.sigmoid(dense(self.heads, "gate"))[..., None]
        return _kept(
            PromptDense(h.shape[-1], self.dtype, name="out")(
                (gate * normed).reshape(h.shape[:2] + (inner,))
            )
        )


class LatentAttention(nn.Module):
    """Latent attention (MLA) in its expanded form: keys and values
    come up out of one normed low-rank latent, the rotated part of the
    key is one head that every query head reads, q and k are as wide
    as v or wider, and with ``q_rank`` the query comes up out of a
    normed latent of its own. Every product's input hands its gradient
    on after the kernel's (``_prompt_dot_general``)."""

    num_heads: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float
    norm_eps: float
    dtype: Any
    use_flash: bool
    q_rank: int = 0

    @nn.compact
    def __call__(self, h, positions):
        def heads(width, name, x):
            return _kept(
                nn.DenseGeneral(
                    features=(self.num_heads, width),
                    use_bias=False,
                    dtype=self.dtype,
                    dot_general=_prompt_dot_general,
                    name=name,
                )(x)
            )

        def down_to(width, name):
            return _kept(
                nn.Dense(
                    width, use_bias=False, dtype=self.dtype,
                    dot_general=_prompt_dot_general, name=name,
                )(h)
            )

        def normed(name, x):
            return nn.RMSNorm(
                epsilon=self.norm_eps, dtype=self.dtype, name=name
            )(x)

        q_from = h
        if self.q_rank:
            # the query through a latent of its own
            q_from = normed("q_norm", down_to(self.q_rank, "q_down"))
        q = heads(self.nope_dim + self.rope_dim, "query", q_from)
        down = down_to(self.kv_rank + self.rope_dim, "kv_down")
        latent = normed("kv_norm", down[..., : self.kv_rank])
        up = heads(self.nope_dim + self.v_dim, "kv_up", latent)
        k_nope, v = up[..., : self.nope_dim], up[..., self.nope_dim :]
        rotated = lambda x: _lm._rotary(x, positions, self.rope_theta)
        k_rope = rotated(down[..., None, self.kv_rank :])
        q = jnp.concatenate(
            [q[..., : self.nope_dim], rotated(q[..., self.nope_dim :])], axis=-1
        )
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:3] + k_rope.shape[3:])],
            axis=-1,
        )
        attn = pick_causal_attention(h.shape[1], self.use_flash)(q, k, v)
        return _kept(
            nn.DenseGeneral(
                features=h.shape[-1],
                axis=(-2, -1),
                use_bias=False,
                dtype=self.dtype,
                dot_general=_prompt_dot_general,
                name="out",
            )(attn)
        )


class Indexer(nn.Module):
    """Which keys each query reads: (B, L, L) int8, float32 inside."""

    heads: int
    head_dim: int
    topk: int

    @nn.compact
    def __call__(self, h):
        h = jax.lax.stop_gradient(h).astype(jnp.float32)

        def project(features, name):
            return nn.DenseGeneral(
                features=features,
                use_bias=False,
                dtype=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
                name=name,
            )(h)

        operands = (
            project((self.heads, self.head_dim), "query"),
            project(self.head_dim, "key"),
            project(self.heads, "weights"),
        )
        if self.is_initializing():
            # the variables are made, and no shape depends on what is
            # selected: an eager init (the trainer's) is spared the
            # compile of every layer's loops
            return None
        return sparse_select.select_keys(*operands, self.topk)


class GroupedAttention(nn.Module):
    """The attention operator over grouped KV heads: over every earlier
    key, or, with ``select_topk``, over those its indexer selects, or,
    with ``window``, over the ``window`` nearest (its own among them)."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    norm_eps: float
    dtype: Any
    use_flash: bool
    select_topk: int = 0
    indexer_heads: int = 0
    indexer_dim: int = 0
    rope: bool = True
    qk_norm: bool = True
    attention_scale: float = 0.0
    window: int = 0

    def _selection(self, h):
        """The indexer's selection, counted into the module's state
        where a training step holds it mutable (the counters wrap;
        readers take differences)."""
        selection = Indexer(
            self.indexer_heads, self.indexer_dim, self.select_topk,
            name=INDEXER,
        )(h)  # fmt: skip
        collection = expert.MOE_STATE_COLLECTION
        if self.is_initializing() or self.is_mutable_collection(collection):
            zero = lambda: jnp.zeros((), jnp.int32)
            kept = self.variable(collection, "sel_pairs_kept", zero)
            causal = self.variable(collection, "sel_pairs_causal", zero)
            if selection is not None:
                b, l = h.shape[:2]
                kept.value = kept.value + jnp.sum(selection, dtype=jnp.int32)
                causal.value = causal.value + b * l * (l + 1) // 2
        return selection

    @nn.compact
    def __call__(self, h, positions):
        def heads(n, name):
            return _kept(
                nn.DenseGeneral(
                    features=(n, self.head_dim),
                    axis=-1,
                    use_bias=False,
                    dtype=self.dtype,
                    name=name,
                )(h)
            )

        def head_norm(name):
            return nn.RMSNorm(
                epsilon=self.norm_eps, dtype=self.dtype, name=name
            )

        q, k = heads(self.num_heads, "query"), heads(self.num_kv_heads, "key")
        v = heads(self.num_kv_heads, "value")
        if self.qk_norm:
            q, k = head_norm("q_norm")(q), head_norm("k_norm")(k)
        if self.rope:
            q = _lm._rotary(q, positions, self.rope_theta)
            k = _lm._rotary(k, positions, self.rope_theta)
        if self.attention_scale:
            # the attention of ops/flash_attention.py scales by
            # head_dim ** -0.5: q carries what is left of the scale
            q = q * jnp.asarray(
                self.attention_scale * self.head_dim**0.5, q.dtype
            )
        group = self.num_heads // self.num_kv_heads
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        if self.select_topk:
            selection = self._selection(h)
            attn = (
                q  # initialising: the output's shape, nothing attended
                if selection is None
                else pick_selected_attention(h.shape[1], self.use_flash)(
                    q, k, v, selection
                )
            )
        else:
            attn = pick_causal_attention(
                h.shape[1], self.use_flash, window=self.window or None
            )(q, k, v)
        return _kept(
            nn.DenseGeneral(
                features=h.shape[-1],
                axis=(-2, -1),
                use_bias=False,
                dtype=self.dtype,
                name="out",
            )(attn)
        )


class SwiGLU(nn.Module):
    width: int
    dtype: Any

    @nn.compact
    def __call__(self, h):
        def dense(features, name):
            return nn.Dense(
                features, use_bias=False, dtype=self.dtype, name=name
            )

        gate = nn.silu(_kept(dense(self.width, "w1")(h))) * _kept(
            dense(self.width, "w3")(h)
        )
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
    routing: str = ROUTINGS[0]
    apply: str = EXPERT_APPLIES[0]
    act: str = expert.EXPERT_ACTS[0]
    shared_expert_dim: int = 0
    num_expert_groups: int = 1
    expert_groups_per_tok: int = 1

    @nn.compact
    def __call__(self, h, router_input=None):
        """``h`` is what the experts read; the router reads
        ``router_input`` where one is given, else ``h`` too."""
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
        routed = tokens if router_input is None else router_input.reshape(-1, d)
        logits = jnp.dot(
            routed.astype(jnp.float32),
            router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        collection = expert.MOE_STATE_COLLECTION
        has_state = self.is_initializing() or self.has_variable(
            collection, "assignments"
        )
        if has_state:
            made = self.variable(
                collection,
                "assignments",
                lambda: jnp.zeros((self.num_experts,), jnp.int32),
            )
        biased = self.routing == ROUTINGS[0]
        if biased:
            bias = jnp.zeros((self.num_experts,), jnp.float32)
            if has_state:
                bias_state = self.variable(
                    collection, "expert_bias", lambda: bias
                )
                bias = bias_state.value
            selected, gates = expert.sigmoid_topk_route(
                logits,
                bias,
                self.num_experts_per_tok,
                self.routed_scaling_factor,
                self.num_expert_groups,
                self.expert_groups_per_tok,
            )
        else:
            selected, gates = expert.softmax_topk_route(
                logits, self.num_experts_per_tok, self.routed_scaling_factor
            )
        if (
            has_state
            and not self.is_initializing()
            and self.is_mutable_collection(collection)
        ):
            # after the step, outside the gradient: this step selected
            # with the bias as it was
            counts = expert.expert_assignments(selected, self.num_experts)
            if biased:
                bias_state.value = expert.expert_bias_update(
                    bias, counts, self.expert_bias_rate
                )
            made.value = made.value + counts
        apply = (
            expert.held_experts_apply
            if self.apply == EXPERT_APPLIES[0]
            else expert.held_experts_apply_masked
        )
        out = apply(
            tokens,
            selected,
            gates,
            w_in.astype(self.dtype),
            w_out.astype(self.dtype),
            self.first_expert_held,
            act=self.act,
        )
        if self.shared_expert_dim:
            out = out + expert.shared_expert_apply(
                tokens,
                self.param(
                    "shared_w13",
                    nn.initializers.lecun_normal(),
                    (d, 2 * self.shared_expert_dim),
                ).astype(self.dtype),
                self.param(
                    "shared_w2",
                    nn.initializers.lecun_normal(),
                    (self.shared_expert_dim, d),
                ).astype(self.dtype),
                act=self.act,
            )
        return out.reshape(h.shape)


def _tokens_of(features):
    return features["tokens"] if isinstance(features, dict) else features


def _check(admits, text):
    """One check of a size: ``admits(value, model)``, and what is said
    of a value it does not admit (``name``, ``value`` and ``model``
    filled in). Checks add up with ``+``: the first to fail speaks."""
    return ((admits, text),)


_WHOLE = _check(
    lambda v, m: isinstance(v, int) and v > 0,
    "{name}={value!r} where its sizes all have to be positive whole numbers",
)
_SWITCH = _check(
    lambda v, m: isinstance(v, bool), "{name}={value!r} is neither True nor False"
)
_POSITIVE = _check(
    lambda v, m: v > 0 and math.isfinite(v),
    "{name}={value!r} is not a positive number",
)
_EVEN = _check(lambda v, m: v % 2 == 0, "{name}={value!r} is odd")
_DIVIDES_SSM_HEADS = _check(
    lambda v, m: m.ssm_heads % v == 0,
    "ssm_heads={model.ssm_heads!r} is not a multiple of {name}={value!r}",
)
_LOG_DECAY = _check(
    # the exponents of a sub-block of the recurrence (ops/kda.py)
    lambda v, m: v < 0 and math.isfinite(v) and -v * kda.SUB_BLOCK / 2 <= 80,
    "{name}={value!r}: a log-decay a position, under 0 and no lower than %g"
    % (-160 / kda.SUB_BLOCK),
)
_SCALE = _check(
    lambda v, m: v >= 0 and math.isfinite(v),
    "{name}={value!r}: a positive number, or 0 for head_dim ** -0.5",
)
_WIDTH_OR_NONE = _check(
    lambda v, m: isinstance(v, int) and v >= 0,
    "{name}={value!r}: a width, or 0 for none",
)
_EQUAL_GROUPS = _check(
    lambda v, m: isinstance(v, int)
    and isinstance(m.expert_groups_per_tok, int)
    and 1 <= m.expert_groups_per_tok <= v
    and m.num_experts % v == 0,
    "{name}={value!r} and expert_groups_per_tok={model.expert_groups_per_tok!r}: "
    "num_experts={model.num_experts!r} in groups of equal size, of which "
    "between one and all stay",
)
_NONE_OR_ONE = _check(
    lambda v, m: v in (0, 1) and not isinstance(v, bool),
    "{name}={value!r}: 0 for no prediction module, or 1 (modules chained "
    "one behind the other are not built)",
)
_WEIGHS_A_MODULE = _check(
    lambda v, m: v >= 0 and math.isfinite(v),
    "{name}={value!r} is not a weight: a number, 0 or more",
) + _check(
    lambda v, m: m.mtp_layers or v == HybridMoELM.mtp_loss_weight,
    "{name}={value!r} says nothing: mtp_layers=0, no prediction module",
)
_INSIDE_PATTERN = _check(
    lambda v, m: 0 <= v <= len(m.layer_pattern),
    "num_dense_layers outside the pattern",
)
_SERVES_KV_HEADS = _check(
    lambda v, m: v % m.num_kv_heads == 0,
    "num_heads is not a multiple of num_kv_heads",
)
_AMONG_EXPERTS = _check(
    lambda v, m: 0 < v <= m.num_experts,
    "{name}={value!r} is not between 1 and num_experts={model.num_experts!r}",
)
_HELD_AMONG_EXPERTS = _check(
    lambda v, m: 0 < v and 0 <= m.first_expert_held <= m.num_experts - v,
    "the experts held are not among num_experts",
)


def _one_of(choices):
    return _check(
        lambda v, m: v in choices,
        "{name} {value!r} is not one of " + ", ".join(choices),
    )


def _within_the_groups(kept, model):
    groups = model.num_expert_groups
    return groups == 1 or (
        model.routing == ROUTINGS[0]
        and model.num_experts // groups >= 2
        and model.num_experts_per_tok <= kept * (model.num_experts // groups)
    )


_GROUP_LIMIT = _check(
    _within_the_groups,
    "a group limit ({model.num_expert_groups} groups, {value} kept) is "
    + ROUTINGS[0]
    + " routing's, over groups of two experts or more that hold "
    "num_experts_per_tok={model.num_experts_per_tok!r} between them",
)


class Size(NamedTuple):
    """A keyword of ``custom_model`` that one kind of layer owns."""

    name: str
    check: tuple = ()
    # the operator's keyword: "" the same name, None not the operator's
    to: Optional[str] = ""

    def wrong(self, model):
        """What is wrong with ``model``'s value, or None."""
        value = getattr(model, self.name)
        for admits, text in self.check:
            if not admits(value, model):
                return text.format(name=self.name, value=value, model=model)


def _where(layers, **facts):
    return facts if layers else {}


def _stated(counted, *names):
    """``facts`` of a kind that states, where a layer of it is in the
    pattern, how many are and some of its sizes as they are."""
    return lambda model, layers, features: _where(
        layers, **{counted: layers}, **{n: getattr(model, n) for n in names}
    )


@dataclasses.dataclass(frozen=True)
class Kind:
    """All the model knows of one kind of layer: ``HybridMoELM`` builds
    it, ``step_facts`` states it and ``custom_model`` checks it from
    this record alone. A kind is added in four steps: (1) its sizes as
    fields of ``HybridMoELM``, each with a default that means "none";
    (2) its operator, an ``nn.Module`` over ``h`` (and the positions,
    with ``call=_with_positions``) that names its weight products
    ``_kept``; (3) one record in ``KINDS`` under its letter; (4) its
    paragraph in the module's docstring."""

    operator: type
    name: str  # of its variables, behind ``layer_<i>_``
    # its own: each passes its check where a layer of the kind is in the
    # pattern, and says nothing (has to be the default) where none is,
    # unless another kind that is there owns it too
    sizes: tuple = ()
    # the shared sizes the operator is handed under their own names
    reads: tuple = ()
    says: str = ""  # of the letter, where a pattern is refused
    noun: str = ""  # "holds a ..." and "holds no ...", where not ``says``
    # operator, h, what else the model has (an operator: the positions;
    # a feed-forward half: the operator's normed input) -> the result
    call: Callable = lambda model, operator, h, other: operator(h)
    scope: str = ""  # the named scope the model wraps it in
    # how many of its results are named ``KEPT``: a number, or a
    # function of the model where that follows from a size
    kept: Any = 1
    keeps_recurrence: bool = False  # ``kda.KEPT_NAMES`` beside them
    # (model, how many such layers, a batch or None) -> its step_facts
    facts: Callable = lambda model, layers, features: {}

    def kept_by(self, model):
        return self.kept(model) if callable(self.kept) else self.kept

    def held(self, layers):
        if not self.noun:
            return self.says if layers else "no " + self.says
        return ("a " if layers else "no ") + self.noun

    def apply(self, model, name, h, other):
        given = {shared: getattr(model, shared) for shared in self.reads}
        given.update(
            (size.to or size.name, getattr(model, size.name))
            for size in self.sizes
            if size.to is not None
        )
        operator = self.operator(**given, name=name + self.name)
        with jax.named_scope(self.scope) if self.scope else nullcontext():
            return self.call(model, operator, h, other)


def _with_positions(model, operator, h, positions):
    return operator(h, positions)


def _window_facts(model, layers, features):
    facts = _stated("window_layers", "attention_window")(model, layers, features)
    if layers and features is not None:
        # a sequence's (query, key) pairs in ONE window layer (not
        # times the heads), and the causal pairs
        kept, causal = window_pairs(
            _tokens_of(features).shape[1], model.attention_window
        )
        facts.update(window_pairs_kept=kept, window_pairs_causal=causal)
    return facts


def _expert_facts(model, layers, features):
    # the share is stated with no expert layer too: the worker reads it
    facts = {
        "expert_layers": layers,
        "experts_held": model.experts_held,
        "experts_routed": model.num_experts,
        "first_expert_held": model.first_expert_held,
        "routing": model.routing,
        "expert_apply": model.expert_apply,
    }
    if not layers:
        return facts
    if model.expert_apply == EXPERT_APPLIES[0]:
        facts["moe_dispatch_chunk_rows"] = expert.DISPATCH_CHUNK_ROWS
    for name in ("router_input", "expert_act", "shared_expert_dim"):
        if getattr(model, name) != getattr(HybridMoELM, name):
            facts[name] = getattr(model, name)
    if model.num_expert_groups > 1:
        facts.update(
            expert_groups=model.num_expert_groups,
            expert_groups_per_tok=model.expert_groups_per_tok,
        )
    return facts


# one module serves the three kinds of grouped-head attention
_ATTENDS = dict(
    operator=GroupedAttention,
    name="attention",
    reads=(
        "num_heads", "num_kv_heads", "head_dim", "rope_theta", "norm_eps",
        "dtype", "use_flash",
    ),
    call=_with_positions,
    kept=4,
)  # fmt: skip
_SCALED = (Size("qk_norm", _SWITCH), Size("attention_scale", _SCALE))
KINDS = {
    "c": Kind(
        ShortConv,
        "conv",
        says="a short convolution",
        sizes=(Size("conv_kernel", _WHOLE, "kernel_size"),),
        reads=("dtype",),
        scope="edl/short_conv",
        # stated at none too, as ``attention_layers`` is
        facts=lambda model, layers, features: {"conv_layers": layers},
    ),
    "m": Kind(
        Mamba2,
        "mamba",
        says="a Mamba-2 state-space layer",
        noun="state-space layer",
        sizes=(
            Size("ssm_heads", _WHOLE, "heads"),
            Size("ssm_head_dim", _WHOLE, "head_dim"),
            Size("ssm_state", _WHOLE, "state"),
            Size("ssm_groups", _WHOLE + _DIVIDES_SSM_HEADS, "groups"),
            Size("ssm_conv_kernel", _WHOLE, "conv_kernel"),
            Size("ssm_chunk", _WHOLE, "chunk"),
        ),
        reads=("norm_eps", "dtype"),
        facts=_stated(
            "mamba_layers", "ssm_heads", "ssm_head_dim", "ssm_state", "ssm_chunk"
        ),
    ),
    "a": Kind(
        says="full attention",
        noun="attention layer",
        sizes=(Size("rope", _SWITCH),) + _SCALED,
        facts=lambda model, layers, features: {"attention_layers": layers},
        **_ATTENDS,
    ),
    "s": Kind(
        says="attention over selected keys",
        sizes=(
            Size("rope", _SWITCH),
            Size("select_topk", _WHOLE),
            Size("indexer_heads", _WHOLE),
            Size("indexer_dim", _WHOLE),
        )
        + _SCALED,
        facts=_stated("sparse_layers", "select_topk", "indexer_heads"),
        **_ATTENDS,
    ),
    "w": Kind(
        says="attention within a window",
        noun="window layer",
        sizes=(
            Size("attention_window", _WHOLE, "window"),
            Size("window_rope", _SWITCH, "rope"),
        )
        + _SCALED,
        scope="edl/window_attention",
        facts=_window_facts,
        **_ATTENDS,
    ),
    "k": Kind(
        KimiDeltaAttention,
        "kda",
        says="a Kimi-Delta-Attention layer",
        sizes=(
            Size("kda_heads", _WHOLE, "heads"),
            Size("kda_head_dim", _WHOLE, "head_dim"),
            Size("kda_conv_kernel", _WHOLE, "conv_kernel"),
            Size("kda_gate_lower_bound", _LOG_DECAY, "gate_lower_bound"),
            Size("kda_chunk", _WHOLE, "chunk"),
        ),
        reads=("norm_eps", "dtype"),
        # beside ``out``'s product it keeps what ops/kda.py names: its
        # recurrence is not run again
        keeps_recurrence=True,
        facts=_stated("kda_layers", "kda_heads", "kda_head_dim", "kda_chunk"),
    ),
    "l": Kind(
        LatentAttention,
        "mla",
        says="latent attention",
        sizes=(
            Size("mla_kv_rank", _WHOLE, "kv_rank"),
            Size("mla_nope_dim", _WHOLE, "nope_dim"),
            Size("mla_rope_dim", _WHOLE + _EVEN, "rope_dim"),
            Size("mla_v_dim", _WHOLE, "v_dim"),
            Size("mla_q_rank", _WIDTH_OR_NONE, "q_rank"),
        ),
        reads=("num_heads", "rope_theta", "norm_eps", "dtype", "use_flash"),
        call=_with_positions,
        scope="edl/mla",
        # the low-rank query's down projection beside the four
        kept=lambda model: 5 if model.mla_q_rank else 4,
        facts=lambda model, layers, features: _where(
            layers,
            mla_layers=layers,
            mla_qk_dim=model.mla_nope_dim + model.mla_rope_dim,
            mla_v_dim=model.mla_v_dim,
            **_where(model.mla_q_rank, mla_q_rank=model.mla_q_rank),
        ),
    ),
}
LETTERS = {letter: kind.says for letter, kind in KINDS.items()}
# the feed-forward half of a layer: of the first ``num_dense_layers``
# the dense MLP, of the others this device's share of the experts
DENSE_FF = Kind(
    SwiGLU,
    "mlp",
    noun="dense MLP",
    sizes=(Size("mlp_dim", _WHOLE, "width"),),
    reads=("dtype",),
    kept=2,
)
EXPERT_FF = Kind(
    HeldExperts,
    "moe",
    noun="expert layer",
    sizes=(
        Size("router_input", _one_of(ROUTER_INPUTS), None),
        Size("expert_act", _one_of(expert.EXPERT_ACTS), "act"),
        Size("expert_apply", _one_of(EXPERT_APPLIES), "apply"),
        Size("shared_expert_dim", _WIDTH_OR_NONE),
        Size("num_expert_groups", _EQUAL_GROUPS),
        Size("expert_groups_per_tok", _GROUP_LIMIT),
        Size("expert_bias_rate"),
    ),
    # the tests of a stack with no expert layer give these, so they are
    # shared: none is refused as saying nothing (their checks: SHARED)
    reads=(
        "routing", "routed_scaling_factor", "num_experts", "experts_held",
        "first_expert_held", "num_experts_per_tok", "expert_dim", "dtype",
    ),
    # the router reads the operator's normed input where so placed
    call=lambda model, operator, h, operator_h: operator(
        h, operator_h if model.router_input == ROUTER_INPUTS[1] else None
    ),
    scope="edl/moe",
    kept=0,
    facts=_expert_facts,
)  # fmt: skip
# what no kind owns and has a check, whatever the pattern holds
SHARED = (
    Size("num_dense_layers", _INSIDE_PATTERN),
    Size("remat_layers", _SWITCH),
    Size("embedding_multiplier", _POSITIVE),
    Size("residual_multiplier", _POSITIVE),
    Size("logits_scaling", _POSITIVE),
    Size("num_heads", _SERVES_KV_HEADS),
    Size("routing", _one_of(ROUTINGS)),
    Size("num_experts_per_tok", _AMONG_EXPERTS),
    Size("experts_held", _HELD_AMONG_EXPERTS),
    Size("mtp_layers", _NONE_OR_ONE),
    Size("mtp_loss_weight", _WEIGHS_A_MODULE),
)


def _layers(model):
    """(letter, record, how many layers of ``model`` are of it) of
    every record; the feed-forward halves have no letter. A prediction
    module's layer is one more of the pattern's last."""
    pattern = model.layer_pattern + model.layer_pattern[-1:] * model.mtp_layers
    dense = model.num_dense_layers
    if dense == len(model.layer_pattern):  # the last layer's half is dense
        dense += model.mtp_layers
    return [
        (letter, kind, pattern.count(letter)) for letter, kind in KINDS.items()
    ] + [("", DENSE_FF, dense), ("", EXPERT_FF, len(pattern) - dense)]


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
    routing: str = ROUTINGS[0]
    select_topk: int = 0
    indexer_heads: int = 4
    indexer_dim: int = 16
    tie_head: bool = True
    expert_apply: str = EXPERT_APPLIES[0]
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 0
    ssm_conv_kernel: int = 0
    ssm_chunk: int = 0
    rope: bool = True
    qk_norm: bool = True
    attention_scale: float = 0.0
    attention_window: int = 0
    window_rope: bool = True
    router_input: str = ROUTER_INPUTS[0]
    expert_act: str = expert.EXPERT_ACTS[0]
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_kernel: int = 0
    kda_gate_lower_bound: float = 0.0
    kda_chunk: int = 0
    mla_kv_rank: int = 0
    mla_nope_dim: int = 0
    mla_rope_dim: int = 0
    mla_v_dim: int = 0
    shared_expert_dim: int = 0
    num_expert_groups: int = 1
    expert_groups_per_tok: int = 1
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    remat_layers: bool = False
    mla_q_rank: int = 0
    mtp_layers: int = 0
    mtp_loss_weight: float = 0.3
    dtype: Any = jnp.float32
    use_flash: bool = True

    def step_facts(self, features=None):
        """What the worker's ``step_built`` event says of this model's
        layout (scalars), and what its window counters are read with.
        ``features``, where given, is a batch the step was built for:
        what follows from its sequence length is stated too."""
        facts = {"tie_head": int(self.tie_head)}
        if self.mtp_layers:
            facts.update(
                mtp_layers=self.mtp_layers, mtp_loss_weight=self.mtp_loss_weight
            )
        held = _layers(self)
        for _, record, layers in held:
            facts.update(record.facts(self, layers, features))
        if self.remat_layers:
            facts["remat_layers"] = 1
            facts["remat_kept_products"] = sum(
                record.kept_by(self) * layers for _, record, layers in held
            )
            recurrences = sum(
                layers for _, record, layers in held if record.keeps_recurrence
            )
            if recurrences:
                facts["remat_kept_recurrences"] = recurrences
        return facts

    @nn.compact
    def __call__(self, features, training=False):
        tokens = _tokens_of(features).astype(jnp.int32)
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
        if self.embedding_multiplier != 1.0:
            x = x * jnp.asarray(self.embedding_multiplier, x.dtype)

        def joined(x, out):
            if self.residual_multiplier != 1.0:
                out = out * jnp.asarray(self.residual_multiplier, out.dtype)
            return x + out

        def built(self, x, i, name):
            """A layer of the kind of the pattern's ``i``-th, its
            variables under this module as ``name`` and the part's
            own."""
            h = norm(name + "operator_norm")(x)
            operator = KINDS[self.layer_pattern[i]]
            x = joined(x, operator.apply(self, name, h, positions))
            operator_h, h = h, norm(name + "ffn_norm")(x)
            ff = DENSE_FF if i < self.num_dense_layers else EXPERT_FF
            return joined(x, ff.apply(self, name, h, operator_h))

        def layer(self, x, i):
            return built(self, x, i, "layer_%d_" % i)

        def predicted(self, x, after, head):
            """The prediction module's loss (the module's docstring has
            the equations): ``x`` the trunk's output in front of its
            final norm, ``after`` the embedding of the token behind
            each position, ``head`` (embed_dim, vocab_size). All ``L``
            positions run, so that the kernels tile; the last one reads
            a token that does not exist and nothing reads its result."""
            name = "mtp_0_"
            u = nn.Dense(
                self.embed_dim, use_bias=False, dtype=self.dtype,
                name=name + "proj",
            )(
                jnp.concatenate(
                    [norm(name + "hidden_norm")(x), norm(name + "embed_norm")(after)],
                    axis=-1,
                )
            )  # fmt: skip
            y = built(self, u, len(self.layer_pattern) - 1, name)
            y = norm(name + "final_norm")(y).astype(self.dtype)
            # the last two positions have no token two places on
            counted = positions < l - 2
            return _mean_cross_entropy(
                y, head, self.logits_scaling, jnp.roll(tokens, -2, axis=1), counted
            )

        if self.remat_layers and not self.is_initializing():
            keeps = dict(
                policy=jax.checkpoint_policies.save_only_these_names(
                    KEPT, *kda.KEPT_NAMES
                )
            )
            # static_argnums counts the module: the layer's index
            layer = nn.remat(layer, static_argnums=(2,), **keeps)
            predicted = nn.remat(predicted, **keeps)
        for i in range(len(self.layer_pattern)):
            x = layer(self, x, i)
        trunk, x = x, norm("final_norm")(x)
        # the head over the slice of the vocabulary held here
        if self.tie_head:
            logits = embed_layer.attend(x.astype(jnp.float32))
        else:
            head = nn.Dense(
                self.vocab_size, use_bias=False, dtype=self.dtype, name="head"
            )
            logits = head(x)
        if self.logits_scaling != 1.0:
            logits = logits / jnp.asarray(self.logits_scaling, logits.dtype)
        if not self.mtp_layers or not (training or self.is_initializing()):
            return logits
        with jax.named_scope("edl/mtp"):
            after = embed_layer(jnp.roll(tokens, -1, axis=1))
            if self.embedding_multiplier != 1.0:
                after = after * jnp.asarray(self.embedding_multiplier, after.dtype)
            weighed = self.mtp_loss_weight * predicted(
                self,
                trunk,
                after,
                embed_layer.embedding.T
                if self.tie_head
                else head.variables["params"]["kernel"],
            )
        if self.is_mutable_collection(AUX_LOSS_COLLECTION):
            # a training step holds it: every step builder adds the
            # collection to the loss it differentiates
            held = self.variable(
                AUX_LOSS_COLLECTION, "mtp_loss", lambda: jnp.zeros((), jnp.float32)
            )
            if not self.is_initializing():
                held.value = weighed
            return logits
        # a training forward that was handed no state: ``loss`` adds it
        return LogitsAndLoss(logits, weighed)


def _listed(items):
    return " and ".join(filter(None, (", ".join(items[:-1]), items[-1])))


def custom_model(dtype="float32", **sizes):
    """``HybridMoELM(**sizes)``; every size has the toy default of the
    class, and a name it does not know is refused. One rule for every
    record: where a layer of the kind is in the pattern each of its own
    sizes passes its check, and where none is, a size other than the
    default says nothing and is refused."""
    pattern = str(sizes.get("layer_pattern", HybridMoELM.layer_pattern))
    unknown = sorted(set(pattern) - set(LETTERS))
    if not pattern or unknown:
        raise ValueError(
            "layer_pattern %r holds %s: a letter a layer, %s"
            % (
                pattern, unknown or "no layer",
                ", ".join("%r %s" % item for item in LETTERS.items()),
            )
        )  # fmt: skip
    model = HybridMoELM(dtype=jnp.dtype(dtype), **sizes)

    def checked(rows, holding=""):
        for size in rows:
            said = size.wrong(model)
            if said:
                raise ValueError(holding + said)

    checked(SHARED)
    held = _layers(model)
    owned = {
        size.name for _, record, layers in held if layers for size in record.sizes
    }
    for letter, record, layers in held:
        named = "layer_pattern %r holds %s" % (pattern, record.held(layers))
        if layers:
            checked(record.sizes, named + ": ")
            continue
        # a number or a switch with its value, a choice by its name (the
        # texts the earlier refusals had, which tests/ match)
        idle = [
            size.name if isinstance(value, str) else "%s=%r" % (size.name, value)
            for size in record.sizes
            for value in (getattr(model, size.name),)
            if size.name not in owned and value != getattr(HybridMoELM, size.name)
        ]
        if idle:
            raise ValueError(
                "%s%s, so %s say nothing"
                % (named, letter and " (%r)" % letter, _listed(idle))
            )
    return model
