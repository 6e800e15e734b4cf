"""Decoder-only transformer LM — the long-context model family.

No counterpart exists in the reference zoo (CNNs/DeepFM only, SURVEY.md
§5.7); this family exercises the framework's TPU-native scaling axes:

- ``data``  — batch data parallelism,
- ``model`` — tensor parallelism (parallel/sharding.py rules match this
  module's parameter names: query/key/value/out, mlp_up/mlp_down, embed),
- ``seq``   — sequence parallelism via ring attention
  (parallel/ring_attention.py) when constructed with ``mesh`` +
  ``seq_axis``.

Compute dtype is configurable (bfloat16 on the MXU by default for large
configs); RMSNorm + rotary embeddings keep the block cache/scan friendly.
"""

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.common.constants import Mode
from elasticdl_tpu.data.example import FixedLenFeature, parse_example
from elasticdl_tpu.parallel.ring_attention import (
    make_ring_attention,
    reference_attention,
)


def _rotary(x, positions, theta=10000.0):
    """Rotary position embedding over the last (head) dim, base
    ``theta``."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, L, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)


class MoEMlp(nn.Module):
    """Mixture-of-experts MLP: top-k routing over the ``expert`` mesh
    axis (parallel/expert.py). Expert parameters are stacked on a leading
    (E,) dim sharded over the axis; the dense fallback (no mesh / no
    ``expert`` axis) computes every expert and combines by gate — the
    routed form's reference semantics. With ``aux_loss_coef > 0`` the
    Switch load-balancing loss is written to the ``aux_loss`` collection,
    which every step builder adds to the task loss (training/step.py)."""

    num_experts: int
    mlp_dim: int
    dtype: Any
    mesh: Any = None
    capacity_factor: float = 2.0
    num_selected: int = 1
    aux_loss_coef: float = 0.01

    @nn.compact
    def __call__(self, h):
        from elasticdl_tpu.parallel.expert import (
            load_balancing_loss,
            make_moe_fn,
            reference_moe,
        )
        from elasticdl_tpu.training.step import AUX_LOSS_COLLECTION

        d = h.shape[-1]
        e = self.num_experts
        gate_logits = nn.Dense(
            e, use_bias=False, dtype=self.dtype, name="gate"
        )(h)
        w_up = self.param(
            "experts_up",
            nn.initializers.lecun_normal(),
            (e, d, self.mlp_dim),
        )
        w_down = self.param(
            "experts_down",
            nn.initializers.lecun_normal(),
            (e, self.mlp_dim, d),
        )

        def expert_fn(params, tokens):
            up = tokens.astype(self.dtype) @ params["up"].astype(self.dtype)
            return nn.gelu(up) @ params["down"].astype(self.dtype)

        stacked = {"up": w_up, "down": w_down}
        tokens = h.reshape(-1, d)
        logits_flat = gate_logits.reshape(-1, e)
        aux = self.variable(
            AUX_LOSS_COLLECTION,
            "moe_balance",
            lambda: jnp.zeros((), jnp.float32),
        )
        if self.is_mutable_collection(AUX_LOSS_COLLECTION):
            # training applies pass state collections as mutable; eval
            # forwards are immutable and skip the write
            aux.value = self.aux_loss_coef * load_balancing_loss(
                logits_flat
            )
        use_routed = (
            self.mesh is not None and "expert" in self.mesh.axis_names
        )
        if use_routed:
            # shard the token stream over the data axis when present so
            # dp replicas route only their own slice (a P(None) spec
            # would all-gather and redo the MoE per replica)
            batch_axis = (
                "data" if "data" in self.mesh.axis_names else None
            )
            moe = make_moe_fn(
                self.mesh,
                expert_fn,
                expert_axis="expert",
                batch_axis=batch_axis,
                capacity_factor=self.capacity_factor,
                num_selected=self.num_selected,
            )
            out = moe(stacked, tokens, logits_flat)
        else:
            per_expert = [
                {"up": w_up[i], "down": w_down[i]} for i in range(e)
            ]
            out = reference_moe(
                expert_fn,
                per_expert,
                tokens,
                logits_flat,
                num_selected=self.num_selected,
            )
        return out.reshape(h.shape).astype(h.dtype)


class Block(nn.Module):
    num_heads: int
    head_dim: int
    mlp_dim: int
    dtype: Any
    attention_fn: Any
    num_experts: int = 0
    mesh: Any = None
    moe_capacity_factor: float = 2.0
    moe_num_selected: int = 1
    moe_aux_loss_coef: float = 0.01

    @nn.compact
    def __call__(self, x, positions):
        h = nn.RMSNorm(dtype=self.dtype)(x)
        dense = functools.partial(
            nn.DenseGeneral,
            features=(self.num_heads, self.head_dim),
            axis=-1,
            use_bias=False,
            dtype=self.dtype,
        )
        q = _rotary(dense(name="query")(h), positions)
        k = _rotary(dense(name="key")(h), positions)
        v = dense(name="value")(h)
        attn = self.attention_fn(q, k, v)
        attn = nn.DenseGeneral(
            features=x.shape[-1],
            axis=(-2, -1),
            use_bias=False,
            dtype=self.dtype,
            name="out",
        )(attn)
        x = x + attn
        h = nn.RMSNorm(dtype=self.dtype)(x)
        if self.num_experts:
            h = MoEMlp(
                num_experts=self.num_experts,
                mlp_dim=self.mlp_dim,
                dtype=self.dtype,
                mesh=self.mesh,
                capacity_factor=self.moe_capacity_factor,
                num_selected=self.moe_num_selected,
                aux_loss_coef=self.moe_aux_loss_coef,
                name="moe_mlp",
            )(h)
        else:
            h = nn.Dense(self.mlp_dim, dtype=self.dtype, name="mlp_up")(h)
            h = nn.gelu(h)
            h = nn.Dense(x.shape[-1], dtype=self.dtype, name="mlp_down")(h)
        return x + h


class TransformerLM(nn.Module):
    vocab_size: int = 1024
    num_layers: int = 2
    num_heads: int = 4
    head_dim: int = 16
    embed_dim: int = 64
    mlp_dim: int = 256
    dtype: Any = jnp.float32
    mesh: Any = None
    seq_axis: Any = None
    # Pallas fused-attention kernel (single-chip path; the mesh/seq_axis
    # path uses the fused ring). Trains blockwise since round 2 — the
    # backward recomputes p per tile from the saved logsumexp.
    use_flash: bool = True
    # >0 turns every block's MLP into a top-k MoE; expert parameters
    # shard over the mesh's 'expert' axis when present (parallel/expert)
    num_experts: int = 0
    moe_capacity_factor: float = 2.0
    moe_num_selected: int = 1  # top-k routing (2 = GShard top-2)
    moe_aux_loss_coef: float = 0.01  # Switch load-balancing loss weight

    @nn.compact
    def __call__(self, features, training=False):
        tokens = (
            features["tokens"] if isinstance(features, dict) else features
        )
        tokens = tokens.astype(jnp.int32)
        b, l = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32), (b, l))

        if self.mesh is not None and self.seq_axis is not None:
            attention_fn = make_ring_attention(
                self.mesh, self.seq_axis, causal=True,
                use_flash=self.use_flash,
            )
        else:
            # flash above the measured win threshold, XLA below / for
            # lengths the kernel can't tile (one policy home:
            # ops/flash_attention.pick_causal_attention)
            from elasticdl_tpu.ops.flash_attention import (
                pick_causal_attention,
            )

            attention_fn = pick_causal_attention(l, self.use_flash)

        embed_layer = nn.Embed(
            self.vocab_size,
            self.embed_dim,
            dtype=self.dtype,
            name="embed",
        )
        x = embed_layer(tokens)
        for i in range(self.num_layers):
            x = Block(
                num_heads=self.num_heads,
                head_dim=self.head_dim,
                mlp_dim=self.mlp_dim,
                dtype=self.dtype,
                attention_fn=attention_fn,
                num_experts=self.num_experts,
                mesh=self.mesh,
                moe_capacity_factor=self.moe_capacity_factor,
                moe_num_selected=self.moe_num_selected,
                moe_aux_loss_coef=self.moe_aux_loss_coef,
                name="block_%d" % i,
            )(x, positions)
        x = nn.RMSNorm(dtype=self.dtype)(x)
        # weight-tied LM head (reads the vocab-sharded embed table)
        logits = embed_layer.attend(x.astype(jnp.float32))
        return logits


class StageBlocks(nn.Module):
    """One pipeline stage: a sequential run of transformer blocks.

    The pipeline stage template (parallel/pipeline.py PipelinedStack):
    maps (b, l, d) activations to the same shape; rotary positions are
    recomputed per stage from the activation length (identical across
    examples, so nothing needs to ride the ring besides activations)."""

    n_layers: int
    num_heads: int
    head_dim: int
    mlp_dim: int
    dtype: Any
    use_flash: bool = True

    @nn.compact
    def __call__(self, x):
        b, l = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(
            jnp.arange(l, dtype=jnp.int32), (b, l)
        )
        from elasticdl_tpu.ops.flash_attention import (
            pick_causal_attention,
        )

        attention_fn = pick_causal_attention(l, self.use_flash)
        for i in range(self.n_layers):
            x = Block(
                num_heads=self.num_heads,
                head_dim=self.head_dim,
                mlp_dim=self.mlp_dim,
                dtype=self.dtype,
                attention_fn=attention_fn,
                name="block_%d" % i,
            )(x, positions)
        return x


class PipelinedTransformerLM(nn.Module):
    """TransformerLM with its block stack run as pipeline stages.

    Embed + head (weight-tied) replicate outside the ring; the blocks
    group into ``pipeline_stages`` stages whose parameters live only on
    their stage's devices (mesh axis ``pipe``), composing with ``data``
    batch parallelism on the same mesh (pp x dp)."""

    vocab_size: int = 1024
    num_layers: int = 2
    num_heads: int = 4
    head_dim: int = 16
    embed_dim: int = 64
    mlp_dim: int = 256
    dtype: Any = jnp.float32
    mesh: Any = None
    pipeline_stages: int = 2
    microbatches: int = 0
    use_flash: bool = True
    # in-step raw-collective ring for the multi-process elastic plane
    # (applied inside the weighted step's shard_map; mesh stays None)
    collective: bool = False

    @nn.compact
    def __call__(self, features, training=False):
        tokens = (
            features["tokens"] if isinstance(features, dict) else features
        )
        tokens = tokens.astype(jnp.int32)
        if self.num_layers % self.pipeline_stages:
            raise ValueError(
                "num_layers %d must divide into %d pipeline stages"
                % (self.num_layers, self.pipeline_stages)
            )
        embed_layer = nn.Embed(
            self.vocab_size,
            self.embed_dim,
            dtype=self.dtype,
            name="embed",
        )
        x = embed_layer(tokens)
        from elasticdl_tpu.parallel.pipeline import PipelinedStack

        x = PipelinedStack(
            stage_template=StageBlocks(
                n_layers=self.num_layers // self.pipeline_stages,
                num_heads=self.num_heads,
                head_dim=self.head_dim,
                mlp_dim=self.mlp_dim,
                dtype=self.dtype,
                use_flash=self.use_flash,
            ),
            n_stages=self.pipeline_stages,
            mesh=self.mesh,
            microbatches=self.microbatches,
            collective=self.collective,
            name="pipe",
        )(x)
        x = nn.RMSNorm(dtype=self.dtype)(x)
        logits = embed_layer.attend(x.astype(jnp.float32))
        return logits


_PIPELINE_SUPPORTED_PARAMS = frozenset(
    {
        "vocab_size",
        "num_layers",
        "num_heads",
        "head_dim",
        "embed_dim",
        "mlp_dim",
        "use_flash",
    }
)


def _check_pipeline_params(params):
    """Reject model params the pipelined form would silently drop —
    training a DIFFERENT model than asked for (e.g. dense instead of
    MoE). Shared by both pipelined entry points so their supported
    sets cannot drift."""
    unsupported = set(params) - _PIPELINE_SUPPORTED_PARAMS
    if unsupported:
        raise ValueError(
            "pipeline_stages > 1 does not support model params %s "
            "(pipeline composes with data parallelism only for "
            "now; MoE/seq-parallel pipelined configs are not "
            "implemented)" % sorted(unsupported)
        )


def build_distributed_model(
    mesh, pipeline_stages=0, microbatches=0, dtype="float32", **params
):
    """Zoo hook for the ALLREDUCE trainers: with ``pipeline_stages > 1``
    builds the pipelined form over the mesh's pipe axis (pair with
    :func:`param_shardings` and :func:`mesh_axes`); otherwise the plain
    model over the mesh."""
    stages = int(pipeline_stages)
    # consumed by param_shardings/mesh_axes (placement), not by the
    # model itself
    params.pop("shard_vocab", None)
    params.pop("tensor_parallel", None)
    params.pop("min_tensor_parallel", None)
    if stages > 1:
        _check_pipeline_params(params)
        return PipelinedTransformerLM(
            mesh=mesh,
            pipeline_stages=stages,
            microbatches=int(microbatches),
            dtype=jnp.dtype(dtype),
            **params,
        )
    return custom_model(mesh=mesh, dtype=dtype, **params)


def build_collective_model(
    pipeline_stages=0, microbatches=0, dtype="float32", **params
):
    """Zoo hook for the MULTI-PROCESS elastic plane: the pipelined
    transformer in its raw-collective form, applied inside the weighted
    step's shard_map over a ("data", "pipe") mesh (see
    parallel/pipeline.collective_pipeline_apply). The mesh axis layout
    comes from :func:`mesh_axes`; stage parameters shard per
    :func:`param_shardings`. Requires ``pipeline_stages > 1`` — plain
    (non-sharding) configs train replicated via ``custom_model`` and
    never route here (the worker gates on param_shardings' probe)."""
    stages = int(pipeline_stages)
    if params.pop("shard_vocab", None):
        # param_shardings would declare the embed table P("data", None)
        # while this model builds a full-vocab nn.Embed — the step would
        # feed the local shard to a full-table module. Fail fast with
        # the boundary instead of crash-looping at establish.
        raise ValueError(
            "shard_vocab is not supported on the multi-process elastic "
            "plane yet (the pipelined collective form keeps the embed "
            "table replicated); drop shard_vocab, or use the "
            "single-process ALLREDUCE strategy for vocab-sharded "
            "training"
        )
    if stages <= 1:
        raise ValueError(
            "build_collective_model needs pipeline_stages > 1; "
            "non-pipelined configs train on the replicated plane"
        )
    _check_pipeline_params(params)
    return PipelinedTransformerLM(
        mesh=None,
        collective=True,
        pipeline_stages=stages,
        microbatches=int(microbatches),
        dtype=jnp.dtype(dtype),
        **params,
    )


def param_shardings(
    mesh,
    pipeline_stages=0,
    shard_vocab=False,
    tensor_parallel=0,
    min_tensor_parallel=0,
    **_params,
):
    """Stacked stage parameters shard leaf-dim-0 over ``pipe``; with
    ``shard_vocab`` the token-embedding table additionally row-shards
    its vocab over ``data`` (the weight-tied LM head then contracts a
    vocab-sharded table — XLA inserts the collectives from the
    placement, the HBM-embedding recipe applied to the LM family).

    With ``tensor_parallel > 1`` the dense model itself shards over the
    2D ``data x model`` mesh: the name-pattern TP rules of
    parallel/sharding.py (qkv/out heads, MLP hidden, vocab) emitted as
    real specs — the PLAIN module then trains under the elastic
    trainer's pjit/GSPMD dense path, parameters placed by NamedSharding
    instead of replicated everywhere (docs/distributed.md), unlocking
    dense models bigger than one device's HBM inside the elastic world.

    ``min_tensor_parallel`` opts into the elastic LAYOUT RE-SOLVE
    (docs/distributed.md "Layout re-solve") without freezing a degree:
    the TP specs are emitted (routing the config onto the pjit dense
    plane — the worker's ``_zoo_wants_pjit_dense`` probe sees the
    ``model`` axis), the layout solver picks the actual degree per
    world size, and the value acts as the tp FLOOR the master derives
    its world-size multiple from — so a solver-chosen degree can never
    form a world the mesh rejects. The TP spec patterns themselves are
    degree-free (the mesh's model-axis size carries the degree), which
    is what makes a per-resize degree change sound.

    ``mesh=None`` is the capability probe (does this config shard at
    all?) — answered from the params alone."""
    from jax.sharding import PartitionSpec as P

    specs = {}
    tp = max(int(tensor_parallel), int(min_tensor_parallel))
    if tp > 1 and int(pipeline_stages) > 1:
        raise ValueError(
            "tensor_parallel and pipeline_stages cannot combine yet: "
            "the pjit dense path and the collective pipeline use "
            "different step builders — pick one"
        )
    if tp > 1 and shard_vocab:
        raise ValueError(
            "shard_vocab is redundant with tensor_parallel (the TP "
            "rules already vocab-shard the embed table, over 'model')"
        )
    if tp > 1 and (mesh is None or "model" in mesh.axis_names):
        from elasticdl_tpu.parallel.sharding import tp_param_specs

        specs.update(tp_param_specs())
    if int(pipeline_stages) > 1 and (
        mesh is None or "pipe" in mesh.axis_names
    ):
        specs["pipe"] = {"stages": {"**": P("pipe")}}
    if shard_vocab and (mesh is None or "data" in mesh.axis_names):
        specs["embed"] = {"embedding": P("data", None)}
    return specs or None


def mesh_axes(
    n_devices,
    pipeline_stages=0,
    tensor_parallel=0,
    min_tensor_parallel=0,
    **_params,
):
    """Zoo hook: mesh shape for this model's parallelism config.

    With only ``min_tensor_parallel`` set this answers the FLOOR layout
    (tp = the floor) — the static fallback the layout planner starts
    from and re-solves away from once the model profile exists. The
    master's world-size-multiple derivation keeps every formable world
    a multiple of the floor, so the divisibility check here cannot
    fire on the planner's watch."""
    stages = int(pipeline_stages)
    tp = max(int(tensor_parallel), int(min_tensor_parallel))
    if tp > 1:
        if stages > 1:
            raise ValueError(
                "tensor_parallel does not combine with pipeline_stages"
            )
        if n_devices % tp:
            raise ValueError(
                "%d devices do not divide into tensor_parallel=%d"
                % (n_devices, tp)
            )
        # row-major reshape: consecutive devices fill the model axis
        # first, so each tp group is one contiguous device block
        return {"data": n_devices // tp, "model": tp}
    if stages > 1:
        if n_devices % stages:
            raise ValueError(
                "%d devices do not divide into %d pipeline stages"
                % (n_devices, stages)
            )
        return {"data": n_devices // stages, "pipe": stages}
    return None


def custom_model(
    vocab_size=1024,
    num_layers=2,
    num_heads=4,
    head_dim=16,
    embed_dim=64,
    mlp_dim=256,
    dtype="float32",
    mesh=None,
    seq_axis=None,
    use_flash=True,
    num_experts=0,
    moe_capacity_factor=2.0,
    moe_num_selected=1,
    moe_aux_loss_coef=0.01,
    # consumed by build_distributed_model (the ALLREDUCE job path swaps
    # in PipelinedTransformerLM) / param_shardings (tensor_parallel
    # placement — the pjit dense path trains THIS plain module);
    # accepted here so one --model_params string serves both the plain
    # spec and the distributed hooks
    pipeline_stages=0,
    microbatches=0,
    tensor_parallel=0,
    min_tensor_parallel=0,
    shard_vocab=False,
):
    return TransformerLM(
        vocab_size=vocab_size,
        num_layers=num_layers,
        num_heads=num_heads,
        head_dim=head_dim,
        embed_dim=embed_dim,
        mlp_dim=mlp_dim,
        dtype=jnp.dtype(dtype),
        mesh=mesh,
        seq_axis=seq_axis,
        use_flash=use_flash,
        num_experts=num_experts,
        moe_capacity_factor=moe_capacity_factor,
        moe_num_selected=moe_num_selected,
        moe_aux_loss_coef=moe_aux_loss_coef,
    )


def loss(output, labels):
    """Next-token cross entropy; position 0 predicts token 1, etc."""
    logits = output[:, :-1]
    targets = labels.astype(jnp.int32)[:, 1:]
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, targets
    ).mean()


def optimizer(lr=3e-3):
    return optax.adamw(lr)


def dataset_fn(dataset, mode, _):
    # sequence length follows the record (parse_example reshapes to the
    # spec, and -1 takes whatever the record holds); batching requires
    # the records of one job to agree
    spec = {"tokens": FixedLenFeature([-1], np.int64)}

    def _parse_data(record):
        r = parse_example(record, spec)
        tokens = r["tokens"].astype(np.int32)
        features = {"tokens": tokens}
        if mode == Mode.PREDICTION:
            return features
        return features, tokens

    dataset = dataset.map(_parse_data)
    if mode == Mode.TRAINING:
        dataset = dataset.shuffle(buffer_size=1024)
    return dataset


def eval_metrics_fn():
    def _token_accuracy(labels, predictions):
        pred = np.argmax(np.asarray(predictions)[:, :-1], axis=-1)
        tgt = np.asarray(labels)[:, 1:]
        return (pred == tgt).reshape(-1)

    return {"token_accuracy": _token_accuracy}
