"""Pipeline parallelism: layer stages over a ``pipe`` mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2.2: absent).
This is the TPU-native form: the network's layers are grouped into S
stages, stage s's parameters live only on the devices at ``pipe`` index
s, and microbatches flow through the stage ring with
``lax.ppermute`` — the GPipe schedule expressed as a ``lax.scan`` over
S + M - 1 ticks inside ``shard_map``. XLA overlaps each tick's
stage compute with the activation rotation (async collectives over
ICI), and reverse-mode AD through scan + ppermute yields the matching
1F1B-shaped backward without any hand-written schedule.

Composes with the other axes on one mesh: ``data`` shards the batch,
``pipe`` shards depth. Stage parameters arrive *stacked* on a leading
stage dimension (leaf shape (S, ...) sharded P('pipe', ...)), the layout
:func:`stack_stage_params` builds and
:func:`elasticdl_tpu.parallel.trainer.AllReduceTrainer` can place via
param_specs.
"""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


def stack_stage_params(per_stage_params):
    """[params_stage0, ...] -> one pytree with a leading (S,) stage dim."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params
    )


def pipeline_apply(stage_fn, stage_params, microbatches, axis_name):
    """Run the stage ring over microbatches; call inside shard_map.

    - ``stage_fn(params, x) -> y``: one stage's computation; every stage
      must map the same activation shape to itself (classic pipeline
      constraint — embed/head layers live outside the ring).
    - ``stage_params``: this device's slice of the stacked stage params
      (leading dim 1, squeezed internally).
    - ``microbatches``: (M, mb, ...) activations, replicated along
      ``axis_name`` (every stage sees the input stream; only stage 0
      consumes it).

    Returns (M, mb, ...) outputs, valid on the LAST stage (callers take
    index S-1; the shard_map wrapper below does).
    """
    n_stages = jax.lax.psum(1, axis_name)
    stage = jax.lax.axis_index(axis_name)
    params = jax.tree_util.tree_map(
        lambda x: jnp.squeeze(x, axis=0), stage_params
    )
    m = microbatches.shape[0]
    mb_shape = microbatches.shape[1:]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        held, outputs = carry
        # stage 0 ingests microbatch t (if any remain); others keep the
        # activation that just rotated in
        feed = jnp.where(
            t < m,
            jax.lax.dynamic_index_in_dim(
                microbatches, jnp.minimum(t, m - 1), keepdims=False
            ),
            jnp.zeros(mb_shape, microbatches.dtype),
        )
        x = jnp.where(stage == 0, feed, held)
        y = stage_fn(params, x)
        # the last stage's result for microbatch (t - (S-1)) is ready
        out_idx = t - (n_stages - 1)
        outputs = jnp.where(
            (out_idx >= 0) & (out_idx < m),
            jax.lax.dynamic_update_index_in_dim(
                outputs, y, jnp.clip(out_idx, 0, m - 1), axis=0
            ),
            outputs,
        )
        held_next = jax.lax.ppermute(y, axis_name, perm)
        return (held_next, outputs), None

    held0 = jnp.zeros(mb_shape, microbatches.dtype)
    outputs0 = jnp.zeros((m,) + mb_shape, microbatches.dtype)
    (_, outputs), _ = jax.lax.scan(
        tick,
        (held0, outputs0),
        jnp.arange(m + n_stages - 1),
    )
    return outputs


def make_pipeline_fn(mesh, stage_fn, pipe_axis="pipe", batch_axis=None):
    """Global-array wrapper: ``(stacked_params, microbatches) -> out``.

    ``stacked_params`` leaves are (S, ...) sharded over ``pipe_axis``;
    ``microbatches`` is (M, mb, ...) (optionally batch-sharded over
    ``batch_axis`` on dim 1 for dp x pp). Output matches microbatches'
    shape/sharding: the last stage's results, broadcast over the pipe
    axis so downstream (loss) code sees ordinary replicated activations.
    """

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            P(pipe_axis),
            P(None, batch_axis),
        ),
        out_specs=P(None, batch_axis),
        check_vma=False,
    )
    def _pipe(stacked_params, microbatches):
        out = pipeline_apply(
            stage_fn, stacked_params, microbatches, pipe_axis
        )
        # broadcast the last stage's outputs to every pipe rank so the
        # result is replicated along the pipe axis
        n_stages = jax.lax.psum(1, pipe_axis)
        stage = jax.lax.axis_index(pipe_axis)
        mask = (stage == n_stages - 1).astype(out.dtype)
        return jax.lax.psum(out * mask, pipe_axis)

    return _pipe


def stage_param_sharding(mesh, pipe_axis="pipe"):
    """NamedSharding for stacked stage parameters."""
    return NamedSharding(mesh, P(pipe_axis))


def collective_pipeline_apply(
    stage_fn, local_stage_params, x_local, pipe_axis, microbatches=0
):
    """Pipeline ring over per-device batch rows INSIDE an enclosing
    shard_map — the elastic weighted step's form of pipeline
    parallelism, the same raw-collective recipe as
    nn/hbm_embedding.py's ``collective=True`` lookups (a nested
    shard_map is impossible there).

    - ``local_stage_params``: this device's slice of the stacked stage
      params — leading dim 1 (the pipe axis size must equal the stage
      count).
    - ``x_local``: (b_loc, ...) THIS device's activation rows (each
      device of a data group holds different rows).
    - Returns (b_loc, ...): the ring outputs for exactly this device's
      rows.

    Data flow: all_gather the data group's rows over ``pipe_axis`` (so
    stage 0 can ingest the whole group's stream), microbatch, run the
    ring, psum-broadcast the last stage's outputs back over the pipe
    axis, slice this device's rows back out. Gradient flow is exact:
    the all_gather's transpose routes activation gradients back to each
    row's source device; the ppermute transposes inside the ring's
    backward deliver each stage's parameter gradients to that stage's
    devices (the step then psums them over the remaining axes).
    """
    n_stages = jax.lax.psum(1, pipe_axis)
    stage = jax.lax.axis_index(pipe_axis)
    b_loc = x_local.shape[0]
    group = jax.lax.all_gather(x_local, pipe_axis, tiled=True)
    rows = group.shape[0]
    m = microbatches or n_stages
    padded = -(-rows // m) * m
    if padded != rows:
        group = jnp.concatenate(
            [
                group,
                jnp.broadcast_to(
                    group[-1:], (padded - rows,) + group.shape[1:]
                ),
            ]
        )
    micro = jnp.reshape(group, (m, padded // m) + group.shape[1:])
    out = pipeline_apply(stage_fn, local_stage_params, micro, pipe_axis)
    # only the last stage's outputs are the ring's result; broadcast
    # them to every pipe rank so each can slice its own rows
    mask = (stage == n_stages - 1).astype(out.dtype)
    out = jax.lax.psum(out * mask, pipe_axis)
    flat = jnp.reshape(out, (padded,) + out.shape[2:])[:rows]
    return jax.lax.dynamic_slice_in_dim(flat, stage * b_loc, b_loc, 0)


class PipelinedStack(nn.Module):
    """Flax module running a stage template through the pipe ring.

    The job-path integration of :func:`pipeline_apply`: drop this into a
    model where a sequential stack of identical-shape layers would sit
    (transformer blocks — embed/head stay outside the ring), declare its
    ``stages`` parameter subtree as ``{"**": P("pipe")}`` in the zoo's
    ``param_shardings``, and the ALLREDUCE trainers place each stage's
    parameters only on that stage's devices.

    - ``stage_template``: an UNBOUND module whose ``__call__(x)`` maps an
      activation to the same shape (the classic pipeline constraint).
    - ``n_stages``: ring length; must equal the mesh's ``pipe`` axis size.
    - ``microbatches``: how many microbatches the incoming batch splits
      into (0 -> ``n_stages``; more microbatches shrink the bubble,
      S/(S+M-1) of ticks are ramp).
    - ``mesh=None``: degenerate single-device form — runs the stages
      sequentially (used for init shape-tracing and CPU smoke tests).
    - ``collective=True``: the module is being applied INSIDE an
      enclosing shard_map whose mesh has a ``pipe`` axis (the elastic
      weighted step). The stacked param arrives as this device's local
      (1, ...) stage slice, and the ring runs via raw collectives
      (:func:`collective_pipeline_apply`) — ``mesh`` stays None. Init
      still traces the sequential form and creates the full (S, ...)
      stacked parameters.

    Parameters are created by initializing the template once per stage
    and stacking each leaf on a leading (S,) dim — a single flax param
    whose value is the stacked subtree, so checkpoints/optimizers see
    ordinary (S, ...) leaves.
    """

    stage_template: object
    n_stages: int
    mesh: object = None
    pipe_axis: str = "pipe"
    microbatches: int = 0
    collective: bool = False

    @nn.compact
    def __call__(self, x):
        m = self.microbatches or self.n_stages

        def init_fn(rng):
            rngs = jax.random.split(rng, self.n_stages)
            per = [
                self.stage_template.init(r, x[:1])["params"]
                for r in rngs
            ]
            return stack_stage_params(per)

        if self.collective:
            # self.variable, not self.param: flax shape-validates params
            # against their initializer at apply time, but in collective
            # mode the apply-time value is this device's (1, ...) LOCAL
            # stage slice of the declared (S, ...) stacked subtree (the
            # same recipe as nn/hbm_embedding.py's collective table)
            stacked = self.variable(
                "params",
                "stages",
                lambda: init_fn(self.make_rng("params")),
            ).value
        else:
            stacked = self.param("stages", init_fn)

        def stage_fn(params, act):
            return self.stage_template.apply({"params": params}, act)

        if self.collective and not self.is_initializing():
            return collective_pipeline_apply(
                stage_fn,
                stacked,
                x,
                self.pipe_axis,
                microbatches=self.microbatches,
            )
        if (
            self.is_initializing()
            or self.mesh is None
            or self.pipe_axis not in getattr(self.mesh, "axis_names", ())
        ):
            # sequential reference form: init tracing (single example,
            # no microbatching possible) and pipe-less meshes
            y = x
            for s in range(self.n_stages):
                p = jax.tree_util.tree_map(
                    lambda a, s=s: a[s], stacked
                )
                y = stage_fn(p, y)
            return y
        batch_axis = (
            "data" if "data" in self.mesh.axis_names else None
        )
        # pad ragged batches (eval tails) up to a whole number of
        # microbatch rows per data shard, slice the padding back off
        chunk = m * (
            self.mesh.shape[batch_axis] if batch_axis else 1
        )
        b = x.shape[0]
        padded = -(-b // chunk) * chunk
        if padded != b:
            x = jnp.concatenate(
                [x, jnp.broadcast_to(x[-1:], (padded - b,) + x.shape[1:])]
            )
        micro = jnp.reshape(x, (m, padded // m) + x.shape[1:])
        out = make_pipeline_fn(
            self.mesh,
            stage_fn,
            pipe_axis=self.pipe_axis,
            batch_axis=batch_axis,
        )(stacked, micro)
        out = jnp.reshape(out, (padded,) + out.shape[2:])
        return out[:b]


def reference_pipeline(stage_fn, per_stage_params, microbatches):
    """Sequential semantics the ring must match (tests)."""
    outs = []
    for x in np.asarray(microbatches):
        y = jnp.asarray(x)
        for params in per_stage_params:
            y = stage_fn(params, y)
        outs.append(y)
    return jnp.stack(outs)
