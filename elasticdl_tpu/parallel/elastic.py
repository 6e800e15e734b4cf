"""Multi-process elastic data-parallel training over a global device mesh.

This is the cross-host realization of the ALLREDUCE strategy
(parallel/trainer.py is the single-process form): every worker process
holds a slot in one ``jax.sharding.Mesh`` spanning all hosts, parameters
live replicated in device memory, and the per-step gradient exchange is
the in-step XLA collective. The reference never built this plane (its
allreduce.md is a design survey, SURVEY.md §2.2); the gRPC dense-gradient
round trips it *did* build (GetModel/ReportGradient) are exactly what the
in-mesh collective replaces.

Three problems unique to the elastic multi-process setting, and their
solutions here:

- **Lockstep with independent task queues.** Each process pulls its own
  tasks from the master, so processes run out of data at different
  times — but every process must participate in every collective. The
  step is *weighted*: each device contributes its gradient scaled by a
  0/1 weight, the weighted psum divides by the live count, and the step
  returns that count. A process with no data feeds its previous batch at
  weight 0 and keeps stepping until the global count reaches zero — the
  collective itself is the "anyone still training?" barrier.

- **State continuity across membership epochs.** On a world change the
  worker pulls its addressable replica to host, re-forms the world
  (parallel/distributed.py), and re-places state with
  :func:`broadcast_from_device0`: every process offers its copy, device 0
  (rank 0 = the longest-lived survivor) wins, XLA broadcasts it. A fresh
  joiner offers garbage and receives the survivors' state — replacing the
  reference's workers-re-push-to-PS re-init (ps/servicer.py:70-79).
  That holds the offer and the picked copy on the device at once; on a
  mesh whose devices all belong to this process there is nobody to
  adopt from, and :func:`place_from_host` puts the host tree onto the
  mesh once (a state over half a chip's memory starts that way).

- **Failure visibility.** A peer death mid-collective surfaces as an
  error from the jitted step on every survivor. On a mesh that spans
  processes the step's inputs are not donated, so the pre-step state is
  still addressable afterwards; the worker fetches it, waits for the
  master to bump the epoch, and re-forms. That double buffering is the
  price of kill-anywhere recovery, and it is paid where a peer exists:
  on a mesh whose devices all belong to this process no collective can
  fail under a live process, so there the step donates its train state
  (:func:`state_donation`) and the trainer keeps no second device copy
  (``ElasticDPTrainer._keep_checked``).
"""

import json
import os
import re
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.nn.hbm_embedding import (
    METRICS_COLLECTION,
    a2a_overflow_total,
)
from elasticdl_tpu.nn.model_api import apply_model, init_variables, split_variables
from elasticdl_tpu.ops import flash_attention
from elasticdl_tpu.parallel import compile_plane, distributed, layout_solver
from elasticdl_tpu.parallel.expert import MOE_STATE_COLLECTION
from elasticdl_tpu.parallel.sharding import tp_degree_candidates
from elasticdl_tpu.training.step import (
    TrainState,
    accumulate_gradients,
    AUX_LOSS_COLLECTION,
    aux_loss_total,
    block_device_losses,
)
from elasticdl_tpu.utils import profiling, step_ops


# re-exported: the trainer's historical home for the escapable-call
# machinery; the implementation lives in a leaf module
from elasticdl_tpu.common.escapable import (  # noqa: F401
    EscapeTimeout,
    escapable_call,
)


# resize pause distribution, scraped via /metrics: one observation per
# establish(), labeled by whether the step fn came out of the
# executable cache (a PLANNED resize pays state movement only) or had
# to trace/compile
_RESIZE_PAUSE = profiling.metrics.histogram(
    "edl_resize_pause_seconds",
    "establish() wall seconds, world re-form through step-fn acquire",
    labels=("compile_phase",),
)


def build_world_mesh(mesh_axes_fn=None):
    """The elastic world's device mesh.

    Default: every device on one flat ``("data",)`` axis. With a zoo
    ``mesh_axes`` hook, the hook's ``{axis: size}`` layout (insertion
    order = axis order), e.g. ``{"data": n // S, "pipe": S}`` — the
    row-major reshape makes consecutive processes fill the trailing
    axis first, so the first ``S`` processes form one complete pipe
    group (and a world shrink keeps whole groups)."""
    devices = np.asarray(jax.devices())
    axes = mesh_axes_fn(devices.size) if mesh_axes_fn else None
    if not axes:
        return Mesh(devices, ("data",))
    names = tuple(axes)
    sizes = tuple(int(axes[n]) for n in names)
    if int(np.prod(sizes)) != devices.size:
        raise ValueError(
            "mesh_axes %r does not cover the %d-device world"
            % (axes, devices.size)
        )
    return Mesh(devices.reshape(sizes), names)


def row_partition_spec(mesh):
    """Dim-0-over-all-axes PartitionSpec (flattened device order)."""
    names = tuple(mesh.axis_names)
    return P(names if len(names) > 1 else names[0])


def mesh_local_count(mesh):
    """Devices of ``mesh`` this process addresses: the row count of
    everything placed one-row-per-local-device (the re-broadcast tiles,
    the step's weights and epochs). Not ``jax.local_device_count()``: a
    mesh may hold fewer devices than the process sees (an in-process
    resize onto a device subset), and jax refuses process-local data
    that does not fit the sharding it is placed with."""
    return len(mesh.local_devices)


def state_donation(mesh):
    """``donate_argnums`` of a train step built for ``mesh``: the
    TrainState (argument 0) when every device of the mesh belongs to
    this process, nothing when the mesh spans processes. A step whose
    collective a dying peer can fail must leave its input addressable
    for the survivors' re-form (module docstring, "Failure
    visibility"); a process-local mesh has no such peer, and holding
    the state as input and output at once only costs memory and lets
    the host run no further ahead than the allocator has room for.
    Read off the mesh alone, so an executable cached or speculatively
    compiled for a world follows that world's rule."""
    return () if mesh.is_multi_process else (0,)


def count_donated_inputs(lowered_text):
    """Inputs a lowered module (its MLIR text) donates. A donated input
    carries one of two attributes: the output it aliases where the
    lowering paired them, else the bare donor mark the compiler pairs
    later."""
    return len(
        re.findall(
            r"tf\.aliasing_output = \d+|jax\.buffer_donor = true",
            lowered_text,
        )
    )


def _local_replica(x):
    """The replica of ``x`` this process addresses (``x`` itself where
    it is no jax array)."""
    if hasattr(x, "addressable_shards"):
        return x.addressable_shards[0].data
    return x


def host_copy(tree):
    """Fetch each leaf's process-addressable replica to host numpy."""
    return jax.tree_util.tree_map(
        lambda x: np.asarray(_local_replica(x)), tree
    )


# The collections of the model's state that a sync point reads: the
# expert layers' routing state (a bias and wrapping counters a layer),
# what the model added to its loss, the embedding layers' overflow
# counters. Each is a few hundred bytes, replicated.
RECEIPT_COLLECTIONS = (
    MOE_STATE_COLLECTION,
    AUX_LOSS_COLLECTION,
    METRICS_COLLECTION,
)


def receipt_state(state):
    """The part of a model state (or of its spec tree) that a sync
    point reads: :data:`RECEIPT_COLLECTIONS`, those of them the model
    keeps."""
    if not isinstance(state, dict):
        return {}
    return {c: state[c] for c in RECEIPT_COLLECTIONS if c in state}


def kept_of(ts):
    """What of the train state (or of its spec tree) a step hands back
    a second time, beside the train state, as outputs of their own: its
    version and :func:`receipt_state` of the model's state. The next
    step donates the train state it is given (:func:`state_donation`)
    and these it is not given, so the worker can read them one step
    late, while that next step runs (``ElasticDPTrainer.settle``)."""
    return {"version": ts.version, "state": receipt_state(ts.state)}


def broadcast_from_device0(mesh, host_tree, source_process=0):
    """Place ``host_tree`` replicated on ``mesh``, all processes adopting
    ``source_process``'s copy (default: rank 0).

    Each process tiles its own host copy across its local devices into a
    global (n_devices, ...) array sharded on ``data``; selecting the
    source process's first device row under jit makes XLA broadcast that
    copy to every device. This is both the multi-process placement
    primitive (plain ``device_put`` can't target non-addressable
    shardings) and the survivor-state re-broadcast.
    """
    n_local = mesh_local_count(mesh)
    n_dev = mesh.devices.size
    src_dev = source_process * n_local
    row_axes = row_partition_spec(mesh)[0]

    def place(x):
        x = np.asarray(x)
        tiled = np.broadcast_to(x[None], (n_local,) + x.shape)
        spec = P(*((row_axes,) + (None,) * x.ndim))
        return jax.make_array_from_process_local_data(
            NamedSharding(mesh, spec), tiled, (n_dev,) + x.shape
        )

    stacked = jax.tree_util.tree_map(place, host_tree)
    pick = jax.jit(
        lambda t: jax.tree_util.tree_map(lambda a: a[src_dev], t),
        out_shardings=NamedSharding(mesh, P()),
    )
    return pick(stacked)


def place_from_host(mesh, host_tree):
    """Place ``host_tree`` replicated on a mesh whose devices all belong
    to this process: each leaf goes from the host straight onto its
    replicated sharding. Device memory holds the tree once while this
    runs and once when it returns, where :func:`broadcast_from_device0`
    holds the stacked offer beside the picked copy; the values are the
    host's, bit for bit, either way. Not for a mesh that spans
    processes (``device_put`` cannot target shards another process
    addresses, and there a source has to win)."""
    if mesh.is_multi_process:
        raise ValueError("the mesh spans processes: broadcast_from_device0")
    return jax.device_put(
        jax.tree_util.tree_map(np.asarray, host_tree),
        NamedSharding(mesh, P()),
    )


def _is_sharded_spec(spec):
    return spec is not None and any(a is not None for a in spec)


def _spec_axes(spec):
    """Flat set of mesh axis names a PartitionSpec shards over."""
    used = set()
    for entry in spec or ():
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            used.update(entry)
        else:
            used.add(entry)
    return used


class ShardMirror:
    """One rank's in-memory replica of the sharded state plane.

    Captured by :meth:`ElasticDPTrainer.refresh_mirror` (a collective —
    every rank at the same aligned step): this rank's own shards of
    every sharded leaf, a ``ppermute``-received copy of the LEFT
    neighbor process's shards, and a host copy of the replicated leaves
    — all at one consistent ``version``. Any single process death
    leaves every old shard present on some survivor (own everywhere +
    replica on the right neighbor), so a re-form can reassemble the
    full state device-to-device with no disk in the path; the loss
    bound is the refresh cadence. This implements (and betters) the
    replica design the reference specified but never built
    (/root/reference/docs/designs/parameter_server.md:109-131).
    """

    __slots__ = (
        "version",
        "n_old",
        "old_pid",
        "own",
        "replica",
        "replicated",
    )

    def __init__(self, version, n_old, old_pid, own, replica, replicated):
        self.version = version
        self.n_old = n_old  # process count of the world that captured it
        self.old_pid = old_pid  # this rank's process id in that world
        self.own = own  # {path names: np rows of this process's block}
        self.replica = replica  # left neighbor process's block, same keying
        self.replicated = replicated  # host ts; sharded leaves are placeholders


def process_dim0_block(axes, spec, shape0, n_local, pid):
    """(lo, hi) of the contiguous dim-0 rows process ``pid`` holds for a
    leaf whose dim 0 is sharded per ``spec`` on a mesh laid out as
    ``axes`` ({name: size}, insertion order = axis order).

    Derived analytically from the mesh layout — the replica plane needs
    any OLD process's block without that process being alive (its
    mirror holder reconstructs the range from the old world's shape
    alone). Handles any dim-0 sharding: single axis, axis tuples, and
    leaves replicated over part of the mesh (a P("pipe") stage subtree
    on a data x pipe mesh repeats the same range across data groups).
    """
    entry = spec[0] if spec is not None and len(spec) else None
    if entry is None:
        return (0, shape0)
    axs = tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)
    names = tuple(axes)
    sizes = tuple(int(axes[n]) for n in names)
    shard_count = 1
    for a in axs:
        shard_count *= int(axes[a])
    rows = shape0 // shard_count
    starts = set()
    for d in range(pid * n_local, (pid + 1) * n_local):
        coord = dict(zip(names, np.unravel_index(d, sizes)))
        idx = 0
        for a in axs:
            idx = idx * int(axes[a]) + int(coord[a])
        starts.add(idx * rows)
    lo, hi = min(starts), max(starts) + rows
    if hi - lo != len(starts) * rows:
        raise ValueError(
            "process %d holds a non-contiguous dim-0 block for spec %r "
            "on mesh %r" % (pid, spec, axes)
        )
    return (lo, hi)


def _subtract_intervals(lo, hi, covered):
    """Pieces of [lo, hi) not covered by the sorted disjoint list."""
    out = []
    cur = lo
    for s, e in covered:
        if e <= cur:
            continue
        if s >= hi:
            break
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return out


def _insert_interval(covered, lo, hi):
    covered.append((lo, hi))
    covered.sort()
    merged = []
    for s, e in covered:
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    covered[:] = merged


def plan_mirror_ranges(
    info, leaf_blocks, leaf_spans, floor=0, allow_stale=True
):
    """Pure decision core of the replica-plane assembly (range-based).

    ``info``: ``[(has, version, n_old, old_pid)]`` indexed by NEW rank
    (the all-gathered summary — identical on every rank, so this plan
    is too). ``leaf_blocks``: ``{path: fn(old_pid) -> (lo, hi)}`` — the
    dim-0 interval each OLD process owned (its ppermute replica covers
    its LEFT neighbor ``(pid - 1) % n_old``). ``leaf_spans``:
    ``{path: total_rows}``.

    Returns ``(target_v, n_old, {path: [(lo, hi, src_rank, kind)]})``
    with disjoint pieces covering ``[0, total)`` per path (kind 0 =
    the source rank's own block, 1 = its replica), or None:

    - the target version is the newest mirrored version; mirrors from
      an older refresh (a rank that somehow missed one) are excluded,
    - duplicate claims to one old pid keep the lowest new rank,
    - own blocks are preferred over replicas; within a kind the lowest
      rank wins — every rank computes the identical assignment,
    - replication across the old mesh (stage shards repeated over data
      groups) means ANY holder of a row range covers it, which is how
      a pp x dp job survives losing a whole pipe column.
    """
    have = [
        (rank, v, n, pid)
        for rank, (has, v, n, pid) in enumerate(info)
        if has
    ]
    if not have:
        return None
    target_v = max(v for _, v, _, _ in have)
    if not allow_stale and floor > target_v:
        return None
    n_olds = {n for _, v, n, _ in have if v == target_v}
    if len(n_olds) != 1:
        return None
    n_old = n_olds.pop()
    seen_pids = set()
    holders = []  # (new_rank, old_pid), lowest rank keeps a dup pid
    for rank, v, n, pid in sorted(have):
        if v == target_v and n == n_old and pid not in seen_pids:
            seen_pids.add(pid)
            holders.append((rank, pid))
    plan = {}
    for path, block_of in leaf_blocks.items():
        total = leaf_spans[path]
        candidates = [
            (0, rank, block_of(pid)) for rank, pid in holders
        ] + [
            (1, rank, block_of((pid - 1) % n_old))
            for rank, pid in holders
        ]
        covered = []
        pieces = []
        for kind, rank, (lo, hi) in sorted(
            candidates, key=lambda c: (c[0], c[1])
        ):
            for s, e in _subtract_intervals(lo, hi, covered):
                pieces.append((s, e, rank, kind))
                _insert_interval(covered, s, e)
        if covered != [(0, total)]:
            return None
        plan[path] = sorted(pieces)
    return target_v, n_old, plan


def _local_block(arr):
    """(rows ndarray, global row offset) of this process's contiguous
    slice of a row-sharded global array. Deduplicates shards by offset:
    a leaf replicated over part of the mesh (a P("pipe") stage subtree
    on a data x pipe mesh) presents the same rows on several local
    devices, which must not be concatenated twice."""
    by_start = {}
    for s in arr.addressable_shards:
        start = int(s.index[0].start or 0)
        if start not in by_start:
            by_start[start] = np.asarray(s.data)
    starts = sorted(by_start)
    rows = np.concatenate([by_start[s] for s in starts])
    span = sum(by_start[s].shape[0] for s in starts)
    if starts[-1] + by_start[starts[-1]].shape[0] - starts[0] != span:
        raise ValueError("non-contiguous local block")
    return rows, starts[0]


def _max_checkpoint_version(candidate_dirs):
    """Largest ckpt_v{N} among candidate directory paths (0 if none)."""
    import os
    import re

    best = 0
    for d in candidate_dirs or ():
        m = re.match(r"ckpt_v(\d+)$", os.path.basename(str(d)))
        if m:
            best = max(best, int(m.group(1)))
    return best


class PadDim0:
    """Marks a sharded spec whose leaves' dim 0 may be zero-PADDED up
    to the next multiple of the world's shard count, so non-divisor
    world sizes place cleanly (a kill 8 -> 7 keeps training instead of
    erroring). Only sound for leaves whose extra rows are INERT —
    embedding tables, whose rows beyond the declared vocab are never
    addressed (real vocab sizes like GPT-2's 50257 have no divisor
    structure, so Megatron-style padding is the only general answer).
    Leaves with structural dim-0 semantics (stacked pipeline stages)
    must NOT be marked: a zero stage would change the math, and their
    divisibility is kept by the membership layer's world-size rounding
    instead."""

    __slots__ = ("spec",)

    def __init__(self, spec):
        self.spec = spec


def collect_sharded_paths(param_specs):
    """Flatten a nested param_specs dict into {path tuple: PartitionSpec}.

    A ``"**"`` key makes its spec apply to EVERY leaf under the
    enclosing prefix (stored as ``prefix + ("**",)``): the stacked stage
    subtree of a pipeline (parallel/pipeline.py PipelinedStack) has many
    leaves of varying depth that all shard the same way, which per-leaf
    spec paths cannot express. :class:`PadDim0` markers are unwrapped
    (use :func:`collect_paddable_paths` to recover which spec paths
    carried one)."""
    paths = {}
    if not param_specs:
        return paths

    def walk(spec_tree, prefix):
        if hasattr(spec_tree, "items"):
            for k, sub in spec_tree.items():
                walk(sub, prefix + (k,))
        else:
            if isinstance(spec_tree, PadDim0):
                spec_tree = spec_tree.spec
            paths[prefix] = spec_tree

    walk(param_specs, ())
    return paths


def collect_paddable_paths(param_specs):
    """Spec paths whose leaves were marked :class:`PadDim0`."""
    paddable = set()
    if not param_specs:
        return paddable

    def walk(spec_tree, prefix):
        if hasattr(spec_tree, "items"):
            for k, sub in spec_tree.items():
                walk(sub, prefix + (k,))
        elif isinstance(spec_tree, PadDim0):
            paddable.add(prefix)

    walk(param_specs, ())
    return paddable


def dim0_shard_count(spec, axes):
    """How many ways a leaf's dim 0 splits on a mesh laid out ``axes``."""
    entry = spec[0] if spec is not None and len(spec) else None
    if entry is None:
        return 1
    axs = tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)
    count = 1
    for a in axs:
        count *= int(axes[a])
    return count


def padded_dim0(shape0, spec, axes):
    """dim 0 rounded UP to the next multiple of its shard count."""
    count = dim0_shard_count(spec, axes)
    return -(-int(shape0) // count) * count


def spec_path_matches(spec_path, leaf_names):
    """True when a collected spec path claims a leaf's tree path.

    Exact paths match by suffix (so optimizer slot trees, which nest the
    params structure under mu/nu/..., co-shard automatically). Subtree
    paths (ending in ``"**"``) match when their prefix appears as a
    contiguous run anywhere in the leaf path."""
    names = tuple(leaf_names)
    if spec_path and spec_path[-1] == "**":
        prefix = tuple(spec_path[:-1])
        if not prefix:
            return True
        span = len(prefix)
        return any(
            names[i : i + span] == prefix
            for i in range(len(names) - span + 1)
        )
    return names[-len(spec_path):] == tuple(spec_path)


def build_state_specs(ts, sharded_paths):
    """TrainState-shaped PartitionSpec pytree for the elastic step.

    Leaves whose tree path *ends with* a sharded path get that path's
    spec — matching both the parameters and their optimizer slots (optax
    moment trees nest the same sub-structure) — everything else ``P()``.
    """
    from elasticdl_tpu.common.pytree import key_path_names

    def spec_for(key_path, _leaf):
        names = key_path_names(key_path)
        for spec_path, spec in sharded_paths.items():
            if spec_path_matches(spec_path, names):
                return spec
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, ts)


def place_from_host_specs(mesh, tree, spec_tree):
    """Place a full host pytree on a (possibly multi-process) mesh per a
    matching spec pytree; each process materializes only its own
    devices' slices (``make_array_from_callback``)."""

    def put(x, spec):
        x = np.asarray(x)
        return jax.make_array_from_callback(
            x.shape,
            NamedSharding(mesh, spec),
            lambda idx, x=x: x[idx],
        )

    return jax.tree_util.tree_map(put, tree, spec_tree)


def optimizer_couples_leaves(optimizer):
    """Behavioral probe: does one leaf's update depend on ANOTHER leaf's
    gradient?

    On the sharded-state plane each rank holds different local table
    shards, so a cross-leaf transform (``optax.clip_by_global_norm`` is
    the common one) folds each rank's different shard gradients into a
    per-rank scale and silently desynchronizes the replicated
    parameters. Probing behavior instead of matching transform names
    catches every such transform, including ones inside ``optax.chain``
    or custom ``GradientTransformation``s. Probes a tiny 2-leaf tree:
    changing only leaf b's gradient must not change leaf a's update.
    """
    import jax.numpy as jnp

    probe = {
        "a": jnp.ones((4,), jnp.float32),
        "b": jnp.ones((4,), jnp.float32),
    }
    try:
        state = optimizer.init(probe)
        g_small = {
            "a": jnp.full((4,), 0.5, jnp.float32),
            "b": jnp.full((4,), 0.5, jnp.float32),
        }
        g_large = {
            "a": jnp.full((4,), 0.5, jnp.float32),
            "b": jnp.full((4,), 64.0, jnp.float32),
        }
        u1, _ = optimizer.update(g_small, state, probe)
        u2, _ = optimizer.update(g_large, state, probe)
    except Exception:
        # exotic optimizer the probe can't drive: let training proceed —
        # this check exists to catch the common silent footgun, not to
        # gate every optimizer shape
        logger.warning(
            "optimizer cross-leaf probe failed; skipping the sharded-"
            "plane coupling check",
            exc_info=True,
        )
        return False
    return not np.allclose(
        np.asarray(u1["a"]), np.asarray(u2["a"]), rtol=1e-6, atol=1e-8
    )


def make_elastic_train_step(
    module,
    loss_fn,
    optimizer,
    mesh,
    axis=None,
    precision=None,
    accum_steps=1,
    state_specs=None,
    remat=False,
):
    """Weighted lockstep step: ``(ts, features, labels, weights, epochs,
    rng) -> (ts', loss, n_active, epoch_consensus, kept)``: the new
    train state, and the step's receipt, four small outputs that are
    no part of it (``kept`` is :func:`kept_of` the new state, copies
    the next step does not donate).

    Works over ANY mesh axis layout: ``axis`` defaults to the mesh's
    full axis-name tuple, the batch/weights/epochs shard over the
    flattened device order, and reductions run over exactly the axes a
    leaf is NOT sharded over — so a ``("data", "pipe")`` mesh reduces a
    replicated leaf over both axes, a stage-sharded ``P("pipe")`` leaf
    over ``data`` only, and a vocab-sharded ``P("data", None)`` leaf
    over ``pipe`` only (its data-axis row gradients were already routed
    by the collective lookup's a2a backward).

    ``epochs`` is a global (n_devices,) int32 of each process's
    last-polled membership epoch; ``epoch_consensus`` is its in-step
    pmax — the skew-proof pause signal (see the per_device comment).

    ``weights`` is a global (n_devices,) 0/1 array — per-device
    participation. The local loss is scaled by ``w / psum(w)`` INSIDE the
    differentiated function, so every gradient contribution — including
    row gradients an ``all_to_all`` transpose routes to other devices'
    table shards — carries its device's weight at the source; replicated
    leaves then just psum. With zero live devices the state passes
    through unchanged and ``version`` does not advance, so drain-mode
    dummy steps are exact no-ops.

    ``state_specs``: optional pytree with the SAME treedef as the
    TrainState, each leaf a PartitionSpec — ``P()`` for replicated
    leaves, e.g. ``P("data", None)`` for HBM-sharded embedding tables
    (and their co-sharded optimizer slots), ``P("pipe")`` for stacked
    pipeline-stage subtrees. Sharded leaves enter the step as
    their local shard, and the module must use raw in-step collectives
    (nn/hbm_embedding.py ``collective=True``, pipeline.PipelinedStack
    ``collective=True``) since a nested shard_map is impossible here.
    Constraint: the optimizer must
    be per-leaf elementwise (sgd/momentum/adam/adagrad/... all are) —
    a transform that couples across leaves, e.g.
    ``optax.clip_by_global_norm``, would fold each device's DIFFERENT
    local table-shard gradient into a per-device scale and silently
    desynchronize the replicated parameters.

    ``precision``: a training.precision.Policy (or preset name); master
    weights, gradients, and the weighted psum math stay in
    ``param_dtype`` — only the forward/backward compute casts down.

    ``accum_steps > 1``: each device scans its local batch in
    microbatches before the weighted reduction (semantics of
    training/step.py:make_train_step accumulation; the participation
    weight applies to the accumulated mean, so elasticity/tail-batch
    weighting is unchanged). The trainer pads local rows to a multiple
    of ``accum_steps * local_devices``.
    """
    from elasticdl_tpu.training.precision import get_policy
    from elasticdl_tpu.training.step import make_remat_forward

    pol = get_policy(precision)
    forward = make_remat_forward(module, remat)
    if axis is None:
        axis = tuple(mesh.axis_names)
    axes = axis if isinstance(axis, tuple) else (axis,)

    def _is_sharded(spec):
        return spec is not None and any(a is not None for a in spec)

    def _unsharded_axes(spec):
        """Mesh axes a leaf is replicated over (its reduction axes)."""
        used = _spec_axes(spec)
        return tuple(a for a in axes if a not in used)

    def per_device(ts, features, labels, weights, epochs, rng):
        w = weights[0].astype(jnp.float32)
        # membership-epoch consensus rides the step: each process feeds
        # the epoch it last polled, the pmax tells EVERY member (at the
        # same step index — it is the same collective) the newest epoch
        # any member has seen. Pausing on this consensus at aligned sync
        # indices is skew-proof: polled-epoch observation happens at
        # different host iterations once deferred sync lets hosts run
        # ahead, and a member pausing early strands peers' in-flight
        # dispatched steps on a vanished rank.
        epoch_seen = jax.lax.pmax(epochs[0], axes)
        # decorrelate stochastic layers (dropout) across the batch shards
        rng = jax.random.fold_in(rng, jax.lax.axis_index(axes))
        # liveness (how many devices carried data) is separate from the
        # weighted denominator: tail batches contribute fractional weight
        n = jax.lax.psum((w > 0).astype(jnp.float32), axes)
        denom = jnp.maximum(jax.lax.psum(w, axes), 1e-6)
        scale = w / denom

        def grads_of(state, features_mb, labels_mb, rng_mb):
            def loss_of(p):
                if pol is not None:
                    p = pol.cast_to_compute(p)
                    features_c = pol.cast_to_compute(features_mb)
                else:
                    features_c = features_mb
                output, new_state = forward(
                    p, state, features_c, rng_mb
                )
                if pol is not None:
                    output = pol.cast_output(output)
                raw = loss_fn(output, labels_mb) + aux_loss_total(
                    new_state
                )
                # the weight rides the loss so AD distributes it to
                # every gradient contribution, local or routed
                return raw * scale, (raw, new_state)

            (_, (raw, new_state)), grads = jax.value_and_grad(
                loss_of, has_aux=True
            )(ts.params)
            return raw, grads, new_state

        if accum_steps == 1:
            loss, grads, new_state = grads_of(
                ts.state, features, labels, rng
            )
        else:
            loss, grads, new_state = accumulate_gradients(
                grads_of,
                ts.state,
                features,
                labels,
                rng,
                accum_steps,
                ts.params,
            )

        if state_specs is None:
            grad_specs = jax.tree_util.tree_map(lambda _: None, grads)
            state_spec_tree = jax.tree_util.tree_map(
                lambda _: None, new_state
            )
        else:
            grad_specs = state_specs.params
            state_spec_tree = state_specs.state

        def reduce_grad(g, spec):
            # reduce over exactly the axes the leaf replicates over:
            # all of them for dense leaves, none for a fully-sharded
            # table on a 1-axis mesh (weighting rode the loss, the a2a
            # backward already routed row gradients), the data axes for
            # a P("pipe") stage subtree (stage replicas across data
            # groups must agree)
            red = _unsharded_axes(spec)
            return jax.lax.psum(g, red) if red else g

        def wavg(x, spec):
            if _is_sharded(spec):
                return x  # per-shard state stays local
            if jnp.issubdtype(x.dtype, jnp.floating):
                return jax.lax.psum(x * w, axes) / denom
            return x  # int leaves (counters) advance identically everywhere

        # the two scopes are how a traced run tells these ops from the
        # forward and backward passes, which the transformations name
        # themselves (utils/step_ops.py); metadata only, the compiled
        # program and its cache key are the same without them
        with jax.named_scope(step_ops.REDUCE_SCOPE):
            grads = jax.tree_util.tree_map(reduce_grad, grads, grad_specs)
            loss = jax.lax.psum(loss * scale, axes)
            new_state = jax.tree_util.tree_map(
                wavg, new_state, state_spec_tree
            )

        live = n > 0

        def select(new, old):
            return jnp.where(live, new, old)

        with jax.named_scope(step_ops.OPTIMIZER_SCOPE):
            updates, opt_state = optimizer.update(
                grads, ts.opt_state, ts.params
            )
            params = optax.apply_updates(ts.params, updates)
            new_ts = TrainState(
                params=jax.tree_util.tree_map(select, params, ts.params),
                state=jax.tree_util.tree_map(select, new_state, ts.state),
                opt_state=jax.tree_util.tree_map(
                    select, opt_state, ts.opt_state
                ),
                version=ts.version + live.astype(jnp.int32),
            )
        return new_ts, loss, n, epoch_seen, kept_of(new_ts)

    if state_specs is None:
        ts_spec = kept_spec = P()
    else:
        ts_spec = state_specs
        kept_spec = kept_of(state_specs)
    # batch/weights/epochs shard dim 0 over the FLATTENED device order,
    # so each process's rows land on its own devices whatever the mesh
    # shape (same layout the trainer places them with)
    row_spec = row_partition_spec(mesh)
    sharded = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(ts_spec, row_spec, row_spec, row_spec, row_spec, P()),
        out_specs=(ts_spec, P(), P(), P(), kept_spec),
        check_vma=False,
    )
    # the state is donated where no peer can fail the collective, and
    # kept where survivors must re-form from it (see state_donation)
    return jax.jit(sharded, donate_argnums=state_donation(mesh))


def specs_use_axis(sharded_paths, axis):
    """True when any collected spec shards over ``axis`` — the pjit
    dense-path trigger is ``specs_use_axis(paths, "model")``."""
    return any(
        axis in _spec_axes(spec) for spec in (sharded_paths or {}).values()
    )


def derive_model_profile(abstract_ts, state_specs):
    """:class:`layout_solver.ModelProfile` from the abstract TrainState
    and its spec tree — the layout solver's deterministic model input.

    Everything here is a function of the model/optimizer structure
    alone (shapes, dtypes, which leaves shard over ``model``), so every
    process derives the identical profile and the solver's establish
    picks agree without any exchange. The flop/activation terms are
    RELATIVE proxies (6*N flops per example row, activation volume
    proportional to the total model-sharded width); telemetry
    calibration supplies real constants when ordering alone isn't
    enough (layout_solver module docstring)."""
    replicated_bytes = 0.0
    tp_bytes = 0.0
    model_dims = []

    def visit(leaf, spec):
        nonlocal replicated_bytes, tp_bytes
        shape = tuple(leaf.shape)
        nbytes = float(np.prod(shape)) * np.dtype(leaf.dtype).itemsize
        axes = tuple(spec) if spec is not None else ()
        if "model" in axes:
            tp_bytes += nbytes
            model_dims.append(int(shape[axes.index("model")]))
        else:
            replicated_bytes += nbytes

    jax.tree_util.tree_map(visit, abstract_ts, state_specs)
    param_count = sum(
        float(np.prod(tuple(leaf.shape)))
        for leaf in jax.tree_util.tree_leaves(abstract_ts.params)
    )
    return layout_solver.ModelProfile(
        replicated_bytes=replicated_bytes,
        tp_bytes=tp_bytes,
        activation_bytes_per_row=4.0 * float(sum(model_dims)),
        flops_per_row=6.0 * param_count,
        tp_degrees=tp_degree_candidates(model_dims),
    )


def make_pjit_train_step(
    module,
    loss_fn,
    optimizer,
    mesh,
    state_specs,
    precision=None,
    remat=False,
):
    """GSPMD weighted lockstep step — the pjit dense plane.

    Same call signature and external semantics as
    :func:`make_elastic_train_step` (``(ts, features, labels, weights,
    epochs, rng) -> (ts', loss, n_active, epoch_consensus, kept)``), but the
    body is GLOBAL-semantics math under ``jax.jit`` with
    ``NamedSharding`` out-shardings: XLA partitions the dense model per
    the spec tree and inserts the tensor-parallel collectives itself —
    the "Scalable Training of Language Models using JAX pjit and
    TPUv4" blueprint (PAPERS.md 2204.06514) inside the elastic world.
    The module is the PLAIN flax model (no raw in-step collectives, no
    collective zoo form): correctness is placement-independent, so the
    same module trains replicated or 2D ``data x model`` sharded and
    the specs only decide layout.

    Elasticity semantics carried over from the shard_map step:

    - per-device participation ``weights`` scale each device block's
      loss contribution INSIDE the differentiated function
      (:func:`training.step.block_device_losses` recovers the
      per-device granularity from the global batch), so tail batches
      and drain-mode zero-weight devices weight gradients identically
      to the replicated arm;
    - ``epochs``' max is the membership-epoch consensus (the global
      ``jnp.max`` IS the pmax — same collective, spelled globally);
    - with zero live devices the state passes through unchanged and
      ``version`` does not advance.

    Differences, by design: dropout draws ONE global rng (no
    per-device fold-in — parity for stochastic layers is per-batch,
    not per-device), mutable model state (batch stats) updates from
    the full global batch including weight-0 devices' stale rows (use
    the replicated plane for batch-stat models), and the MoE aux loss
    adds once globally rather than per device. Donation follows the
    elastic step's rule (:func:`state_donation`): the state is donated
    on a process-local mesh and kept, for re-forms after a failed
    collective, on one that spans processes.
    """
    from elasticdl_tpu.training.precision import get_policy
    from elasticdl_tpu.training.step import make_remat_forward

    pol = get_policy(precision)
    forward = make_remat_forward(module, remat)
    n_dev = mesh.devices.size
    rep = NamedSharding(mesh, P())
    ts_shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), state_specs
    )

    def step(ts, features, labels, weights, epochs, rng):
        w = weights.astype(jnp.float32)  # (n_dev,)
        n = jnp.sum((w > 0).astype(jnp.float32))
        denom = jnp.maximum(jnp.sum(w), 1e-6)
        epoch_seen = jnp.max(epochs)

        def loss_of(p):
            if pol is not None:
                p = pol.cast_to_compute(p)
                features_c = pol.cast_to_compute(features)
            else:
                features_c = features
            output, new_state = forward(p, ts.state, features_c, rng)
            if pol is not None:
                output = pol.cast_output(output)
            dev_raw = block_device_losses(loss_fn, output, labels, n_dev)
            # the weight rides the loss so AD distributes it to every
            # gradient contribution (the same trick as the shard_map
            # step — there via scale, here via the weighted block sum)
            raw = jnp.sum(dev_raw * w) / denom + aux_loss_total(new_state)
            return raw, new_state

        (loss, new_state), grads = jax.value_and_grad(
            loss_of, has_aux=True
        )(ts.params)
        updates, opt_state = optimizer.update(grads, ts.opt_state, ts.params)
        params = optax.apply_updates(ts.params, updates)
        live = n > 0

        def select(new, old):
            return jnp.where(live, new, old)

        new_ts = TrainState(
            params=jax.tree_util.tree_map(select, params, ts.params),
            state=jax.tree_util.tree_map(select, new_state, ts.state),
            opt_state=jax.tree_util.tree_map(
                select, opt_state, ts.opt_state
            ),
            version=ts.version + live.astype(jnp.int32),
        )
        return new_ts, loss, n, epoch_seen, kept_of(new_ts)

    # out-shardings PIN the layout: without them XLA could silently
    # re-replicate a sharded parameter on the way out and the "bigger
    # than one device" property would evaporate after the first step
    return jax.jit(
        step,
        out_shardings=(ts_shardings, rep, rep, rep, kept_of(ts_shardings)),
        donate_argnums=state_donation(mesh),
    )


class _BatchFeeder:
    """One-slot async H2D stager (the compile plane's step-overlap leg).

    The worker hands the NEXT batch over right before a blocking sync
    step, and this daemon thread pads + places it onto the mesh while
    the training thread sits in the device->host fetch — so the hot
    loop never serializes H2D behind D2H. Single producer, single
    consumer (both the training thread); the worker thread only runs
    the placement callable. A placement that errors or outlives
    ``take``'s wait degrades to inline placement in the caller — the
    feeder is an overlap optimization, never a correctness dependency.
    """

    def __init__(self, place_fn, name="edl-h2d-feeder"):
        self._place_fn = place_fn
        self._lock = threading.Lock()
        self._work = None  # (token, payload) awaiting placement
        self._token = None  # token of the staged (completed) result
        self._result = None
        self._staged_token = None  # token most recently handed to stage()
        self._ready = threading.Event()
        self._wake = threading.Event()
        self._cancel = threading.Event()
        self._broken = False
        self._thread = threading.Thread(
            target=self._run, name=name, daemon=True
        )
        self._thread.start()

    def stage(self, token, payload):
        """Queue one placement; a newer stage replaces an unstarted one."""
        if self._broken or self._cancel.is_set():
            return
        with self._lock:
            self._work = (token, payload)
            self._staged_token = token
            self._ready.clear()
        self._wake.set()

    def _run(self):
        while not self._cancel.is_set():
            if not self._wake.wait(timeout=0.2):
                continue
            with self._lock:
                work, self._work = self._work, None
                self._wake.clear()
            if work is None:
                continue
            token, payload = work
            try:
                result = self._place_fn(*payload)
            except Exception:
                # surfaced as a take() miss; the caller re-places inline
                # and gets the real error there if it reproduces
                logger.warning(
                    "async batch placement failed; falling back to "
                    "inline placement",
                    exc_info=True,
                )
                result = None
            with self._lock:
                self._token, self._result = token, result
                self._ready.set()

    def take(self, token, timeout=30.0, should_abort=None):
        """The staged placement for ``token``, or None (not staged /
        superseded / failed / timed out / aborted). The wait polls
        ``should_abort`` (the trainer's wedge-escape probe) in short
        slices: a placement wedged on a dead transport must not hold
        the training thread past the world moving on. A timeout or an
        abort marks the feeder broken — a wedged device transport must
        not be probed twice."""
        with self._lock:
            if self._broken or self._staged_token != token:
                return None
        deadline = time.monotonic() + timeout
        while not self._ready.wait(0.5):
            aborted = False
            if should_abort is not None:
                try:
                    aborted = should_abort()
                except Exception:
                    logger.debug(
                        "feeder abort probe failed", exc_info=True
                    )
            if aborted or time.monotonic() >= deadline:
                self._broken = True
                logger.warning(
                    "async batch placement still running (%s); feeder "
                    "disabled for this world",
                    "world moved on" if aborted else "timeout",
                )
                return None
        with self._lock:
            if self._token != token:
                return None
            result, self._result = self._result, None
            self._token = None
            self._staged_token = None
            return result

    def shutdown(self, timeout=5.0):
        self._cancel.set()
        self._wake.set()
        t = self._thread
        if t.is_alive():
            t.join(timeout=timeout)


class ElasticDPTrainer:
    """Per-process handle on the global elastic DP training plane."""

    def __init__(
        self,
        module,
        loss_fn,
        optimizer,
        seed=0,
        precision=None,
        accum_steps=1,
        distributed_builder=None,
        restore_provider=None,
        remat=False,
        mesh_axes_fn=None,
        layout_planner=None,
    ):
        """``distributed_builder``: optional ``mesh -> (module,
        param_specs)`` hook for HBM-sharded parameters (the zoo's
        ``build_collective_model`` + ``param_shardings``). Sharded
        leaves cannot ride the survivor re-broadcast (a dead process's
        shards are gone), so re-forms restore the WHOLE state from
        ``restore_provider()`` (the latest sharded checkpoint directory,
        or None) — recovery granularity is the checkpoint cadence; with
        no checkpoint the state re-initializes (the reference lost its
        Redis-resident tables entirely on the same failure,
        reference master/embedding_service.py).

        ``mesh_axes_fn``: optional ``n_devices -> {axis: size} | None``
        (the zoo's ``mesh_axes`` hook) — the elastic world's mesh
        layout, e.g. ``{"data": n // S, "pipe": S}`` for a pipelined
        model. None/absent means the flat 1-axis ``("data",)`` mesh.
        Raises at establish if the world size doesn't fit (the
        membership layer's world_size_multiple exists to prevent such
        worlds from forming).

        ``layout_planner``: optional
        :class:`layout_solver.LayoutPlanner` — resizes then RE-SOLVE
        the dp x tp layout instead of replaying the static
        ``mesh_axes_fn`` (which becomes the planner's fallback until
        the first establish derives the model profile). pjit-dense jobs
        only; see docs/distributed.md "Layout re-solve"."""
        self._module = module
        self._loss_fn = loss_fn
        self._optimizer = optimizer
        self._coupling_checked = False
        self._seed = seed
        self._precision = precision
        self._remat = remat
        self._accum_steps = max(1, accum_steps)
        self._builder = distributed_builder
        self._planner = layout_planner
        if layout_planner is not None:
            if layout_planner.fallback_axes_fn is None:
                layout_planner.fallback_axes_fn = mesh_axes_fn
            mesh_axes_fn = layout_planner.axes_for
        self._mesh_axes_fn = mesh_axes_fn
        self.restore_provider = restore_provider
        self._sharded_paths = {}
        self._paddable_spec_paths = set()
        self._logical_dim0 = {}  # padded leaves: path names -> true dim0
        self._state_specs = None
        # pjit dense plane: specs shard over the "model" axis, the
        # PLAIN module trains under make_pjit_train_step, and resizes
        # re-solve the layout by moving state directly between old and
        # new NamedShardings (docs/distributed.md)
        self._pjit_dense = False
        self._placed_epoch = None  # backend epoch the state was placed in
        self._mesh = None
        self._spec = None
        self._ts = None
        # last fetch-validated device state; kept only on a mesh that
        # spans processes (see _keep_checked)
        self._checked_ts = None
        self._host_ts = None  # latest host snapshot (re-form source)
        self._step_fn = None
        self._eval_fn = None  # in-plane eval forward (built on demand)
        self._gather_fns = {}  # cached per-width info gathers
        self._host_step = 0
        self._last_local = None  # (features, labels) for weight-0 steps
        # steps dispatched and not yet validated, oldest first: each
        # one's receipt on the device (loss, n_active, epoch consensus,
        # kept_of its state) and whether it carried data (see settle)
        self._in_flight = []
        self._in_flight_overflowed = False  # warn once per overflow
        # the state the newest dispatched step was given, where that
        # step did not donate it: what settle(lag=1) has validated
        self._ts_behind = None
        self._settled_losses = []  # validated, not yet handed out
        # of the newest VALIDATED step: the newest epoch any member had
        # seen, the devices that carried data, and the host copy of
        # what it kept (kept_of): its version, its receipt_state
        self.epoch_consensus = None
        self.n_active = None
        self._validated_version = None
        self._validated_state = {}
        # in-memory replica plane (sharded jobs): see ShardMirror
        self.mirror_steps = 0  # 0 disables; worker sets from its flag
        self._mirror = None
        self._mirror_perm_fn = None
        self._last_mirror_version = -1
        # escapable-wait hook (see _escapable): worker sets it to a
        # "has the master already bumped past my epoch?" probe
        self.abort_check = None
        self._wedged = False
        # -- compile-plane fast path (parallel/compile_plane.py) --------
        # executable reuse across establishes: re-forming at a
        # previously-seen (mesh, step-config) hands back the same jitted
        # callable, so jax's aval cache dispatches without retracing
        self.compile_cache_enabled = True
        self._exec_cache = compile_plane.ExecutableCache()
        self.compile_stats = self._exec_cache.stats
        self._step_entry = None  # cache entry backing _step_fn (or None)
        # speculative AOT compiles for likely next world sizes; the
        # worker opts in and feeds membership hints
        self.speculative_compile = False
        self._spec_compiler = None
        self._spec_example = None  # host example batch (abstract args)
        # worker's fixed minibatch: lets speculation derive batch shapes
        self.default_minibatch_size = None
        # step overlap: async H2D stager
        self._feeder = None

    @property
    def mesh(self):
        return self._mesh

    @property
    def version(self):
        if self._ts is None:
            return -1
        # escapable: a peer loss can wedge any device interaction
        return int(
            self._escapable(lambda: host_copy(self._ts.version))
        )

    @property
    def validated_version(self):
        """The version of the newest VALIDATED step's state, from its
        receipt (:meth:`settle`): what a task report carries, since a
        sync point reports with the newest step still on the device and
        :attr:`version` would wait for it. Before a world's first
        validation, the placed state's own."""
        if self._validated_version is None:
            return self.version
        return self._validated_version

    @property
    def has_state(self):
        """Cheap liveness check (no device->host transfer)."""
        return self._ts is not None or self._host_ts is not None

    @property
    def is_sharded(self):
        """True when parameters shard over the mesh (HBM tables)."""
        return bool(self._sharded_paths) or self._builder is not None

    def state_device_coverage(self):
        """The fewest distinct local devices any one train-state leaf
        has a shard on (shard metadata only, nothing moves); None
        between worlds. Replicated and sharded leaves alike sit on
        every device of the mesh, so anything under the mesh's local
        device count means state was left on part of it."""
        if self._ts is None:
            return None
        return min(
            len({shard.device for shard in leaf.addressable_shards})
            for leaf in jax.tree_util.tree_leaves(self._ts)
        )

    @property
    def steps_in_flight(self):
        """Steps dispatched that no fetch has waited for yet."""
        return len(self._in_flight)

    def routing_state(self):
        """The host copy of the model's expert-routing state
        (``parallel/expert.MOE_STATE_COLLECTION``: a selection bias and
        wrapping assignment counters an expert layer, a few hundred
        bytes) as the newest VALIDATED step left it, or None before a
        world's first validation and for a model that keeps none. It
        came with that step's receipt (:meth:`settle`): asking moves
        nothing and waits for nothing."""
        return self._validated_state.get(MOE_STATE_COLLECTION)

    def aux_losses(self):
        """What the model wrote to its ``aux_loss`` collection in the
        newest VALIDATED step (``training/step.py``: every step builder
        adds the collection to the loss), ``{leaf's own name: value}``,
        leaves of one name summed; nothing before a world's first
        validation and for a model that writes none. From that step's
        receipt, as :meth:`routing_state`."""
        parts = {}
        held = self._validated_state.get(AUX_LOSS_COLLECTION, {})
        for path, leaf in jax.tree_util.tree_leaves_with_path(held):
            name = str(getattr(path[-1], "key", path[-1]))
            parts[name] = parts.get(name, 0.0) + float(np.sum(leaf))
        return parts

    def embedding_overflow_total(self):
        """The a2a capacity overflow the embedding layers had counted
        by the newest VALIDATED step (``nn/hbm_embedding.py``
        ``a2a_overflow_total``), None for a model that counts none:
        from that step's receipt, as :meth:`routing_state`."""
        return a2a_overflow_total(self._validated_state)

    def _most_on_a_device(self, stat):
        """The largest ``memory_stats()[stat]`` over the mesh's local
        devices; None between worlds and where the backend reports
        none (the CPU)."""
        if self._mesh is None:
            return None
        read = [
            (d.memory_stats() or {}).get(stat)
            for d in self._mesh.local_devices
        ]
        return max((b for b in read if b is not None), default=None)

    def device_bytes_in_use(self):
        """The most device memory any local device of the mesh holds
        right now (``bytes_in_use``): read as establish returns, it
        says how many copies of the train state the placement left
        behind. None as :meth:`_most_on_a_device`."""
        return self._most_on_a_device("bytes_in_use")

    def peak_hbm_bytes(self):
        """The most device memory any local device of the mesh has held
        since the process started (``memory_stats()``'s
        ``peak_bytes_in_use``); None between worlds and where the
        backend reports none (the CPU)."""
        return self._most_on_a_device("peak_bytes_in_use")

    def _build_init_ts(self, example_batch):
        features = example_batch[0]
        # slice before transfer: a device leaf would otherwise D2H the
        # full batch just to keep one example (same fix as
        # AllReduceTrainer.init_from_batch)
        host_one = jax.tree_util.tree_map(
            lambda x: np.asarray(x[:1]), features
        )

        def build():
            variables = init_variables(
                self._module, jax.random.PRNGKey(self._seed), host_one
            )
            params, state = split_variables(variables)
            return TrainState.create(params, state, self._optimizer)

        return build

    def _host_init_ts(self, example_batch):
        """Deterministic full host init (identical on every process)."""
        return host_copy(self._build_init_ts(example_batch)())

    def _abstract_ts(self, example_batch):
        """ShapeDtypeStruct TrainState — treedef/shapes without
        materializing any parameter values."""
        return jax.eval_shape(self._build_init_ts(example_batch))

    def establish(self, spec, example_batch=None):
        """Join ``spec``'s world and (re)place train state on its mesh.

        ``example_batch`` is required the first time (state init); on
        re-forms the previous host snapshot is re-broadcast, with rank 0
        as the source of truth. Sharded-parameter jobs instead restore
        from the latest checkpoint on EVERY establish (see __init__).
        """
        import time as _time

        # compile-plane helpers target the OLD backend: a speculative
        # compile or an async placement racing the teardown below would
        # wedge against dying devices — stop them first (edlint R4
        # ownership; threads are daemons, a stuck C++ compile is
        # abandoned safely)
        self._shutdown_compile_helpers()
        t0 = _time.time()
        old_layout = self._layout_fields()
        # the planner must answer from the profile on EVERY process
        # from its very first establish (see _maybe_derive_profile),
        # so derive it before the mesh below is laid out
        self._maybe_derive_profile(example_batch)
        profiling.events.emit(
            "resize_begin",
            epoch=spec.epoch,
            rank=spec.process_id,
            world_size=spec.num_processes,
            layout=old_layout,
        )
        distributed.ensure_world(spec)
        t_world = _time.time()
        self._spec = spec
        self._mesh = build_world_mesh(self._mesh_axes_fn)
        # mesh changed: drop EVERY cached jitted callable bound to the
        # old mesh before anything below (the establish-time
        # _replicated_source_rank/_gather_mirror_info all-gathers) can
        # run — a cached fn executed against the dead world's mesh
        # would wedge or corrupt the re-form
        self._mirror_perm_fn = None
        self._eval_fn = None
        self._gather_fns = {}
        self._wedged = False  # fresh backend: device fetches are safe again
        if self._builder is not None:
            self._module, param_specs = self._builder(self._mesh)
            self._sharded_paths = collect_sharded_paths(param_specs)
            self._paddable_spec_paths = collect_paddable_paths(
                param_specs
            )
        self._pjit_dense = specs_use_axis(self._sharded_paths, "model")
        if self._pjit_dense and self._accum_steps > 1:
            raise ValueError(
                "accum_steps > 1 is not supported on the pjit dense "
                "plane yet: global-batch microbatching would regroup "
                "rows across devices and change the weighted-step "
                "semantics — use the replicated plane, or accum_steps=1"
            )
        self._check_optimizer_coupling()
        t_init = t_world
        if self._sharded_paths:
            self._establish_sharded(example_batch)
            t_init = _time.time()  # restore/assembly/init, all of it
        else:
            if example_batch is None and self._host_ts is None:
                raise ValueError(
                    "first establish() needs an example batch"
                )
            # who actually holds replicated state? The broadcast adopts
            # the LOWEST such rank's copy; a fresh joiner then offers a
            # zeros stand-in built from eval_shape (milliseconds)
            # instead of paying a full real host init (~11 s measured
            # for the promoted-standby establish) that
            # the broadcast would overwrite anyway. Only when NOBODY
            # has state (first formation, or every process died) does
            # each member real-init — deterministically identical, so
            # rank 0's copy is the same init everywhere.
            source = self._replicated_source_rank()
            if source < 0:
                if self._host_ts is None:
                    self._host_ts = self._host_init_ts(example_batch)
                offer, source = self._host_ts, 0
            elif self._host_ts is not None:
                offer = self._host_ts
            else:
                abstract = self._abstract_ts(example_batch)
                offer = jax.tree_util.tree_map(
                    lambda leaf: np.zeros(leaf.shape, leaf.dtype),
                    abstract,
                )
            t_init = _time.time()
            if self._mesh.is_multi_process:
                self._ts = broadcast_from_device0(
                    self._mesh, offer, source_process=source
                )
            else:
                # nobody to adopt from: the state goes onto the mesh
                # once, where the broadcast would hold it twice
                self._ts = place_from_host(self._mesh, offer)
        t_place = _time.time()
        self._check_routing_state()
        self._keep_checked(self._ts)
        self._placed_epoch = distributed.backend_epoch()
        self._spec_example = example_batch or self._last_local
        with profiling.span("elastic/establish/compile"):
            cache_hit = self._acquire_step_fn()
        t_compile = _time.time()
        logger.info(
            "establish timing: world %.1fs, init %.1fs, place %.1fs, "
            "compile %.1fs (%s)",
            t_world - t0,
            t_init - t_world,
            t_place - t_init,
            t_compile - t_place,
            "cache hit" if cache_hit else "cache miss",
        )
        compile_phase = "cache_hit" if cache_hit else "cache_miss"
        profiling.events.emit(
            "resize_end",
            epoch=spec.epoch,
            rank=spec.process_id,
            world_size=spec.num_processes,
            world_s=round(t_world - t0, 3),
            init_s=round(t_init - t_world, 3),
            place_s=round(t_place - t_init, 3),
            compile_s=round(t_compile - t_place, 3),
            compile_phase=compile_phase,
            cache_hit=bool(cache_hit),
            state_device_bytes=self.device_bytes_in_use(),
            resize_layout={
                "old": old_layout,
                "new": self._layout_fields(),
            },
        )
        _RESIZE_PAUSE.observe(
            t_compile - t0, compile_phase=compile_phase
        )
        self._start_speculative_compiler()
        if self.mirror_enabled():
            # every rank reaches this point during formation, so the
            # refresh collective is aligned; it also resets
            # _last_mirror_version identically on every rank (joiners
            # included), keeping the cadence predicate global. A
            # FAILED refresh (a peer death racing this formation — the
            # collective fails on every rank together) must not crash
            # the worker out of an otherwise-recoverable establish:
            # swallow it, and advance the cadence marker so the ranks'
            # next-refresh predicate stays aligned whatever mix of
            # old mirrors they keep (the planner version-filters stale
            # ones); the broken world surfaces at the first step and
            # takes the ordinary recovery path
            try:
                self.refresh_mirror()
            except Exception:
                logger.warning(
                    "establish-tail replica refresh failed; the next "
                    "cadence point (or re-form) retries",
                    exc_info=True,
                )
                try:
                    self._last_mirror_version = self.version
                except Exception:
                    # device also wedged: the step failure owns it
                    logger.debug(
                        "cadence marker refresh failed too",
                        exc_info=True,
                    )
        logger.info(
            "elastic plane established: epoch=%d rank=%d/%d devices=%d%s",
            spec.epoch,
            spec.process_id,
            spec.num_processes,
            self._mesh.devices.size,
            " (sharded params)" if self._sharded_paths else "",
        )

    def _layout_fields(self):
        """``{"dp", "tp", "microbatch"}`` of the CURRENT mesh — the
        ``resize_layout`` event payload (None before any establish).
        dp/tp come from the live mesh shape, so the fields are truthful
        whether a planner, a static hook, or the flat default laid the
        world out."""
        if self._mesh is None:
            return None
        shape = dict(self._mesh.shape)
        mb = None
        if (
            self._planner is not None
            and self._planner.last_plan is not None
        ):
            mb = int(self._planner.last_plan.layout.microbatch)
        elif self.default_minibatch_size:
            mb = int(self.default_minibatch_size)
        return {
            "dp": int(shape.get("data", self._mesh.devices.size)),
            "tp": int(shape.get("model", 1)),
            "microbatch": mb,
        }

    def _maybe_derive_profile(self, example_batch):
        """Feed the layout planner its model profile BEFORE the first
        mesh is laid out. Determinism is the point: ``axes_for`` must
        answer from the profile on EVERY process from its very first
        establish — if a fresh joiner solved from the static fallback
        while survivors solved from a profile, the consensus world
        would form over diverging meshes. The probe is mesh-free
        (builder(None) — the same convention the worker's pjit-dense
        probe uses) and abstract (eval_shape): no device work, and the
        numbers are a pure function of the model structure."""
        planner = self._planner
        if planner is None or planner.profile is not None:
            return
        if self._builder is None:
            return
        example = (
            example_batch
            if example_batch is not None
            else self._last_local
        )
        if example is None:
            return
        try:
            _, param_specs = self._builder(None)
            sharded = collect_sharded_paths(param_specs)
            if not specs_use_axis(sharded, "model"):
                return
            abstract = self._abstract_ts(example)
            specs = build_state_specs(abstract, sharded)
            planner.set_profile(derive_model_profile(abstract, specs))
        except Exception:
            logger.warning(
                "layout-planner profile derivation failed; the static "
                "mesh_axes fallback stays in effect",
                exc_info=True,
            )

    def _check_optimizer_coupling(self):
        """Refuse cross-leaf optimizers for sharded-parameter jobs.

        Runs at the FIRST establish, after ``ensure_world`` — the probe
        executes real (tiny) JAX computation, and any JAX computation
        before ``jax.distributed.initialize`` would pin the backend and
        make the world formation itself fail. Once per trainer: the
        optimizer doesn't change across re-forms."""
        if self._coupling_checked or not self._sharded_paths:
            return
        self._coupling_checked = True
        if not optimizer_couples_leaves(self._optimizer):
            return
        import os

        if os.environ.get("EDL_ALLOW_CROSS_LEAF_OPT"):
            logger.warning(
                "cross-leaf optimizer on the sharded plane allowed by "
                "EDL_ALLOW_CROSS_LEAF_OPT=1; replicated parameters may "
                "silently desynchronize"
            )
            return
        # fail before the first step, not N steps into silent divergence
        raise ValueError(
            "the optimizer couples gradients across leaves (e.g. "
            "optax.clip_by_global_norm) but this job shards parameters "
            "across ranks: each rank would fold its own DIFFERENT local "
            "table-shard gradients into the coupled quantity and the "
            "replicated parameters would silently desynchronize. Use "
            "per-leaf transforms instead (e.g. optax.clip / "
            "optax.adaptive_grad_clip), or set "
            "EDL_ALLOW_CROSS_LEAF_OPT=1 if the coupling is known to "
            "exclude the sharded leaves."
        )

    def _check_routing_state(self):
        """Refuse a model with held-share expert layers
        (``parallel/expert.MOE_STATE_COLLECTION`` in its state) on a
        mesh of more than one device. The layer counts the assignments
        of its own device's tokens, and the step's weighted average
        leaves integer state as each device has it ("counters advance
        identically everywhere"): the replicas' counters and selection
        biases would drift apart unseen, and :meth:`routing_state`
        would read one device's. Until the counts are summed over the
        data axes inside the step, fail before the first one."""
        from elasticdl_tpu.parallel.expert import MOE_STATE_COLLECTION

        state = self._ts.state
        if (
            self._mesh.size > 1
            and isinstance(state, dict)
            and MOE_STATE_COLLECTION in state
        ):
            raise NotImplementedError(
                "this model keeps expert-routing state (%r) and the mesh "
                "has %d devices: each device would count only its own "
                "tokens' assignments, so the selection bias and the "
                "routing counters would differ between replicas. The "
                "held-share expert layer runs on a mesh of one device "
                "(--num_workers 1 on a one-chip host) until its counts "
                "are summed over the data axes."
                % (MOE_STATE_COLLECTION, self._mesh.size)
            )

    # -- compile-plane fast path (parallel/compile_plane.py) ---------------

    def _step_config_signature(self, state_specs):
        """Everything the step builder closes over besides the mesh:
        two cache entries may share an executable only when ALL of it
        matches (specs included — a stale spec tree would shard-map the
        state wrong, not just run slow)."""
        return (
            id(self._module),
            id(self._optimizer),
            id(self._loss_fn),
            id(self._precision),
            int(self._accum_steps),
            str(self._remat),
            # the pjit dense plane builds a DIFFERENT step callable for
            # the same (module, specs): the flag must key the cache
            bool(self._pjit_dense),
            compile_plane.spec_signature(state_specs),
        )

    def _build_step_fn(self, mesh, state_specs):
        if self._pjit_dense:
            return make_pjit_train_step(
                self._module,
                self._loss_fn,
                self._optimizer,
                mesh,
                state_specs,
                precision=self._precision,
                remat=self._remat,
            )
        return make_elastic_train_step(
            self._module,
            self._loss_fn,
            self._optimizer,
            mesh,
            precision=self._precision,
            accum_steps=self._accum_steps,
            state_specs=state_specs,
            remat=self._remat,
        )

    def _acquire_step_fn(self):
        """Install the train step for the current mesh, reusing a cached
        executable when this (mesh, step-config) was seen before.
        Returns True on a cache hit. The cached callable is the SAME
        jitted object as last time, so a repeat establish at a
        previously-seen world size dispatches straight through jax's
        aval cache — no retrace, no recompile; a changed batch shape
        (e.g. a different minibatch padding) still misses that aval
        cache and compiles correctly instead of reusing a stale
        executable."""
        key = (
            compile_plane.mesh_signature(self._mesh),
            self._step_config_signature(self._state_specs),
        )
        entry = (
            self._exec_cache.get(key)
            if self.compile_cache_enabled
            else None
        )
        hit = entry is not None
        if entry is None:
            step = self._build_step_fn(self._mesh, self._state_specs)
            if self.compile_cache_enabled:
                entry = self._exec_cache.put(key, step)
            else:
                self._step_entry = None
                self._step_fn = step
                return False
        self._step_entry = entry
        self._step_fn = entry.step_fn
        return hit

    def example_features(self):
        """The features of the batch the built step takes (its shapes
        are what matter), or None before any batch was seen."""
        example = self._spec_example or self._last_local
        return example[0] if example else None

    def describe_step(self):
        """What the step this establish built contains, read off its
        own trace and lowering rather than off the flags that asked for
        it: the Pallas kernels in the jaxpr, by name, and how many of
        the calls are interpreted; and the Mosaic custom calls in the
        lowered module, by kernel name, and its triangular solves (on
        the chip an explicit inversion each: ops/kda.py); and how many
        of the module's inputs it donates (the state's leaves on a
        process-local mesh,
        none on one that spans processes: :func:`state_donation`); and,
        where it holds the flash kernels, how many steps their grids
        take, how many of those have no tile to compute and how many
        of the forward calls keep their row sums by lanes
        (``flash_grid_steps``, ``flash_grid_steps_empty``,
        ``flash_fwd_lane_sums``).
        Asked BEFORE the first step it
        costs about nothing: jax caches the trace and the lowering, and
        the step's own first call reuses both (CPU, 8 layers: 4.4 s
        here + 5.5 s first step, against a 10.3 s first step alone).
        Asked later it would trace again, so callers ask once, right
        after establish.

        In a traced run (``EDL_PROFILE_DIR``) and only there it also
        compiles that lowering and writes the compiled step's ops by
        class beside the trace (:meth:`_step_ops_facts`)."""
        args = self._abstract_step_args(
            self._mesh, self._spec_example, self._state_specs
        )
        with self._mesh:
            traced = self._step_fn.trace(*args)
            jaxpr_text = str(traced.jaxpr)
            lowered = traced.lower()
            lowered_text = lowered.as_text()
        trace_dir = profiling.profile_dir()
        return {
            "pallas_calls": jaxpr_text.count("pallas_call["),
            "pallas_interpreted": jaxpr_text.count("interpret=True"),
            # out_avals follows name in a pallas_call's printed
            # parameters and in no other primitive's
            "pallas_kernels": sorted(
                set(re.findall(r"name=(\S+)\n\s+out_avals=", jaxpr_text))
            ),
            "tpu_custom_calls": lowered_text.count("@tpu_custom_call"),
            # lax.linalg.triangular_solve as lowered: XLA's op on the
            # chip, LAPACK's trsm on the CPU
            "triangular_solves": len(
                re.findall(
                    r"stablehlo\.triangular_solve|@lapack_[sdcz]trsm",
                    lowered_text,
                )
            ),
            "mosaic_kernels": sorted(
                set(re.findall(r'kernel_name = "([^"]+)"', lowered_text))
            ),
            "donated_inputs": count_donated_inputs(lowered_text),
            # where the step holds the flash kernels: their grids' steps
            # and which forward body they were built with
            **flash_attention.grid_steps_in(traced.jaxpr),
            **(self._step_ops_facts(lowered, trace_dir) if trace_dir else {}),
        }

    @staticmethod
    def _step_ops_facts(lowered, trace_dir):
        """Compile ``lowered`` and write, as
        ``<trace_dir>/edl_step_ops.json``, which class each instruction
        of the compiled step holds (``utils/step_ops.py``:
        ``{"module": name, "ops": {"fusion.1916": "bwd", ...}}``), so
        that a reader of the trace can sum the device's ops under the
        program's names; one file a process, replaced when a re-formed
        world builds its step. Returns what ``step_built`` says of it:
        how many instructions the rule classes of how many that run,
        and the compiler's own account of the step's memory, which
        counts the temporaries that ``peak_hbm_bytes`` cannot see. The
        step's own first call does not find this executable (the jitted
        call compiles its own lowering): where compiled programs are
        kept (``compile_cache_dir``) it loads the program once more
        from there, elsewhere it compiles a second time. A traced run
        pays that in its first window; the two executables are one
        program with one set of instruction names."""
        compiled = lowered.compile()
        ops_map, total = step_ops.step_ops_map(compiled.as_text())
        path = os.path.join(trace_dir, step_ops.FILE_NAME)
        os.makedirs(trace_dir, exist_ok=True)
        with open(path + ".tmp", "w", encoding="utf-8") as f:
            json.dump(ops_map, f)
        os.replace(path + ".tmp", path)
        facts = {
            "step_ops_named": len(ops_map["ops"]),
            "step_ops_total": total,
        }
        memory = compiled.memory_analysis()
        if memory is not None:
            facts.update(
                step_argument_bytes=int(memory.argument_size_in_bytes),
                step_temp_bytes=int(memory.temp_size_in_bytes),
                step_alias_bytes=int(memory.alias_size_in_bytes),
            )
        return facts

    def _step_callable_for(self, args):
        """An AOT-compiled executable exactly matching this call's
        signature (a speculative compile that landed), else the jitted
        step. The choice is memoized per batch signature: the full-args
        signature walks the whole TrainState pytree, which must not
        happen on every hot-loop step — the state/weights/rng shapes
        are fixed for the entry's lifetime, so the (cheap, few-leaf)
        batch part keys the decision."""
        entry = self._step_entry
        if entry is None or not entry.aot:
            return self._step_fn
        batch_sig = compile_plane.args_signature(args[1:3])
        fn = entry.dispatch_memo.get(batch_sig)
        if fn is None:
            compiled = entry.aot.get(compile_plane.args_signature(args))
            fn = compiled if compiled is not None else self._step_fn
            entry.dispatch_memo[batch_sig] = fn
        return fn

    def _world_mesh_for(self, n_devices, axes=None):
        """Hypothetical mesh over the first ``n_devices`` visible
        devices (same layout rule as :func:`build_world_mesh`), or None
        when that size cannot materialize on this backend. This is the
        speculation target and bounds what speculation can reach:
        shrink/re-grow sizes within the visible device set compile
        (exactly for single-backend resizes; as a persistent-cache warm
        across a cross-host re-form), while a GROWTH past the visible
        set returns None and the hint is dropped — no backend can
        compile for devices it cannot see (docs/compile_plane.md).

        ``axes`` overrides the layout hook — a layout-hinted
        speculation targets the SOLVER's candidate layout for that
        world, not whatever the hook would answer today.

        Runs on the speculative compiler's daemon thread against a live
        established backend, but the device enumeration still goes
        through the escapable probe with a hard timeout (edlint R1): a
        transport that wedges mid-steady-state must fail this
        background compile, not park it forever."""
        devices = np.asarray(escapable_call(jax.devices, timeout=30.0))
        n_devices = int(n_devices)
        if n_devices <= 0 or n_devices > devices.size:
            return None
        sub = devices[:n_devices]
        if axes is None:
            axes = (
                self._mesh_axes_fn(n_devices)
                if self._mesh_axes_fn
                else None
            )
        if not axes:
            return Mesh(sub, ("data",))
        names = tuple(axes)
        sizes = tuple(int(axes[n]) for n in names)
        if int(np.prod(sizes)) != n_devices:
            return None
        return Mesh(sub.reshape(sizes), names)

    def _abstract_step_args(
        self, mesh, example, state_specs=None, state_abstract=None
    ):
        """ShapeDtypeStruct argument tuple for AOT-lowering the step on
        ``mesh`` — shapes exactly as :meth:`train_step` will place them
        (padded rows derive from the worker's fixed minibatch).

        The replicated plane passes neither optional: the live state's
        shapes with replicated shardings. A layout-hinted speculation
        passes BOTH — the hypothetical layout's spec tree and padded
        abstract state — so the lowered signature carries each leaf's
        NamedSharding exactly as the future establish will place it."""
        features, labels = example
        # shape metadata only — no host materialization of the leaf
        leaf0 = jax.tree_util.tree_leaves(features)[0]
        mb = self.default_minibatch_size or int(leaf0.shape[0])
        rows = self.local_rows(mb)
        n_proc = self._spec.num_processes if self._spec else 1
        g_rows = rows * n_proc
        # weights/epochs carry one row per device of the mesh
        w_rows = mesh.devices.size
        row_axes = row_partition_spec(mesh)[0]

        def batch_abs(x):
            x = np.asarray(x)
            spec = P(*((row_axes,) + (None,) * (x.ndim - 1)))
            return jax.ShapeDtypeStruct(
                (g_rows,) + x.shape[1:],
                x.dtype,
                sharding=NamedSharding(mesh, spec),
            )

        def state_abs(leaf, spec=None):
            return jax.ShapeDtypeStruct(
                tuple(leaf.shape),
                leaf.dtype,
                sharding=NamedSharding(
                    mesh, spec if spec is not None else P()
                ),
            )

        state_src = (
            state_abstract if state_abstract is not None else self._ts
        )
        if state_specs is None:
            state_tree = jax.tree_util.tree_map(state_abs, state_src)
        else:
            state_tree = jax.tree_util.tree_map(
                state_abs, state_src, state_specs
            )
        row_shard = NamedSharding(mesh, P(row_axes))
        return (
            state_tree,
            jax.tree_util.tree_map(batch_abs, features),
            jax.tree_util.tree_map(batch_abs, labels),
            jax.ShapeDtypeStruct(
                (w_rows,), np.float32, sharding=row_shard
            ),
            jax.ShapeDtypeStruct((w_rows,), np.int32, sharding=row_shard),
            jax.random.PRNGKey(0),
        )

    def _speculative_compile(self, hint):
        """SpeculativeCompiler's compile_fn: build + AOT-compile the
        step for a hypothetical world and park it in the executable
        cache. Returns False (-> counted dropped) for candidates that
        cannot materialize.

        ``hint`` is either a bare device count (the replicated plane's
        historic form) or a ``(n_devices, axes_items)`` tuple from
        :meth:`_layout_hints` — a solver candidate layout for that
        world. Bare-size hints on sharded planes stay skipped (their
        spec/padding trees are world-specific establish-time state,
        and multi-process re-forms tear the backend down regardless —
        the persistent cache is their amortization layer). LAYOUT
        hints on the pjit dense plane are the exception that motivated
        this PR: a single-backend resize survives the membership
        change, so a pre-compiled (mesh, specs) entry turns the next
        planned layout change into pure state movement."""
        axes = None
        if isinstance(hint, tuple):
            n_devices, axes = int(hint[0]), dict(hint[1])
        else:
            n_devices = int(hint)
        if not self.compile_cache_enabled:
            return False
        if axes is None and self.is_sharded:
            return False
        if axes is not None and not (
            self._pjit_dense and self._builder is not None
        ):
            return False
        example = self._spec_example or self._last_local
        if example is None or self._ts is None:
            return False
        mesh = self._world_mesh_for(n_devices, axes=axes)
        if mesh is None:
            return False
        if axes is None:
            state_specs = None
            state_abstract = None
        else:
            _, param_specs = self._builder(mesh)
            sharded = collect_sharded_paths(param_specs)
            abstract = self._abstract_ts(example)
            state_specs = build_state_specs(abstract, sharded)
            state_abstract = self._padded_abstract_for(
                mesh, abstract, state_specs
            )
            if not self._specs_fit_mesh(mesh, state_abstract, state_specs):
                return False  # layout the shards reject: drop the hint
        key = (
            compile_plane.mesh_signature(mesh),
            self._step_config_signature(state_specs),
        )
        if self._exec_cache.get(key, count=False) is not None:
            return True  # already built (idempotent hint)
        step = self._build_step_fn(mesh, state_specs)
        entry = self._exec_cache.put(key, step, speculative=True)
        compile_plane.aot_compile(
            entry,
            self._abstract_step_args(
                mesh,
                example,
                state_specs=state_specs,
                state_abstract=state_abstract,
            ),
            stats=self._exec_cache.stats,
        )
        return True

    @staticmethod
    def _specs_fit_mesh(mesh, abstract_ts, state_specs):
        """Quiet feasibility probe for a HYPOTHETICAL layout: every
        sharded dim must divide its mesh axis. The establish-path twin
        (:meth:`_check_shard_divisibility`) raises with operator
        guidance; a speculation just drops the candidate."""
        ok = [True]

        def check(leaf, spec):
            for dim, axis_name in enumerate(spec or ()):
                if axis_name is None:
                    continue
                if leaf.shape[dim] % int(mesh.shape[axis_name]):
                    ok[0] = False

        jax.tree_util.tree_map(check, abstract_ts, state_specs)
        return ok[0]

    def _start_speculative_compiler(self):
        if not (self.speculative_compile and self.compile_cache_enabled):
            return
        sc = compile_plane.SpeculativeCompiler(
            self._speculative_compile, stats=self._exec_cache.stats
        )
        sc.start()
        self._spec_compiler = sc
        # default hints: one process joining or leaving the current
        # world; the worker layers membership-service hints on top.
        # With a layout planner the CURRENT size hints too — its top-2
        # covers the next-best layout at this size, so a planned
        # same-size layout change (e.g. a budget-driven dp/tp shift)
        # finds its executable pre-built
        n_dev = self._mesh.devices.size
        n_proc = self._spec.num_processes if self._spec else 1
        per_proc = max(1, n_dev // max(1, n_proc))
        sizes = [n_dev - per_proc, n_dev + per_proc]
        if self._planner is not None and self._pjit_dense:
            sizes.append(n_dev)
        self.hint_world_sizes(sizes)

    def hint_world_sizes(self, device_counts):
        """Feed likely next world sizes (in DEVICES) to the speculative
        compiler; non-blocking, deduplicated, no-op when speculation is
        off. With a layout planner, each size expands to the solver's
        top-2 (world, layout) candidates — the layout-hinted
        speculation of the ISSUE-20 tentpole."""
        if self._spec_compiler is None:
            return
        hints = []
        for n in device_counts:
            n = int(n)
            expanded = self._layout_hints(n)
            hints.extend(expanded if expanded else [n])
        self._spec_compiler.hint(hints)

    def _layout_hints(self, n_devices):
        """Solver candidates for ``n_devices`` as hashable
        ``(n, axes_items)`` hint tuples (empty without a planner /
        profile / pjit plane — the bare size is the hint then)."""
        if self._planner is None or not self._pjit_dense:
            return []
        if n_devices <= 0:
            return []
        try:
            layouts = self._planner.candidates(n_devices, top=2)
        except Exception:
            logger.debug(
                "layout candidate enumeration failed for %d devices",
                n_devices,
                exc_info=True,
            )
            return []
        return [
            (
                n_devices,
                tuple(layout_solver.mesh_axes_for(lay).items()),
            )
            for lay in layouts
        ]

    def _shutdown_compile_helpers(self):
        sc, self._spec_compiler = self._spec_compiler, None
        if sc is not None:
            sc.shutdown()
        feeder, self._feeder = self._feeder, None
        if feeder is not None:
            feeder.shutdown()

    def close(self):
        """Release compile-plane helper threads (idempotent; the worker
        calls it at teardown, tests at fixture exit)."""
        self._shutdown_compile_helpers()

    # -- step overlap: async H2D staging + deferred metric fetches ---------

    def _place_local_pair(self, features, labels, rows):
        local = (
            self._pad_local(features, rows),
            self._pad_local(labels, rows),
        )
        return (
            local,
            self._place_batch(local[0]),
            self._place_batch(local[1]),
        )

    def stage_next(self, features, labels, minibatch_size):
        """Start placing a batch onto the mesh on the feeder thread; a
        later :meth:`train_step` with the same (features, labels)
        objects picks the placement up instead of re-placing inline.
        Call right before a blocking sync step so H2D overlaps the
        fetch."""
        if features is None or self._mesh is None:
            return
        if self._feeder is None:
            self._feeder = _BatchFeeder(self._place_local_pair)
        rows = self.local_rows(minibatch_size)
        self._feeder.stage(
            (id(features), id(labels)), (features, labels, rows)
        )

    def _take_staged(self, features, labels):
        if self._feeder is None:
            return None
        return self._feeder.take(
            (id(features), id(labels)), should_abort=self.abort_check
        )

    def settle(self, lag=0):
        """Wait for every dispatched step but the newest ``lag`` and
        take their receipts: the validation of those steps. Returns
        the losses of the data steps among them (and of data steps a
        ``sync=True`` step validated without handing out), host floats,
        oldest first.

        ``lag=1`` is the worker's sync point: it has just dispatched
        step ``i`` and waits for step ``i - 1``, which is the moment
        the device starts step ``i``, so everything the sync point does
        with what it reads here runs while the device computes. What
        it reads is the receipt alone (loss, ``n_active``, the epoch
        consensus, :func:`kept_of` its state), small outputs that step
        ``i`` was not given and whose host copies began at dispatch:
        never the train state, which step ``i`` has donated or is
        writing. ``lag=0`` waits for the newest step too and leaves the
        device idle; a weight-0 step, whose ``n_active`` drives the
        exit, and the pause paths do.

        The newest validated step's ``epoch_consensus``, ``n_active``,
        version (:attr:`validated_version`) and receipt state
        (:meth:`routing_state`, :meth:`aux_losses`,
        :meth:`embedding_overflow_total`) replace the last ones, and
        its state becomes the checked (re-form fallback) state where
        one is kept (:meth:`_keep_checked`). Raises what a failed
        collective raises, with nothing taken: the caller's
        failed-window path owns those steps."""
        # the wait for the newest of them is this window's ``fetch``
        with profiling.phases.measure("fetch"):
            self._validate(lag)
        out, self._settled_losses = self._settled_losses, []
        return out

    def _validate(self, lag):
        """:meth:`settle`'s wait, charged to no phase; returns the
        newest validated step's loss, or None where there was no step
        to validate."""
        n_taken = len(self._in_flight) - lag
        if n_taken <= 0:
            return None
        taken = self._in_flight[:n_taken]

        def _fetch():
            # the copies began when each step was dispatched
            losses = [
                float(host_copy(receipt[0]))
                for receipt, has_data in taken
                if has_data
            ]
            return losses, host_copy(taken[-1][0])

        losses, newest = self._escapable(_fetch)
        del self._in_flight[:n_taken]
        self._in_flight_overflowed = False
        self._settled_losses.extend(losses)
        loss, n, epoch_seen, kept = newest
        self.n_active, self.epoch_consensus = int(n), int(epoch_seen)
        self._validated_version = int(kept["version"])
        self._validated_state = kept["state"]
        # the fetch proves every dispatched collective up to that step
        # completed; checkpoint its state as the re-form fallback
        self._keep_checked(self._ts if lag == 0 else self._ts_behind)
        return float(loss)

    def drain_metrics(self):
        """:meth:`settle` for a caller that has validated already
        (:meth:`validate`) or can do without: the losses of every step
        in flight, oldest first. On a wedged device or a failed
        collective the receipts in flight are dropped (their steps'
        accounting is handled by the failed-window path)."""
        try:
            if self._wedged:
                # a fetch would block forever on the wedged stream
                return []
            return self.settle()
        except Exception:
            logger.warning(
                "receipt fetch failed (broken collective?); dropping "
                "the losses of %d steps in flight",
                len(self._in_flight),
                exc_info=True,
            )
            return []
        finally:
            self._in_flight = []

    def _leaf_is_paddable(self, names):
        return any(
            spec_path_matches(spec_path, names)
            for spec_path in self._paddable_spec_paths
        )

    def _padded_abstract_for(
        self, mesh, abstract, state_specs, record=False
    ):
        """Placement shapes of ``abstract`` on ``mesh``: PadDim0-marked
        sharded leaves whose dim 0 doesn't divide round UP; everything
        else passes through. ``record=True`` replaces
        ``_logical_dim0`` (padding is a per-world property) — establish
        only; a layout-hinted speculation computes a HYPOTHETICAL
        world's padding on the daemon thread and must not mutate the
        live trainer's map."""
        from elasticdl_tpu.common.pytree import key_path_names

        logical = {}
        axes = {
            name: int(mesh.shape[name]) for name in mesh.axis_names
        }

        def pad(key_path, leaf, spec):
            if not _is_sharded_spec(spec):
                return leaf
            names = tuple(key_path_names(key_path))
            pad0 = padded_dim0(leaf.shape[0], spec, axes)
            if pad0 == leaf.shape[0] or not self._leaf_is_paddable(
                names
            ):
                return leaf
            logical[names] = int(leaf.shape[0])
            return jax.ShapeDtypeStruct(
                (pad0,) + tuple(leaf.shape[1:]), leaf.dtype
            )

        padded = jax.tree_util.tree_map_with_path(
            pad, abstract, state_specs
        )
        if record:
            self._logical_dim0 = logical
        return padded

    def _pad_abstract(self, abstract):
        """This world's placement shapes (recorded in
        ``_logical_dim0``); see :meth:`_padded_abstract_for`."""
        return self._padded_abstract_for(
            self._mesh, abstract, self._state_specs, record=True
        )

    def _pad_tree_values(self, tree, padded_abstract):
        """Zero-pad host values up to this world's placement shapes."""

        def pad(x, leaf):
            x = np.asarray(x)
            if x.shape == tuple(leaf.shape):
                return x
            out = np.zeros(tuple(leaf.shape), x.dtype)
            out[: x.shape[0]] = x
            return out

        return jax.tree_util.tree_map(pad, tree, padded_abstract)

    def logical_dim0_by_path(self):
        """{'a/b/c': true dim0} for this world's padded leaves — the
        checkpoint manager records these so host-side consumers
        (export, host-twin scoring) clip the padding back off."""
        return {
            "/".join(names): v
            for names, v in self._logical_dim0.items()
        }

    def _establish_sharded(self, example_batch):
        """Place sharded-parameter state: the in-memory replica plane
        first (no disk in the path — see ShardMirror), then the newest
        restorable checkpoint (falling back through older complete ones
        — a killed rank can leave the newest version torn), then a
        second replica attempt (a torn newer checkpoint must not beat a
        healthy mirror), else deterministic re-init."""
        from elasticdl_tpu.common.sharded_checkpoint import load_sharded

        if example_batch is None and self._last_local is None:
            raise ValueError("first establish() needs an example batch")
        example = example_batch or self._last_local
        # abstract shapes, not a real init: spec building only needs the
        # treedef, and a full host materialization of every (V,D) table
        # on every process at every re-form is exactly the memory spike
        # vocab-sharding exists to avoid
        abstract = self._abstract_ts(example)
        self._state_specs = build_state_specs(
            abstract, self._sharded_paths
        )
        # PadDim0-marked leaves whose dim 0 doesn't divide THIS world
        # get zero-padded placement shapes (recorded in _logical_dim0);
        # everything downstream — placement, mirrors, restore targets —
        # works in this world's padded space, while checkpoints and the
        # plan math stay anchored to the logical rows
        padded = self._pad_abstract(abstract)
        self._check_shard_divisibility(padded)
        candidates = (
            self.restore_provider() if self.restore_provider else None
        ) or []
        if isinstance(candidates, str):
            candidates = [candidates]
        was_live = self._host_step > 0
        old_ts, self._ts = self._ts, None
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self._mesh, s), self._state_specs
        )
        floor = _max_checkpoint_version(candidates)
        if (
            self._pjit_dense
            and old_ts is not None
            and self._placed_epoch == distributed.backend_epoch()
        ):
            # layout re-solve on resize (ElasWave-style, PAPERS.md
            # 2510.00606): the backend survived this membership change
            # (single-backend resize), so the state moves DIRECTLY from
            # the old placement to the new NamedShardings — the runtime
            # relays buffers device-to-device, no host round trip, no
            # disk. When the backend was torn down (a multi-process
            # re-form), the old buffers are gone and the snapshot
            # interchange below (sharded checkpoints) is the path.
            try:
                with profiling.span("elastic/resize/relayout"):

                    def move(target, leaf, sharding):
                        t_shape = tuple(target.shape)
                        if tuple(leaf.shape) != t_shape:
                            # a PadDim0 leaf whose padded extent
                            # differs between the two worlds: repad in
                            # DEVICE space (slice the old world's inert
                            # rows off / append zero rows) before the
                            # relayout put. Rows past the logical
                            # extent are zeros by construction, so the
                            # move stays bitwise on the logical rows.
                            if tuple(leaf.shape[1:]) != t_shape[1:]:
                                raise ValueError(
                                    "relayout shape mismatch beyond "
                                    "dim 0: %r -> %r"
                                    % (tuple(leaf.shape), t_shape)
                                )
                            t0, o0 = t_shape[0], leaf.shape[0]
                            if t0 < o0:
                                leaf = leaf[:t0]
                            else:
                                leaf = jnp.concatenate(
                                    [
                                        leaf,
                                        jnp.zeros(
                                            (t0 - o0,) + t_shape[1:],
                                            leaf.dtype,
                                        ),
                                    ],
                                    axis=0,
                                )
                        return jax.device_put(leaf, sharding)

                    self._ts = jax.tree_util.tree_map(
                        move, padded, old_ts, shardings
                    )
                logger.info(
                    "pjit dense plane re-laid out onto the new mesh "
                    "(old -> new NamedShardings, state moved in place)"
                )
                return
            except Exception:
                self._ts = None
                logger.warning(
                    "direct layout re-solve failed; falling back to "
                    "the snapshot interchange",
                    exc_info=True,
                )
        # COLLECTIVE attempts: mirror_enabled() answers from the job
        # args, so every rank takes the same branch; all further
        # decisions inside derive from the all-gathered summary
        if self.mirror_enabled():
            try:
                if self._try_assemble_from_mirrors(
                    abstract, floor, allow_stale=False
                ):
                    return
            except Exception:
                logger.warning(
                    "replica-plane assembly failed; falling back to "
                    "checkpoints",
                    exc_info=True,
                )
        # EVERY PadDim0 leaf restores into THIS world's placement shape
        # (padded, or the logical rows when this world divides): the
        # stored checkpoint may carry a DIFFERENT world's padding, and
        # rows past the logical extent are zeros either way. Keying on
        # currently-padded leaves alone would let a padded-world
        # checkpoint restore at its stored padded shape into a
        # divisible world — desynchronizing the state from the specs.
        from elasticdl_tpu.common.pytree import key_path_names

        target_shapes = {}

        def _collect_target(key_path, leaf, spec):
            names = tuple(key_path_names(key_path))
            if _is_sharded_spec(spec) and self._leaf_is_paddable(names):
                target_shapes["/".join(names)] = tuple(leaf.shape)

        jax.tree_util.tree_map_with_path(
            _collect_target, padded, self._state_specs
        )
        for restore_dir in candidates:
            try:
                version, self._ts = load_sharded(
                    restore_dir,
                    shardings,
                    target_shapes=target_shapes or None,
                )
                logger.info(
                    "sharded state restored at v%d from %s",
                    version,
                    restore_dir,
                )
                if floor > version:
                    # a torn NEWER directory exists (killed rank):
                    # future saves must number past it, or its stale
                    # manifests would merge into later restores. The
                    # scalar is committed onto the mesh like every other
                    # leaf (a host-local scalar inside an otherwise
                    # mesh-global TrainState breaks multi-host jit).
                    self._ts = self._ts.replace(
                        version=place_from_host_specs(
                            self._mesh, np.int32(floor), P()
                        )
                    )
                break
            except Exception:
                logger.warning(
                    "sharded checkpoint %s unrestorable onto the new "
                    "mesh; trying older",
                    restore_dir,
                    exc_info=True,
                )
        if self._ts is None and self.mirror_enabled():
            # second attempt, stale allowed: every checkpoint candidate
            # proved unrestorable, so an older-than-floor mirror is
            # still the best recoverable state (all ranks reach this
            # point together — the checkpoint loop reads the same
            # shared directory)
            try:
                self._try_assemble_from_mirrors(
                    abstract, floor, allow_stale=True
                )
            except Exception:
                logger.warning(
                    "stale replica-plane assembly failed",
                    exc_info=True,
                )
        if self._ts is None:
            if was_live:
                logger.warning(
                    "membership change with sharded parameters and no "
                    "restorable checkpoint: state RE-INITIALIZED "
                    "(enable --checkpoint_steps to bound this loss)"
                )
            init_ts = self._pad_tree_values(
                self._host_init_ts(example), padded
            )
            # version continuity: re-initialized state must start PAST
            # any existing checkpoint version, or future saves would
            # reuse an old ckpt_vN directory whose stale manifests (from
            # a departed rank / larger world) would silently merge into
            # restores
            if floor:
                init_ts = init_ts.replace(
                    version=np.int32(floor)
                )
            self._ts = place_from_host_specs(
                self._mesh, init_ts, self._state_specs
            )

    # -- in-memory replica plane (no-disk recovery) -------------------------

    def _world_axes(self, n_devices):
        """Mesh layout for an arbitrary world size: the zoo hook's
        answer, else the flat 1-axis data layout. Deterministic, so
        every rank (and every FUTURE world reasoning about a PAST
        world's blocks) computes the same layout."""
        axes = (
            self._mesh_axes_fn(n_devices) if self._mesh_axes_fn else None
        )
        return dict(axes) if axes else {"data": int(n_devices)}

    def mirror_enabled(self):
        """True when the replica plane is on (sharded job + cadence set).
        The flag comes from the job args, so it is GLOBAL: every rank
        answers identically, which the collective call sites rely on."""
        return bool(self.mirror_steps) and self.is_sharded

    def maybe_refresh_mirror(self, version):
        """Cadence wrapper; call at rank-aligned sync indices only.

        ``version`` is the aligned step version (identical on every
        rank), and ``_last_mirror_version`` is set by the collective
        refresh itself (identical on every rank after establish's
        refresh), so the predicate is global — no rank can sit out the
        ppermute."""
        if not self.mirror_enabled() or self._ts is None:
            return False
        # gate on the VERSION MARKER alone, never on _mirror presence:
        # the marker is aligned across ranks by construction (set by
        # every establish-tail attempt, success or failure), while
        # _mirror presence diverges — a joiner has none, survivors keep
        # stale ones — and a presence-gated predicate would send the
        # joiner into the collective ppermute alone
        if version - self._last_mirror_version < self.mirror_steps:
            return False
        self.refresh_mirror()
        return True

    def _split_by_sharding(self):
        """(sharded {path: global leaf}, {path: spec}, replicated host
        pytree with int8 placeholders at the sharded leaves)."""
        from elasticdl_tpu.common.pytree import key_path_names

        sharded, specs = {}, {}

        def pick(key_path, leaf, spec):
            names = tuple(key_path_names(key_path))
            if _is_sharded_spec(spec):
                sharded[names] = leaf
                specs[names] = spec
                return np.zeros((), np.int8)
            if hasattr(leaf, "addressable_shards"):
                return np.asarray(leaf.addressable_shards[0].data)
            return np.asarray(leaf)

        replicated = jax.tree_util.tree_map_with_path(
            pick, self._ts, self._state_specs
        )
        return sharded, specs, replicated

    def refresh_mirror(self):
        """Capture a :class:`ShardMirror` — COLLECTIVE: every rank must
        call at the same aligned step (periodic cadence, the consensus
        pause, or establish's tail). One jitted ppermute ships each
        sharded leaf's process block to the next process over ICI; the
        host staging afterwards is local-only."""
        if self._ts is None or not self._sharded_paths:
            return
        # replicated-leaf host fetches are device interactions too
        sharded, specs, replicated = self._escapable(
            self._split_by_sharding
        )
        if not sharded:
            return
        n_dev = self._mesh.devices.size
        n_local = mesh_local_count(self._mesh)
        flat_axes = row_partition_spec(self._mesh)[0]
        if self._mirror_perm_fn is None:
            spec_tree = {p: specs[p] for p in sharded}
            # shift by n_local devices = one PROCESS: the whole process
            # block lands on the next process (a one-device shift would
            # leave most of a multi-device process's rows on itself)
            perm = [(d, (d + n_local) % n_dev) for d in range(n_dev)]

            def body(tree):
                return jax.tree_util.tree_map(
                    lambda x: jax.lax.ppermute(x, flat_axes, perm), tree
                )

            self._mirror_perm_fn = jax.jit(
                jax.shard_map(
                    body,
                    mesh=self._mesh,
                    in_specs=(spec_tree,),
                    out_specs=spec_tree,
                    check_vma=False,
                )
            )
        # the permute dispatch AND the host fetches are escapable: a
        # peer death racing the refresh must not wedge this rank
        def _permute_and_stage():
            with self._mesh:
                permuted = self._mirror_perm_fn(sharded)
            version = int(host_copy(self._ts.version))
            own, replica = {}, {}
            for path, leaf in sharded.items():
                own[path], _ = _local_block(leaf)
                replica[path], _ = _local_block(permuted[path])
            return version, own, replica

        version, own, replica = self._escapable(_permute_and_stage)
        n_proc = self._spec.num_processes if self._spec else 1
        old_pid = self._spec.process_id if self._spec else 0
        self._mirror = ShardMirror(
            version, n_proc, old_pid, own, replica, replicated
        )
        self._last_mirror_version = version
        logger.info(
            "replica plane refreshed at v%d (pid %d/%d)",
            version,
            old_pid,
            n_proc,
        )

    def _all_gather_process_row(self, row):
        """All-gather one small int32 row per process (device slot 0
        carries it). COLLECTIVE: every rank must call with the same row
        width. Returns [tuple(ints)] indexed by process — identical on
        every rank, so decisions derived from it are global."""
        n_dev = self._mesh.devices.size
        n_local = mesh_local_count(self._mesh)
        n_proc = self._spec.num_processes
        flat_axes = row_partition_spec(self._mesh)[0]
        row = np.asarray(row, np.int32)
        local = np.zeros((n_local, row.shape[0]), np.int32)
        local[0] = row
        g = jax.make_array_from_process_local_data(
            NamedSharding(self._mesh, P(flat_axes, None)),
            local,
            (n_dev, row.shape[0]),
        )
        gather = self._gather_fns.get(row.shape[0])
        if gather is None:
            # cached per (mesh, row width): the in-plane eval consensus
            # calls this once per aligned sync — a fresh lambda each
            # call would retrace/recompile every time
            gather = jax.jit(
                jax.shard_map(
                    lambda x: jax.lax.all_gather(
                        x, flat_axes, tiled=True
                    ),
                    mesh=self._mesh,
                    in_specs=(P(flat_axes, None),),
                    out_specs=P(None, None),
                    check_vma=False,
                )
            )
            self._gather_fns[row.shape[0]] = gather
        with self._mesh:
            out = gather(g)
        table = np.asarray(out.addressable_shards[0].data)
        return [
            tuple(int(v) for v in table[p * n_local])
            for p in range(n_proc)
        ]

    def eval_have_consensus(self, have):
        """COLLECTIVE: total count of ranks reporting pending eval work.

        The in-plane eval protocol's loop condition — every rank calls
        at the same aligned point, ranks with no work participate in
        the forwards with dummy rows until this reaches zero."""
        table = self._escapable(
            lambda: self._all_gather_process_row([1 if have else 0])
        )
        return sum(h for (h,) in table)

    def eval_step(self, features, minibatch_size):
        """COLLECTIVE forward for in-plane evaluation: every rank of
        the mesh participates (the sharded model's lookups/ring are
        collectives), each feeding its own eval rows — ``features=None``
        participates with dummy rows (the previous batch) and discards
        the outputs. Returns this process's output rows as host numpy
        (caller slices to its true row count). Scores the CURRENT
        parameters — no checkpoint, no host twin, no aggregate-table
        materialization anywhere (the table stays sharded in HBM,
        which is the point: reference worker/worker.py:659-693
        evaluates on the training plane the same way)."""
        rows = self.local_rows(minibatch_size)
        if features is None:
            if self._last_local is None:
                raise RuntimeError(
                    "cannot run a dummy eval step before the first data "
                    "step"
                )
            features = self._last_local[0]
        local = self._pad_local(features, rows)
        g = self._place_batch(local)
        if self._eval_fn is None:
            self._eval_fn = self._build_eval_fn()

        def _dispatch():
            with self._mesh:
                out = self._eval_fn(self._ts, g)
            return jax.tree_util.tree_map(
                lambda a: _local_block(a)[0], out
            )

        return self._escapable(_dispatch)

    def _build_eval_fn(self):
        """Jitted shard_map INFERENCE forward over the established mesh
        (training=False: no dropout, no mutable-state updates — the
        same mode every other eval path scores in)."""
        from elasticdl_tpu.nn.model_api import apply_model
        from elasticdl_tpu.training.precision import get_policy

        pol = get_policy(self._precision)
        module = self._module
        ts_spec = (
            self._state_specs if self._state_specs is not None else P()
        )
        row_spec = row_partition_spec(self._mesh)
        if self._pjit_dense:
            # global-semantics forward: XLA partitions per the params'
            # NamedShardings (same GSPMD discipline as the train step);
            # the row-sharded out-sharding keeps each process's output
            # rows on its own devices for the _local_block consumer
            def global_fwd(ts, features):
                params, state = ts.params, ts.state
                if pol is not None:
                    params = pol.cast_to_compute(params)
                    features = pol.cast_to_compute(features)
                output, _ = apply_model(
                    module, params, state, features, training=False
                )
                if pol is not None:
                    output = pol.cast_output(output)
                return output

            return jax.jit(
                global_fwd,
                out_shardings=NamedSharding(self._mesh, row_spec),
            )

        def per_device(ts, features):
            params, state = ts.params, ts.state
            if pol is not None:
                params = pol.cast_to_compute(params)
                features = pol.cast_to_compute(features)
            output, _ = apply_model(
                module, params, state, features, training=False
            )
            if pol is not None:
                output = pol.cast_output(output)
            return output

        return jax.jit(
            jax.shard_map(
                per_device,
                mesh=self._mesh,
                in_specs=(ts_spec, row_spec),
                out_specs=row_spec,
                check_vma=False,
            )
        )

    def _replicated_source_rank(self):
        """Lowest rank holding live replicated state (the broadcast
        source), or -1 when nobody does. Collective when the world has
        more than one process; local (trivial) otherwise."""
        mine = 1 if self._host_ts is not None else 0
        if self._spec is None or self._spec.num_processes <= 1:
            return 0 if mine else -1
        table = self._all_gather_process_row([mine])
        ranks = [p for p, (has,) in enumerate(table) if has]
        return min(ranks) if ranks else -1

    def _gather_mirror_info(self):
        """All-gather every NEW-world process's mirror summary:
        ``[(has, version, n_old, old_pid)]`` indexed by new rank."""
        row = [0, 0, 0, 0]
        if self._mirror is not None:
            row = [
                1,
                self._mirror.version,
                self._mirror.n_old,
                self._mirror.old_pid,
            ]
        return self._all_gather_process_row(row)

    def _try_assemble_from_mirrors(self, abstract, floor, allow_stale):
        """Rebuild the full TrainState from surviving mirrors — no disk.

        COLLECTIVE: every rank of the new world must call with the same
        arguments; all decisions derive from the all-gathered summary so
        ranks cannot diverge. Returns True when ``self._ts`` was set.
        ``allow_stale=False`` refuses when a checkpoint directory is
        newer than the mirrors (first attempt; the checkpoint loop runs,
        then a second attempt with True catches torn checkpoints)."""
        from elasticdl_tpu.common.pytree import key_path_names

        info = self._gather_mirror_info()
        n_local = mesh_local_count(self._mesh)

        # sharded leaf metadata from the abstract state (joiners need
        # shapes/dtypes/specs without holding any data)
        meta = {}  # path -> (shape, dtype, spec)

        def collect(key_path, leaf, spec):
            if _is_sharded_spec(spec):
                names = tuple(key_path_names(key_path))
                meta[names] = (tuple(leaf.shape), leaf.dtype, spec)

        jax.tree_util.tree_map_with_path(
            collect, abstract, self._state_specs
        )

        # the OLD world's mesh layout is reconstructible from its
        # process count alone (the zoo hook is deterministic), so every
        # new rank — joiners included — computes identical old blocks.
        # Blocks live in each world's PADDED space (pad == logical for
        # divisible worlds) and are CLIPPED to the logical rows: the
        # pad rows are zeros by construction, so the plan only ever
        # moves real rows, whatever padding either world used.
        def clipped_block(axes, spec, shape0, pid):
            pad0 = padded_dim0(shape0, spec, axes)
            lo, hi = process_dim0_block(
                axes, spec, pad0, n_local, pid
            )
            return lo, min(hi, int(shape0))

        n_olds = {n for has, v, n, _ in info if has}
        old_blocks_by_n = {}
        old_bases_by_n = {}  # UNCLIPPED lo (slicing into mirror arrays)
        for n in n_olds:
            try:
                old_axes = self._world_axes(n * n_local)
            except Exception:
                logger.warning(
                    "old world of %d processes does not fit the mesh "
                    "layout hook; its mirrors are unusable", n,
                    exc_info=True,
                )
                continue
            old_blocks_by_n[n] = {
                path: (
                    lambda pid, _axes=old_axes, _spec=spec, _s0=shape[0]:
                    clipped_block(_axes, _spec, _s0, pid)
                )
                for path, (shape, _, spec) in meta.items()
            }
            old_bases_by_n[n] = {
                path: (
                    lambda pid, _axes=old_axes, _spec=spec, _s0=shape[0]:
                    process_dim0_block(
                        _axes,
                        _spec,
                        padded_dim0(_s0, _spec, _axes),
                        n_local,
                        pid,
                    )[0]
                )
                for path, (shape, _, spec) in meta.items()
            }
        leaf_spans = {
            path: shape[0] for path, (shape, _, _) in meta.items()
        }
        plan = None
        # plan against the newest version whose old layout resolved
        # (rows whose n_old failed to resolve never equal a dict key,
        # so the per-n filter alone excludes them)
        for n, leaf_blocks in old_blocks_by_n.items():
            cand = plan_mirror_ranges(
                [
                    row if row[2] == n else (0, 0, 0, 0)
                    for row in info
                ],
                leaf_blocks,
                leaf_spans,
                floor,
                allow_stale,
            )
            if cand is not None and (
                plan is None or cand[0] > plan[0]
            ):
                plan = cand
        if plan is None:
            if any(has for has, _, _, _ in info):
                logger.warning(
                    "replica plane cannot cover the old world (gap or "
                    "stale mirrors) — falling back to checkpoints"
                )
            return False
        target_v, n_old, assignments = plan

        n_proc_new = self._spec.num_processes
        n_dev = self._mesh.devices.size
        me = self._spec.process_id
        new_axes = {
            name: int(self._mesh.shape[name])
            for name in self._mesh.axis_names
        }
        flat_axes = row_partition_spec(self._mesh)[0]

        # my contributions: the plan's pieces assigned to my new rank,
        # sliced out of my mirror's own/replica arrays
        m = self._mirror
        my_old_pid = m.old_pid if m is not None else -1

        old_bases = old_bases_by_n[n_old]

        def my_piece(path, lo, hi, kind):
            # base = the UNCLIPPED start of the source block (the mirror
            # arrays include any old-world pad rows)
            if kind == 0:
                base = old_bases[path](my_old_pid)
                return m.own[path][lo - base : hi - base]
            base = old_bases[path]((my_old_pid - 1) % n_old)
            return m.replica[path][lo - base : hi - base]

        psum_specs = {
            path: P(flat_axes, *([None] * len(shape)))
            for path, (shape, _, _) in meta.items()
        }
        exchange = jax.jit(
            jax.shard_map(
                lambda tree: jax.tree_util.tree_map(
                    lambda x: jax.lax.psum(x, flat_axes), tree
                ),
                mesh=self._mesh,
                in_specs=(psum_specs,),
                out_specs={
                    path: P(*([None] * (len(shape) + 1)))
                    for path, (shape, _, _) in meta.items()
                },
                check_vma=False,
            )
        )

        my_shards = {}
        for r in range(n_proc_new):
            bufs = {}
            for path, (shape, dtype, spec) in meta.items():
                # the new rank's block in THIS world's padded space
                # (plan pieces are clipped to the logical rows, so the
                # buffer's pad tail simply stays zero)
                new_pad0 = padded_dim0(shape[0], spec, new_axes)
                r_lo, r_hi = process_dim0_block(
                    new_axes, spec, new_pad0, n_local, r
                )
                # device slot 0 carries the process contribution; the
                # other local slots stay zero so the psum over devices
                # is an exact sum over processes
                buf = np.zeros(
                    (n_local, r_hi - r_lo) + tuple(shape[1:]), dtype
                )
                for lo, hi, src, kind in assignments[path]:
                    s, e = max(lo, r_lo), min(hi, r_hi)
                    if s < e and src == me:
                        piece = my_piece(path, lo, hi, kind)
                        buf[0, s - r_lo : e - r_lo] = piece[
                            s - lo : e - lo
                        ]
                bufs[path] = buf
            placed = {
                path: jax.make_array_from_process_local_data(
                    NamedSharding(self._mesh, psum_specs[path]),
                    buf,
                    (n_dev,) + buf.shape[1:],
                )
                for path, buf in bufs.items()
            }
            with self._mesh:
                out = exchange(placed)
            if r == me:
                my_shards = {
                    path: np.asarray(
                        arr.addressable_shards[0].data
                    )[0]
                    for path, arr in out.items()
                }

        # replicated leaves: the broadcast SOURCE must be a rank the
        # plan knows holds a target_v mirror — blindly using rank 0
        # would adopt its zero stand-ins when rank 0's own refresh
        # failed or it is a joiner, silently zeroing every dense
        # parameter and optimizer slot. Any participant works; pick the
        # lowest rank deterministically (identical plan on every rank).
        source_rank = min(
            src
            for pieces in assignments.values()
            for _, _, src, _ in pieces
        )
        if m is not None and m.version == target_v:
            repl_host = m.replicated
        else:

            def stand_in(key_path, leaf, spec):
                if _is_sharded_spec(spec):
                    return np.zeros((), np.int8)
                return np.zeros(tuple(leaf.shape), leaf.dtype)

            repl_host = jax.tree_util.tree_map_with_path(
                stand_in, abstract, self._state_specs
            )
        repl = broadcast_from_device0(
            self._mesh, repl_host, source_process=source_rank
        )

        def combine(key_path, leaf, spec, broadcasted):
            names = tuple(key_path_names(key_path))
            if _is_sharded_spec(spec):
                local = my_shards[names]
                new_pad0 = padded_dim0(leaf.shape[0], spec, new_axes)
                return jax.make_array_from_process_local_data(
                    NamedSharding(self._mesh, spec),
                    local,
                    (new_pad0,) + tuple(leaf.shape[1:]),
                )
            return broadcasted

        self._ts = jax.tree_util.tree_map_with_path(
            combine, abstract, self._state_specs, repl
        )
        version = max(target_v, floor)
        self._ts = self._ts.replace(
            version=place_from_host_specs(
                self._mesh, np.int32(version), P()
            )
        )
        logger.info(
            "sharded state reassembled from the replica plane at v%d "
            "(no disk; %d source ranks, old world of %d)",
            target_v,
            len({s for p in assignments.values() for _, _, s, _ in p}),
            n_old,
        )
        return True

    def _check_shard_divisibility(self, abstract_ts):
        """Every sharded leaf must split evenly over the NEW world's mesh.

        The elastic world size changes at runtime; a re-form to a
        non-divisor size would otherwise fail at shard_map trace time
        with an opaque error and crash-loop the worker through
        relaunches. Fail once, loudly, with the fix in the message.
        Validates against the spec tree the step will actually use, so
        the check can never disagree with placement."""
        problems = []

        mirror_problems = []

        def check(key_path, leaf, spec):
            from elasticdl_tpu.common.pytree import key_path_names

            for dim, axis_name in enumerate(spec or ()):
                if axis_name is None:
                    continue
                n = self._mesh.shape[axis_name]
                if leaf.shape[dim] % n:
                    problems.append(
                        "%s: dim %d (=%d) %% %d devices != 0"
                        % (
                            "/".join(key_path_names(key_path)),
                            dim,
                            leaf.shape[dim],
                            n,
                        )
                    )
                if dim != 0:
                    # the replica plane's block math (_local_block,
                    # shape[0] // n_proc) assumes leading-dim sharding;
                    # a P(None, 'data') leaf would stage/assemble wrong
                    mirror_problems.append(
                        "/".join(key_path_names(key_path))
                    )

        jax.tree_util.tree_map_with_path(
            check, abstract_ts.params, self._state_specs.params
        )
        if problems:
            raise ValueError(
                "sharded parameters do not divide the %d-device world: "
                "%s. For row tables whose extra rows are inert "
                "(embeddings), mark the spec PadDim0 in the zoo's "
                "param_shardings and the elastic plane pads/reshards "
                "automatically; otherwise pad the sharded dimension "
                "(e.g. vocab_size) to a multiple of every world size "
                "the job can shrink/grow to."
                % (self._mesh.devices.size, "; ".join(problems))
            )
        if mirror_problems and self.mirror_enabled():
            raise ValueError(
                "the replica plane (--replica_refresh_steps) supports "
                "only leading-dim sharded parameters, but these leaves "
                "shard a later dim: %s. Reshape so the sharded axis is "
                "dim 0, or disable the mirror (replica_refresh_steps=0) "
                "to fall back to checkpoint-based recovery."
                % "; ".join(mirror_problems)
            )

    def _place_batch(self, tree):
        n_proc = self._spec.num_processes
        spec = row_partition_spec(self._mesh)

        def place(x):
            x = np.asarray(x)
            global_shape = (x.shape[0] * n_proc,) + x.shape[1:]
            return jax.make_array_from_process_local_data(
                NamedSharding(self._mesh, spec), x, global_shape
            )

        return jax.tree_util.tree_map(place, tree)

    def _pad_local(self, tree, rows):
        def pad(x):
            x = np.asarray(x)
            short = rows - x.shape[0]
            if short <= 0:
                return x[:rows]
            return np.concatenate([x, np.repeat(x[-1:], short, axis=0)])

        return jax.tree_util.tree_map(pad, tree)

    def local_rows(self, minibatch_size):
        """Fixed per-process rows: minibatch padded so each local device
        holds a whole number of microbatches."""
        n_local = (
            mesh_local_count(self._mesh)
            if self._mesh is not None
            else jax.local_device_count()
        )
        chunk = n_local * self._accum_steps
        return -(-minibatch_size // chunk) * chunk

    def train_step(
        self, features, labels, minibatch_size, sync=True, epoch_hint=0
    ):
        """One weighted lockstep step; ``features=None`` participates at
        weight 0 (drain mode). Returns (loss, n_active_devices, count)
        where count is this process's true (unpadded) contribution.

        ``epoch_hint`` is this process's last-polled membership epoch;
        the step pmax-es it across members and ``epoch_consensus`` (set
        at sync) exposes the newest epoch ANY member has seen — the
        skew-proof reform/pause trigger.

        ``sync=False`` dispatches and returns (None, None, count):
        the host (task RPCs, input pipeline) runs ahead of the device
        instead of stalling a round trip per step, and the step's
        receipt (its loss, ``n_active``, the epoch consensus and
        :func:`kept_of` its state, whose host copies begin here) waits in
        flight for :meth:`settle`, which validates it. The worker
        settles one step behind what it has dispatched
        (``settle(lag=1)`` every ``sync_every`` steps), so a collective
        failure rolls the snapshot back to the last validated state,
        at most the caller's sync cadence and one step.

        ``sync=True`` is the step and ``settle()`` in one: it waits for
        this step's own result, with the device left idle meanwhile,
        and returns its loss; the losses of earlier unsynced steps stay
        for :meth:`settle` / :meth:`drain_metrics`."""
        # pad + place the batch, the weights and the epochs (the wait
        # for a staged placement when one was taken)
        with profiling.phases.measure("batch_place"):
            rows = self.local_rows(minibatch_size)
            has_data = features is not None
            staged = None
            if has_data:
                leaf = jax.tree_util.tree_leaves(features)[0]
                count = int(np.asarray(leaf).shape[0])
                # step overlap: a placement staged via stage_next (padded +
                # placed on the feeder thread while the previous sync step's
                # fetch blocked) is byte-identical to the inline path — same
                # _pad_local/_place_batch code on the same host arrays
                staged = self._take_staged(features, labels)
                if staged is not None:
                    local = staged[0]
                else:
                    local = (
                        self._pad_local(features, rows),
                        self._pad_local(labels, rows),
                    )
                self._last_local = local
            else:
                count = 0
                if self._last_local is None:
                    raise RuntimeError(
                        "cannot run a weight-0 step before the first data step"
                    )
                local = self._last_local
            n_local = mesh_local_count(self._mesh)
            # partial batches pad by repeating the last example; weighting the
            # whole process by its true row fraction keeps a 1-row tail batch
            # from contributing a full step's worth of gradient
            w_value = min(1.0, count / rows) if has_data else 0.0
            w_local = np.full((n_local,), w_value, dtype=np.float32)
            row_spec = row_partition_spec(self._mesh)
            if staged is not None:
                g_features, g_labels = staged[1], staged[2]
            else:
                g_features = self._place_batch(local[0])
                g_labels = self._place_batch(local[1])
            g_weights = jax.make_array_from_process_local_data(
                NamedSharding(self._mesh, row_spec),
                w_local,
                (self._mesh.devices.size,),
            )
            g_epochs = jax.make_array_from_process_local_data(
                NamedSharding(self._mesh, row_spec),
                np.full((n_local,), int(epoch_hint), dtype=np.int32),
                (self._mesh.devices.size,),
            )
        self._host_step += 1
        host_step = self._host_step

        def _dispatch():
            # everything device-touching — eager PRNG ops, the jit
            # call — runs on the sacrificial thread (as the receipts'
            # fetches do, in _validate)
            with profiling.phases.measure("dispatch"):
                rng = jax.random.fold_in(
                    jax.random.PRNGKey(self._seed), host_step
                )
                args = (
                    self._ts,
                    g_features,
                    g_labels,
                    g_weights,
                    g_epochs,
                    rng,
                )
                fn = self._step_callable_for(args)
                with self._mesh:
                    try:
                        new_ts, *receipt = fn(*args)
                    except (TypeError, ValueError):
                        if fn is self._step_fn:
                            raise
                        # a speculative AOT executable whose signature
                        # check disagreed with the live call: drop it
                        # and dispatch through the jit path (retraces,
                        # stays correct)
                        logger.warning(
                            "AOT executable rejected the step call; "
                            "falling back to jit dispatch",
                            exc_info=True,
                        )
                        if self._step_entry is not None:
                            self._step_entry.aot.clear()
                            self._step_entry.dispatch_memo.clear()
                        new_ts, *receipt = self._step_fn(*args)
                # the receipt's way to the host begins now, so that the
                # sync point that reads it one step late finds it there
                # the moment its step has finished
                for leaf in jax.tree_util.tree_leaves(receipt):
                    _local_replica(leaf).copy_to_host_async()
            return new_ts, receipt

        behind = self._ts
        self._ts, receipt = self._escapable(_dispatch)
        self._ts_behind = behind if self._mesh.is_multi_process else None
        if len(self._in_flight) >= 4096:
            # the bound only exists as a leak backstop; a caller that
            # never settles loses losses, which must not happen silently
            del self._in_flight[0]
            if not self._in_flight_overflowed:
                self._in_flight_overflowed = True
                logger.warning(
                    "4096 steps in flight: the receipts (and losses) of "
                    "the oldest are DROPPED until the next settle — "
                    "settle more often to keep the loss record complete"
                )
        self._in_flight.append((receipt, has_data))
        if not sync:
            return None, None, count
        with profiling.phases.measure("fetch"):
            loss_v = self._validate(0)
        if has_data:
            self._settled_losses.pop()  # this step's own: returned here
        return loss_v, self.n_active, count

    def _keep_checked(self, ts):
        """Remember ``ts`` as the fetch-validated device state a failed
        collective rolls back to: where a peer exists. On a
        process-local mesh the next step donates these very buffers
        (:func:`state_donation`), so a kept reference would be a
        deleted array one dispatch later, and there is no failed
        collective to roll back from: nothing is kept, and
        :meth:`snapshot` falls back to the host snapshot."""
        self._checked_ts = ts if self._mesh.is_multi_process else None

    def _escapable(self, fn):
        """Run a device-touching callable so the host thread can escape
        a wedged backend.

        A peer death can block ANY backend interaction forever in C++ —
        not just fetches: observed stacks show eager op dispatch
        (PRNGKey) and the jit call itself wedging, because the CPU
        collectives backend executes on the calling thread and the
        listening side of a dead gloo socket just waits (only the
        connected side gets a reset error). A blocked host thread
        cannot poll the master, so the fencer kills a healthy rank and
        turns one process failure into two — exactly the adjacent
        double failure the replica plane cannot cover.

        Delegates to :func:`escapable_call` with the worker-provided
        ``abort_check`` probe and NO hard timeout (a first-step compile
        legitimately takes minutes). When the master has already moved
        the world on, the stuck thread is abandoned (left parked in the
        dead gloo op), the trainer marks itself wedged, and WorldBroken
        takes the ordinary failed-step recovery path with this rank's
        host state intact for the replica-plane reassembly."""
        try:
            return escapable_call(fn, should_abort=self.abort_check)
        except EscapeTimeout:
            self._wedged = True
            raise distributed.WorldBroken(
                "world moved on while this rank's device "
                "stream was wedged by a peer loss"
            )

    def validate(self):
        """Force-complete all dispatched work; True if it all succeeded.

        The wait is for the receipts in flight (:meth:`settle` with
        the losses kept for :meth:`drain_metrics`, and charged to
        whatever phase the caller is in), the newest step's included.
        On success the latest state becomes the checked
        (re-form fallback) state, where one is kept
        (:meth:`_keep_checked`); on failure the checked state is left
        at the last validated point.
        """
        if self._ts is None:
            return True
        if self._wedged:
            # a fetch already wedged on this world: touching the device
            # again would block forever — the state is unvalidatable
            return False
        try:
            self._validate(0)
        except Exception:
            logger.warning("validation failed: a dispatched step errored")
            return False
        return True

    def snapshot(self):
        """Pull current state to host (the re-form / checkpoint source).

        Falls back to the last fetch-validated state when the newest
        buffers carry a failed collective (unsynced steps roll back);
        on a process-local mesh, whose step donates its input and keeps
        no such state, to the latest host snapshot.
        Sharded-parameter jobs return None: one process's host copy of a
        sharded leaf would be its shard alone — the sharded checkpoint
        plane (save_sharded / restore on establish) is their snapshot
        mechanism."""
        if self._sharded_paths:
            return None
        if self._wedged:
            # device fetches block forever on a wedged stream; the last
            # validated host snapshot is the only safe source
            return self._host_ts
        if self._ts is not None:
            try:
                self._host_ts = host_copy(self._ts)
                return self._host_ts
            except Exception:
                logger.warning(
                    "latest state poisoned by a failed collective; "
                    "snapshotting the last validated state"
                )
            if self._checked_ts is not None:
                self._host_ts = host_copy(self._checked_ts)
        return self._host_ts

    def host_params(self):
        return self.snapshot().params

    def load_host_state(self, host_ts):
        """Adopt a checkpointed host TrainState before establish()."""
        self._host_ts = host_ts

    def save_sharded(self, directory):
        """Write this process's shards of the train state (no gather)."""
        from elasticdl_tpu.common.sharded_checkpoint import save_sharded

        save_sharded(
            directory,
            self._ts,
            version=self.version,
            logical_dim0=self.logical_dim0_by_path() or None,
        )

    def restore_sharded(self, directory):
        """Replace the established state with a sharded checkpoint,
        materialized straight onto the current mesh placement."""
        from elasticdl_tpu.common.sharded_checkpoint import load_sharded

        shardings = jax.tree_util.tree_map(
            lambda a: a.sharding, self._ts
        )
        # every PadDim0 leaf restores at the CURRENT placement shape
        # (self._ts already carries it) whatever padding the stored
        # checkpoint used — see the same logic in _establish_sharded
        from elasticdl_tpu.common.pytree import key_path_names

        target_shapes = {}

        def _collect(key_path, leaf):
            names = tuple(key_path_names(key_path))
            if self._leaf_is_paddable(names):
                target_shapes["/".join(names)] = tuple(leaf.shape)

        jax.tree_util.tree_map_with_path(_collect, self._ts)
        version, ts = load_sharded(
            directory, shardings, target_shapes=target_shapes or None
        )
        self._ts = ts
        self._keep_checked(ts)
        self._host_ts = host_copy(ts)
        logger.info(
            "restored sharded checkpoint v%d from %s", version, directory
        )
        return version

    def leave(self):
        """Snapshot and leave the world (graceful epoch boundary)."""
        # helper threads must not touch the backend once it starts dying
        self._shutdown_compile_helpers()
        try:
            self.snapshot()
        except Exception:
            logger.warning(
                "state snapshot failed; re-form will use the previous one",
                exc_info=True,
            )
        if (
            self._spec is not None
            and self._spec.process_id == 0
            and self._spec.num_processes > 1
            and not self._wedged
        ):
            # the coordination service lives in THIS process: at a
            # synchronized pause every member leaves at once, and a
            # peer whose disconnect RPC races this teardown FATALs in
            # C++ (uncatchable LOG(FATAL) — a clean drain turns into a
            # crash exit). Rank 0 lingers briefly so peers disconnect
            # against a live coordinator first.
            import time as _time

            _time.sleep(1.5)
        distributed.leave_world()
        self._ts = None
        self._checked_ts = None
        self._mesh = None
        self._step_fn = None
        self._step_entry = None
        # receipts in flight reference the departed world's buffers;
        # losses nobody took belong to no window of the next world
        self._in_flight = []
        self._settled_losses = []
        self._ts_behind = None
        self._validated_version = None
        self._validated_state = {}
