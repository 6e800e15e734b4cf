"""Elastic on-device data-parallel trainer (the ALLREDUCE strategy).

Replaces the reference's dense-gradient RPC plane (GetModel/ReportGradient
full-tensor round trips, SURVEY.md §3.3) with a single jitted train step
over a ``jax.sharding.Mesh``: parameters live replicated in HBM, the global
batch is split over the ``data`` axis, and XLA inserts the gradient
reduction over ICI — the ``grads_to_wait`` barrier *is* the collective.

Elasticity: ``resize(devices)`` rebuilds the mesh over the surviving/new
device set and re-places the train state. Compiled steps are cached per
(mesh shape, batch shape) so repeated membership changes between the same
world sizes pay compilation once (SURVEY.md §7.3 amortization note). The
task dispatcher above is untouched: a resize looks like "some workers'
tasks were recovered" plus a barrier.
"""

import jax
import numpy as np
from jax.sharding import NamedSharding

from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.nn.model_api import init_variables, split_variables
from elasticdl_tpu.parallel.mesh import (
    create_mesh,
    replicate,
    replicated,
    shard_batch,
)
from elasticdl_tpu.training.step import TrainState, make_train_step


class AllReduceTrainer:
    def __init__(
        self,
        module,
        loss_fn,
        optimizer,
        devices=None,
        batch_axis="data",
        seed=0,
        mesh=None,
        param_specs=None,
        accum_steps=1,
        precision=None,
        remat=False,
    ):
        """``param_specs``: optional nested dict mirroring (a prefix of)
        the params tree whose leaves are PartitionSpecs — parameters it
        names shard over the mesh instead of replicating (HBM embedding
        tables); their optimizer slots co-shard by tree-path suffix.
        ``accum_steps``/``precision`` forward to
        :func:`training.step.make_train_step` (gradient accumulation and
        the mixed-precision policy)."""
        self._module = module
        self._loss_fn = loss_fn
        self._optimizer = optimizer
        self._batch_axis = batch_axis
        self._seed = seed
        self._param_specs = param_specs
        self._sharded_paths = {}
        # the persistent compile cache covers this plane too: a
        # restarted local job re-jits the identical step HLO, which the
        # disk cache satisfies without an XLA compile
        # (docs/compile_plane.md)
        from elasticdl_tpu.parallel.compile_plane import (
            enable_persistent_cache,
        )

        # probe_backend: this single-process trainer touches the backend
        # at construction anyway (create_mesh below), so asking it
        # directly catches an accelerator-less box jax lands on CPU
        # implicitly — where a cache-reloaded donated executable would
        # crash (see enable_persistent_cache)
        enable_persistent_cache(probe_backend=True)
        self._step_fn = make_train_step(
            module,
            loss_fn,
            optimizer,
            accum_steps=accum_steps,
            precision=precision,
            remat=remat,
        )
        self._mesh = mesh if mesh is not None else create_mesh(devices=devices)
        self._ts = None
        self._host_step = 0

    @property
    def mesh(self):
        return self._mesh

    @property
    def num_devices(self):
        return self._mesh.devices.size

    @property
    def train_state(self):
        return self._ts

    @property
    def version(self):
        return int(self._ts.version) if self._ts is not None else -1

    def _collect_sharded_paths(self):
        """Flatten param_specs into {path tuple: NamedSharding}.

        ``"**"`` keys mark subtree specs (every leaf under the prefix) —
        see parallel/elastic.py collect_sharded_paths."""
        from elasticdl_tpu.parallel.elastic import collect_sharded_paths

        return {
            path: NamedSharding(self._mesh, spec)
            for path, spec in collect_sharded_paths(
                self._param_specs
            ).items()
        }

    @staticmethod
    def _key_names(key_path):
        from elasticdl_tpu.common.pytree import key_path_names

        return key_path_names(key_path)

    def _place(self, tree):
        """Place a host pytree: leaves whose tree path *ends with* a
        spec path shard, the rest replicates.

        Suffix matching places both the parameters themselves (path ==
        spec path) and their optimizer slots (optax moment trees nest the
        same sub-structure under mu/nu/...), without false positives on
        unrelated leaves that merely share a shape.
        """
        rep = replicated(self._mesh)
        specs = self._sharded_paths

        from elasticdl_tpu.parallel.elastic import spec_path_matches

        def put(key_path, x):
            names = self._key_names(key_path)
            for spec_path, sharding in specs.items():
                if spec_path_matches(spec_path, names):
                    return jax.device_put(x, sharding)
            return jax.device_put(x, rep)

        return jax.tree_util.tree_map_with_path(put, tree)

    def init_from_batch(self, global_batch):
        """Create + place train state from one example batch."""
        features = (
            global_batch[0]
            if isinstance(global_batch, tuple)
            else global_batch
        )
        # slice BEFORE the host transfer: init only needs one example's
        # shape, and np.asarray on the full leaf would D2H the whole
        # batch (a device leaf slices on device; a numpy leaf stays a
        # view either way)
        host_features = jax.tree_util.tree_map(
            lambda x: np.asarray(x[:1]), features
        )
        variables = init_variables(
            self._module, jax.random.PRNGKey(self._seed), host_features
        )
        params, state = split_variables(variables)
        ts = TrainState.create(params, state, self._optimizer)
        self._sharded_paths = self._collect_sharded_paths()
        self._ts = self._place(ts)
        return self._ts

    def load_state(self, ts):
        """Adopt an existing host/device train state (checkpoint restore)."""
        self._sharded_paths = self._collect_sharded_paths()
        self._ts = self._place(ts)

    def train_step(self, features, labels):
        """One global step. Batch leading dim must divide the data axis."""
        if self._ts is None:
            self.init_from_batch((features, labels))
        features = shard_batch(self._mesh, features, self._batch_axis)
        labels = shard_batch(self._mesh, labels, self._batch_axis)
        self._host_step += 1
        rng = jax.random.fold_in(
            jax.random.PRNGKey(self._seed), self._host_step
        )
        with self._mesh:
            self._ts, loss = self._step_fn(self._ts, features, labels, rng)
        return loss

    def resize(self, devices):
        """Membership change: rebuild the mesh and re-place state.

        Survivor state is the source of truth (replaces the reference's
        re-push-from-workers PS re-init, ps/servicer.py:70-79). The
        re-placement is a direct ``device_put`` from the old placement to
        the new mesh's shardings — the runtime moves buffers
        device-to-device (ICI/DMA) where it can, instead of a forced
        full HBM -> host -> HBM round trip of every parameter.
        """
        from elasticdl_tpu.utils import profiling

        old_ts = self._ts
        self._mesh = create_mesh(devices=devices)
        logger.info(
            "membership epoch: mesh re-formed over %d devices",
            self.num_devices,
        )
        if old_ts is not None:
            self._sharded_paths = self._collect_sharded_paths()
            # the step fn object is reused across resizes, so stepping
            # again at a previously-seen device set hits jax's aval
            # cache (no retrace/recompile); only the state re-placement
            # below is per-resize work — annotated so it separates in
            # traces
            with profiling.span("allreduce/resize/replace"):
                self._ts = self._place(old_ts)

    def get_host_state(self):
        """Pull the train state to host memory (for checkpointing).

        Leaves come back as OWNED copies: ``np.asarray`` on a CPU
        backend returns a zero-copy view of the device buffer, and this
        trainer's step DONATES its state — a checkpoint thread reading
        such a view races the next step recycling the buffer. Sharded
        leaves gather through ``jax.device_get`` (assembling the
        addressable shards) before the same owned-copy floor."""

        def fetch(x):
            if hasattr(x, "addressable_shards"):
                x = jax.device_get(x)
            # np.array(copy=True): never a view of device memory
            return np.array(x, copy=True)

        return jax.tree_util.tree_map(fetch, self._ts)

    def save_sharded(self, directory):
        """Per-shard checkpoint: HBM-sharded parameters (embedding
        tables) write one file per device shard — no dense gather."""
        from elasticdl_tpu.common.sharded_checkpoint import save_sharded

        save_sharded(directory, self._ts, version=self.version)

    def restore_sharded(self, directory):
        """Restore a sharded checkpoint onto the current placement
        (state must be initialized first, e.g. via init_from_batch)."""
        from elasticdl_tpu.common.sharded_checkpoint import load_sharded

        shardings = jax.tree_util.tree_map(
            lambda a: a.sharding, self._ts
        )
        version, self._ts = load_sharded(directory, shardings)
        return version
