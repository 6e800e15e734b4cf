"""Worker runtime: the task-driven training/eval/predict loop.

Parity: reference worker/worker.py (876 lines) — task loop with
train/evaluate/predict modes (:866-876), minibatch retry up to 64x on
rejected (stale) gradients (:620-656), variable creation via one forward
pass then report-to-master (:489-526), SSP-style local updates every
``get_model_steps`` (:748-825), evaluation-result batching and reporting
(:458-474, :577-608), SAVE_MODEL export task (:695-715).

TPU-native deltas:
- compute is a jitted ``value_and_grad`` step (training/step.make_grad_fn)
  instead of TF eager + GradientTape; forward is a jitted apply,
- model parameters are a JAX pytree; the wire form is the named-array
  mapping from common/tensor.py pytree bridges,
- the "stub" is anything implementing the MasterServicer method surface:
  the in-process servicer (tests; reference tests/in_process_master.py
  pattern) or an RPC client proxy,
- PS-sharded mode plugs in through ``ps_client`` (see elasticdl_tpu/ps/).
"""

import os
import time
import traceback

import jax
import numpy as np

from elasticdl_tpu.common.constants import (
    MAX_MINIBATCH_RETRY_NUM,
    GetModelMethod,
    JobType,
    MetricsDictKey,
    Mode,
    SaveModelConfig,
    TaskType,
)
from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.common.model_utils import get_model_spec
from elasticdl_tpu.common.tensor import (
    Tensor,
    named_arrays_to_pytree,
    pytree_to_named_arrays,
)
from elasticdl_tpu.nn.embedding import (
    IDX_COLLECTION,
    ROWS_COLLECTION,
    build_collection,
    call_slot_name,
    capture_embedding_ids,
    flatten_collection,
    path_name,
)
from elasticdl_tpu.nn.model_api import init_variables, split_variables
from elasticdl_tpu.ps.parameters import EmbeddingTableInfo
from elasticdl_tpu.training.step import (
    make_embedding_forward_fn,
    make_embedding_grad_fn,
    make_forward_fn,
    make_grad_fn,
)
from elasticdl_tpu.utils import profiling
from elasticdl_tpu.worker.task_data_service import TaskDataService


class Worker:
    def __init__(
        self,
        worker_id,
        job_type,
        minibatch_size,
        model_zoo,
        model_def,
        model_params=None,
        dataset_fn="dataset_fn",
        loss="loss",
        optimizer="optimizer",
        eval_metrics_fn="eval_metrics_fn",
        prediction_outputs_processor="PredictionOutputsProcessor",
        stub=None,
        ps_client=None,
        get_model_steps=1,
        max_minibatch_retry_num=MAX_MINIBATCH_RETRY_NUM,
        data_reader_params=None,
        seed=0,
        precision=None,
        sparse_dedup=True,
        task_prefetch=1,
        task_ack_queue=8,
        loss_log_steps=20,
        telemetry_report_secs=5.0,
        embedding_plane="ps",
        embedding_prefetch=None,
        export_dir=None,
        export_every_versions=0,
        export_keep=4,
    ):
        self._worker_id = worker_id
        self._job_type = job_type
        self._minibatch_size = minibatch_size
        self._stub = stub
        self._ps_client = ps_client
        self._get_model_steps = get_model_steps
        self._max_minibatch_retry_num = max_minibatch_retry_num
        self._seed = seed
        # loss logging costs a device sync (float(loss)); throttle it to
        # every N accepted minibatches and fetch lazily (0 = never)
        self._loss_log_steps = max(0, int(loss_log_steps))
        self._accepted_steps = 0
        # sparse-comms fast path: batch-wide id dedup before every row
        # pull, which also makes the pushed row gradients come back
        # pre-combined (docs/sparse_fast_path.md). False restores the
        # naive per-occurrence plan for benchmarking/equivalence runs.
        self._sparse_dedup = sparse_dedup
        # comm-plane mode (docs/embedding_planes.md): "ps" is the
        # classic parameter-server trainer (dense params round-trip
        # through pull_dense/push_gradient); "hybrid" keeps dense
        # params (HBM-plane tables included — they are ordinary
        # parameters) in the local/allreduce world and uses the PS
        # fleet ONLY for PS-plane embedding tables, served by the
        # overlapped pull pipeline below.
        if embedding_plane not in ("ps", "hybrid"):
            raise ValueError(
                "embedding_plane must be 'ps' or 'hybrid', got %r"
                % (embedding_plane,)
            )
        self._dense_local = embedding_plane == "hybrid"
        if self._dense_local and ps_client is None:
            raise ValueError(
                "embedding_plane='hybrid' needs a ps_client: the PS "
                "fleet serves the sparse tables while dense stays local"
            )
        if self._dense_local and job_type in (
            JobType.EVALUATION_ONLY,
            JobType.PREDICTION_ONLY,
        ):
            # hybrid's local replica is populated BY training (get_model
            # is a no-op); an eval/predict-only job would silently score
            # the random init and report garbage that looks finished
            raise ValueError(
                "embedding_plane='hybrid' only supports training job "
                "types: %s has no training loop to populate the local "
                "dense replica (serve saved models via the allreduce "
                "plane's eval/predict modes or PS-mode workers)"
                % job_type
            )
        from elasticdl_tpu.nn.comm_plane import (
            EmbeddingPullPipeline,
            MasterStorePlane,
            PsPlane,
        )

        # one plane object fronts whichever store holds the PS-resident
        # tables; the worker's embedding data path (plan -> pull ->
        # scatter -> push -> drain) only ever talks to this interface
        self._sparse_plane = (
            PsPlane(ps_client)
            if ps_client is not None
            else MasterStorePlane(lambda: self._stub)
        )
        if stub is not None and hasattr(
            stub, "set_on_master_epoch_change"
        ):
            # master reconnect protocol (docs/master_recovery.md): a
            # relaunched master's journal restores the LEDGER, not the
            # master-KV model store — in stub-held-model mode the
            # worker re-pushes its replica (first-write-wins, so a
            # master that kept its model ignores it). PS-mode dense
            # state lives on the PS fleet, which a master crash never
            # touches.
            stub.set_on_master_epoch_change(self._on_master_epoch_change)
        if ps_client is not None and hasattr(
            ps_client, "set_on_shard_reset"
        ):
            # reconnect protocol (docs/ps_recovery.md): a relaunched PS
            # shard that came back EMPTY (no snapshot to restore) gets
            # the model + embedding infos re-pushed before the next
            # data-plane round — push_model is first-write-wins per
            # shard, so live shards ignore it. Without this, a hybrid
            # worker (which never pulls dense) would error forever
            # against the empty store.
            ps_client.set_on_shard_reset(self._on_ps_shard_reset)
        if embedding_prefetch is None:
            # the overlapped pull pays off exactly when the dense half
            # no longer serializes on the PS (hybrid); the classic PS
            # trainer keeps the strictly-ordered inline pull
            embedding_prefetch = self._dense_local
        self._emb_pipeline = (
            EmbeddingPullPipeline()
            if embedding_prefetch and ps_client is not None
            else None
        )

        spec = get_model_spec(
            model_zoo=model_zoo,
            model_def=model_def,
            model_params=model_params,
            dataset_fn=dataset_fn,
            loss=loss,
            optimizer=optimizer,
            eval_metrics_fn=eval_metrics_fn,
            prediction_outputs_processor=prediction_outputs_processor,
        )
        self._model = spec.model
        self._dataset_fn = spec.dataset_fn
        from elasticdl_tpu.common.export import export_provenance

        self._export_meta = export_provenance(
            model_zoo, model_def, model_params
        )
        self._loss = spec.loss
        self._opt_fn = spec.optimizer
        self._eval_metrics_fn = spec.eval_metrics_fn
        self._prediction_outputs_processor = (
            spec.prediction_outputs_processor
        )

        self._params = None  # trainable pytree
        self._state = {}  # non-trainable collections
        self._model_version = -1
        self._var_created = False
        self._step_count = 0

        self._precision = precision
        self._grad_fn = make_grad_fn(
            self._model, self._loss, precision=precision
        )
        self._forward_fn = make_forward_fn(self._model)
        # elastic embedding layers (populated at variable creation)
        self._embedding_dims = {}  # {path_tuple: dim}
        self._embedding_initializers = {}  # {path_tuple: initializer name}
        self._embedding_num_calls = 0  # total calls (idx slots) per forward
        self._emb_grad_fn = None
        self._emb_forward_fn = None

        # local optimizer for SSP local updates (reference worker.py:122-126)
        self._local_opt = None
        self._local_opt_state = None
        self._non_embed_grads = None

        # streaming export cadence (docs/serving.md): write a complete
        # export artifact every N model versions so a scorer fleet's
        # directory watcher can hot-swap to it — the export third of
        # the train->export->serve loop. 0 disables (the end-of-job
        # SAVE_MODEL task is unaffected either way).
        self._export_dir = export_dir or None
        self._export_every = max(0, int(export_every_versions))
        self._export_keep = max(1, int(export_keep))
        self._last_export_version = -1

        self._evaluation_result = {}
        self._task_data_service = TaskDataService(
            self,
            self._job_type == JobType.TRAINING_WITH_EVALUATION,
            data_reader_params=data_reader_params,
            # pipelined input plane: fetch tasks ahead of consumption and
            # queue success acks for the boundary drains
            # (docs/input_pipeline.md)
            task_prefetch=task_prefetch,
            ack_queue_size=task_ack_queue,
        )
        # job telemetry: per-batch rate accounting + low-frequency
        # snapshots shipped behind task reports (docs/observability.md)
        from elasticdl_tpu.worker.telemetry import WorkerTelemetry

        self._telemetry = WorkerTelemetry(
            worker_id,
            stats=self._task_data_service.stats,
            interval_s=telemetry_report_secs,
            ps_client=ps_client,
        )

    # -- master RPC surface -------------------------------------------------

    def get_task(self, task_type=None):
        return self._stub.get_task(self._worker_id, task_type)

    def report_task_result(self, task_id, err_msg="", exec_counters=None):
        result = self._stub.report_task_result(
            task_id, err_msg, exec_counters
        )
        # the piggyback point: a task ack already cost a master round
        # trip, so the (rate-limited) telemetry snapshot rides here
        self._telemetry.ship(self._stub)
        return result

    def get_model(self, version, method=GetModelMethod.MINIMUM):
        """Pull parameters >= ``version`` (MINIMUM) or exactly (FIXED).

        In sharded-PS mode the pull merges every shard's partition
        (reference worker.py:189-227); eval pinning to checkpointed
        versions is a master-mode feature, PS serves latest.

        Hybrid mode never pulls: dense parameters live in the local/
        allreduce world by construction (the PS fleet only ever sees
        sparse tables), so eval/export score the local replica and the
        model version advances from sparse-push responses instead.
        """
        if self._dense_local:
            return
        with profiling.span("step/pull_model"):
            return self._pull_model(version, method)

    def _pull_model(self, version, method):
        if self._ps_client is not None:
            initialized, got_version, named = self._ps_client.pull_dense()
            if not initialized and self._params is not None:
                # a relaunched PS shard lost its state: re-push our model
                # (init-once per shard; reference ps/servicer.py:70-79 +
                # k8s_instance_manager.py:229-231 relaunch-same-id design)
                self.report_variable()
                initialized, got_version, named = (
                    self._ps_client.pull_dense()
                )
            if not initialized:
                return
            self._params = named_arrays_to_pytree(named, self._params)
            self._model_version = got_version
            return
        got_version, named = self._stub.get_model(version, method)
        if not named:
            return
        # aliasing note (docs/wire.md): over real gRPC these arrays are
        # zero-copy read-only views pinning ONE get_model reply buffer
        # until the next pull replaces them — safe and copy-free; jnp
        # consumers copy at device put anyway. Replies that rode a
        # recycled shm slot were already materialized inside
        # MasterClient.get_model (its audited retention edge), and the
        # PS path above materializes in pull_dense for the same reason.
        if self._params is not None:
            flat = pytree_to_named_arrays(self._params)
            if set(flat) == set(named):
                self._params = named_arrays_to_pytree(named, self._params)
            else:
                raise ValueError(
                    "master model parameters do not match local structure"
                )
        else:
            raise RuntimeError(
                "get_model before local variable creation"
            )
        self._model_version = got_version

    def _on_ps_shard_reset(self, shards):
        """PSClient reconnect hook: shards came back uninitialized."""
        if self._var_created and self._params is not None:
            logger.warning(
                "re-pushing model + embedding infos after PS shard(s) "
                "%s relaunched without restorable state",
                shards,
            )
            self.report_variable()

    def _on_master_epoch_change(self, old_epoch, new_epoch):
        """MasterClient reconnect hook: a relaunched master is serving.

        Only the master-KV mode holds model state in the master; its
        store is first-write-wins, so re-pushing is exactly right for
        an incarnation that lost it and a no-op for one that did not
        (docs/master_recovery.md). PS-mode state is on the PS fleet —
        nothing to do beyond the ack dedup the channel already gets.
        """
        if (
            self._ps_client is None
            and self._var_created
            and self._params is not None
        ):
            logger.warning(
                "re-pushing model after master relaunch (epoch %s -> %s)",
                old_epoch,
                new_epoch,
            )
            try:
                if self._embedding_dims:
                    self._stub.push_embedding_info(
                        self._embedding_table_infos()
                    )
                self.report_variable()
            except Exception:
                # the next get_model/report_gradient surfaces the real
                # failure through the ordinary retry machinery
                logger.warning(
                    "model re-push after master relaunch failed",
                    exc_info=True,
                )

    def _embedding_table_infos(self):
        """The declared elastic-embedding tables, in wire form — ONE
        builder for every push site (initial handshake, PS push_model,
        the master-relaunch re-push)."""
        return [
            EmbeddingTableInfo(
                path_name(path),
                dim,
                self._embedding_initializers.get(path, "uniform"),
            )
            for path, dim in self._embedding_dims.items()
        ]

    def report_variable(self):
        # PS pushes ride the dlpack wire bridge: device leaves stay on
        # device and the frame write is their single host copy
        # (docs/wire.md) — the master stub keeps host numpy (in-process
        # masters retain what they are handed)
        named = pytree_to_named_arrays(
            self._params, keep_device=self._ps_client is not None
        )
        if self._ps_client is not None:
            self._ps_client.push_model(
                named, self._embedding_table_infos()
            )
        else:
            self._stub.report_variable(named)

    def report_gradient(self, grads, sparse_tensors=None):
        """Ship dense grads as named tensors (+ sparse embedding grads)."""
        named = pytree_to_named_arrays(
            grads, keep_device=self._ps_client is not None
        )
        if self._ps_client is not None:
            return self._ps_client.push_gradient(
                named, sparse_tensors, self._model_version
            )
        tensors = [Tensor(name, values) for name, values in named.items()]
        tensors.extend(sparse_tensors or ())
        return self._stub.report_gradient(tensors, self._model_version)

    def _drain_ps_pushes(self):
        """Synchronously settle the async gradient-push window.

        Called at every task boundary, before evaluation, and before
        checkpoint/export so no gradient is still on the wire when the
        job observes or persists model state (docs/dense_overlap.md).
        ``pull_dense`` also drains, so the window never widens the SSP
        staleness bound beyond what get_model_steps already allows.
        The drain goes through the comm-plane interface, so hybrid and
        classic PS mode settle their sparse pushes at the SAME SSP
        boundaries (docs/embedding_planes.md).
        """
        if self._ps_client is None:
            return
        # skeletal instances (tests build Worker.__new__ with only a
        # ps_client) drain the client directly; fully-constructed
        # workers go through the plane
        plane = getattr(self, "_sparse_plane", None)
        if plane is None and not hasattr(self._ps_client, "drain"):
            return
        try:
            with profiling.span("task/push_drain"):
                accepted, _ = (
                    plane.drain()
                    if plane is not None
                    else self._ps_client.drain()
                )
        except RuntimeError as err:
            # a PS failure surfacing HERE (a boundary, not a minibatch)
            # means an already-reported batch's gradient was lost on
            # the wire — bounded staleness the async plane tolerates,
            # same as a stale rejection. The worker must survive: the
            # NEXT minibatch's pull hits the same dead shard inside
            # the retry machinery, which converts it to a failed-task
            # report (drain inside pull_dense takes that path too)
            logger.warning(
                "async gradient push window drained with a shard "
                "failure; the in-flight updates were dropped: %s",
                err,
            )
            return
        if not accepted:
            # async-window pushes resolve after the optimistic accept;
            # a late rejection (stale gradient on a sync-mode PS) only
            # costs that one update — the next pull resynchronizes —
            # but must not pass silently
            logger.warning(
                "async gradient push window drained with rejected "
                "shard pushes; the rejected updates were dropped"
            )

    def report_evaluation_metrics(self, model_outputs, labels):
        outputs = {
            name: np.concatenate([np.asarray(v) for v in chunks])
            for name, chunks in model_outputs.items()
        }
        labels = np.concatenate([np.asarray(v) for v in labels])
        return self._stub.report_evaluation_metrics(
            self._model_version, outputs, labels
        )

    def report_prediction_outputs(self, predictions):
        if self._prediction_outputs_processor:
            self._prediction_outputs_processor.process(
                predictions, self._worker_id
            )
        else:
            logger.warning(
                "prediction_outputs_processor is not defined in the model "
                "definition. Prediction outputs are not processed."
            )
        return True

    # -- model/variable lifecycle ------------------------------------------

    def _run_model_call_before_training(self, features):
        """Create variables with one tracing pass; report them once.

        Parity: reference worker.py:489-526 (the eager create-then-report
        handshake; the master keeps the first reported init).
        """
        if self._params is None:
            variables = init_variables(
                self._model, jax.random.PRNGKey(self._seed), features
            )
            self._params, self._state = split_variables(variables)
            # elastic embedding collections are per-batch inputs, not state
            rows_template = self._state.pop(ROWS_COLLECTION, None)
            idx_template = self._state.pop(IDX_COLLECTION, None)
            if rows_template:
                self._embedding_dims = {
                    path: int(arr.shape[-1])
                    for path, arr in flatten_collection(
                        rows_template, "rows"
                    ).items()
                }
                # total CALLS per forward (>= layer count: a tied layer
                # owns one idx slot per call) — bounds every capture pass
                self._embedding_num_calls = len(
                    flatten_collection(idx_template, "idx")
                )
                # one capture pass to learn each layer's declared
                # initializer (forwarded in EmbeddingTableInfo)
                layer_info = {}
                capture_embedding_ids(
                    self._model,
                    {"params": self._params, **self._state},
                    features,
                    expected_count=self._embedding_num_calls,
                    layer_info=layer_info,
                )
                self._embedding_initializers = {
                    path: info[1] for path, info in layer_info.items()
                }
                self._emb_grad_fn = make_embedding_grad_fn(
                    self._model, self._loss, precision=self._precision
                )
                self._emb_forward_fn = make_embedding_forward_fn(self._model)
        if not self._var_created:
            if self._embedding_dims and self._ps_client is None:
                self._stub.push_embedding_info(
                    self._embedding_table_infos()
                )
            self.report_variable()
            self._var_created = True

    def _apply_local_dense(self, grads):
        """Advance the local dense replica by one optimizer step.

        The hybrid plane's dense world: dense layers AND HBM-plane
        tables (ordinary parameters) update here with the worker's own
        optimizer instance — no PS round trip. A multi-worker hybrid
        job syncs this replica on the allreduce plane; the degenerate
        one-worker world needs no sync at all. Also the engine behind
        classic SSP local updates (reference worker.py:168-176). The
        update is jitted (training/step.make_local_update_fn): hybrid
        runs it every accepted minibatch, and the eager optax tree
        walk would pay a dispatch per leaf per step."""
        if self._local_opt is None:
            from elasticdl_tpu.training.step import make_local_update_fn

            self._local_opt = self._opt_fn()
            self._local_opt_state = self._local_opt.init(self._params)
            self._local_update_fn = make_local_update_fn(self._local_opt)
        self._params, self._local_opt_state = self._local_update_fn(
            grads, self._local_opt_state, self._params
        )

    def _update_local_model(self):
        """Apply the last accepted gradients locally (SSP local updates).

        Parity: reference worker.py:168-176 — between model pulls, the
        worker advances its own replica with its own optimizer instance.
        """
        if self._non_embed_grads is None:
            return
        grads, self._non_embed_grads = self._non_embed_grads, None
        self._apply_local_dense(grads)

    # -- elastic embedding plumbing ----------------------------------------

    def _plan_embedding_lookups(self, features):
        """Capture ids on host, build the per-layer dedup plan.

        Runs on the worker thread always — the flax capture interceptor
        must not race a real forward — and is cheap (numpy only), so
        the prefetch pipeline plans inline and backgrounds only the
        RTT-heavy pull. Returns {path: (unique_ids, idxs, bucket)}.
        """
        variables = {"params": self._params, **self._state}
        captured = capture_embedding_ids(
            self._model,
            variables,
            features,
            expected_count=self._embedding_num_calls,
        )
        # one union pull per layer, however many times it is called:
        # every call slot gathers from the same rows buffer, so row
        # gradients of a tied embedding accumulate across calls
        return {
            path: self._sparse_plane.plan_lookup_multi(
                ids_list, dedup=self._sparse_dedup
            )
            for path, ids_list in captured.items()
        }

    def _pull_embedding_rows(self, lookups):
        """One comm-plane round for EVERY layer's rows: the per-layer
        serial pull loop would pay one PS round trip per table
        (docs/dense_overlap.md). Also the thunk the prefetch pipeline
        runs on its background thread."""
        return self._sparse_plane.pull(
            {
                path_name(path): unique
                for path, (unique, _, _) in lookups.items()
            }
        )

    def _kick_embedding_prefetch(self, batch):
        """Stage the NEXT batch's embedding pull so its PS fan-out
        overlaps the CURRENT batch's jitted forward/backward
        (docs/embedding_planes.md). Plans inline (capture is worker-
        thread-only), submits only the pull."""
        if (
            self._emb_pipeline is None
            or not self._embedding_dims
            or self._params is None
        ):
            return
        features = batch[0] if isinstance(batch, tuple) else batch
        try:
            lookups = self._plan_embedding_lookups(features)
        except Exception:
            # planning the lookahead batch must never kill the current
            # one — the consumer simply plans+pulls inline
            logger.warning(
                "embedding prefetch planning failed; next batch pulls "
                "inline",
                exc_info=True,
            )
            return
        # the background pull's span carries the CURRENT task's trace
        # (the lookahead batch almost always belongs to the same task;
        # at worst the span lands one trace early — documented)
        cur = self._task_data_service.get_current_task()
        trace_id = (
            (cur.extended_config or {}).get("trace_id")
            if cur is not None
            else None
        )
        self._emb_pipeline.submit(
            features,
            lookups,
            lambda lookups=lookups: self._pull_embedding_rows(lookups),
            trace_id=trace_id,
        )

    def _prepare_embedding_batch(self, features):
        """Plan ids, pull + pad rows; returns (rows, idx, plan).

        ``plan``: {path: (unique_ids, k)} for stripping padded gradients.
        This is the hoisted-out-of-jit equivalent of the reference's
        in-graph py_function lookup (layers/embedding.py:216-253). A
        pull prefetched for exactly this batch is consumed instead of
        re-pulling; on a miss (first batch, retry after a stale-gradient
        rejection — which WANTS fresh rows — or an invalidated round)
        the pull runs inline.
        """
        with profiling.span("step/embedding_pull") as sp:
            pre = (
                self._emb_pipeline.consume(features)
                if self._emb_pipeline is not None
                else None
            )
            if pre is not None:
                # the wait here is the TAIL of the overlapped round
                # trip; the fan-out itself shows as the pipeline
                # thread's step/embedding_pull_bg span
                sp.add(pipelined=True)
                lookups, pulled = pre
            else:
                lookups = self._plan_embedding_lookups(features)
                pulled = self._pull_embedding_rows(lookups)
        rows_by_path, idx_by_path, plan = {}, {}, {}
        for path, (unique, idxs, bucket) in lookups.items():
            rows_by_path[path] = self._sparse_plane.scatter(
                pulled[path_name(path)], bucket
            )
            for i, idx in enumerate(idxs):
                idx_by_path[path + (call_slot_name(i),)] = idx
            plan[path] = (unique, len(unique))
        return (
            build_collection(rows_by_path, "rows"),
            build_collection(idx_by_path, "idx"),
            plan,
        )

    def _sparse_grad_tensors(self, row_grads, plan):
        grads_by_path = flatten_collection(row_grads, "rows")
        tensors = []
        for path, (unique, k) in plan.items():
            g = np.asarray(grads_by_path[path])[:k]
            tensors.append(Tensor(path_name(path), g, indices=unique))
        return tensors

    # -- compute ------------------------------------------------------------

    def training_process(self, features, labels):
        # fresh dropout mask per step per worker: fold in a local step
        # counter (the model version alone repeats within a sync round and
        # across workers)
        self._step_count += 1
        rng = jax.random.fold_in(
            jax.random.PRNGKey(self._seed * 100003 + self._worker_id),
            self._step_count,
        )
        # step/compute = the host-blocking side of the jitted step:
        # embedding prep (which nests step/embedding_pull) + the grad
        # dispatch. The async device work that outlives the dispatch
        # materializes in step/grad_push, where its results are forced
        # onto the wire (docs/observability.md attribution note).
        with profiling.span("step/compute"):
            if self._embedding_dims:
                rows, idx, plan = self._prepare_embedding_batch(features)
                loss, grads, row_grads, new_state, _ = self._emb_grad_fn(
                    self._params, rows, self._state, idx, features,
                    labels, rng,
                )
                self._state = new_state
                return (
                    loss,
                    grads,
                    self._sparse_grad_tensors(row_grads, plan),
                )
            loss, grads, new_state, _ = self._grad_fn(
                self._params, self._state, features, labels, rng
            )
            self._state = new_state
            return loss, grads, None

    def forward_process(self, features):
        if self._embedding_dims:
            rows, idx, _ = self._prepare_embedding_batch(features)
            return self._emb_forward_fn(
                self._params, rows, self._state, idx, features
            )
        return self._forward_fn(self._params, self._state, features)

    def _run_training_task(self, features, labels):
        loss, grads, sparse_grads = self.training_process(features, labels)
        if self._dense_local:
            # hybrid comm plane: only the PS-resident tables' row
            # gradients cross the wire (riding the shared push window);
            # dense gradients apply to the local replica immediately.
            accepted, version = True, -1
            if sparse_grads:
                with profiling.span("step/grad_push", sparse=True):
                    accepted, version = self._sparse_plane.push(
                        sparse_grads, max(self._model_version, 0)
                    )
            if version is not None and version >= 0:
                # the version a rejection reports feeds the retry's
                # next push; accepted pushes advance the SSP clock
                self._model_version = max(self._model_version, version)
            if accepted:
                with profiling.span("step/local_update"):
                    self._apply_local_dense(grads)
            return accepted, self._model_version, loss
        with profiling.span("step/grad_push"):
            accepted, min_model_version = self.report_gradient(
                grads, sparse_grads
            )
        if accepted and self._get_model_steps > 1:
            self._non_embed_grads = grads
        return accepted, min_model_version, loss

    def _collect_evaluation_result(self, outputs, labels):
        key = MetricsDictKey.MODEL_OUTPUT
        if key not in self._evaluation_result:
            self._evaluation_result[key] = {
                k: [np.asarray(v)] for k, v in outputs.items()
            }
        else:
            for k, v in outputs.items():
                self._evaluation_result[key][k].append(np.asarray(v))
        key = MetricsDictKey.LABEL
        self._evaluation_result.setdefault(key, []).append(np.asarray(labels))

    def _run_evaluation_task(self, features, labels):
        outputs = self.forward_process(features)
        if not isinstance(outputs, dict):
            outputs = {MetricsDictKey.MODEL_OUTPUT: outputs}
        self._collect_evaluation_result(outputs, labels)
        return True

    def _run_prediction_task(self, features):
        predictions = self.forward_process(features)
        return self.report_prediction_outputs(predictions)

    # -- minibatch state machine -------------------------------------------

    def _process_minibatch(
        self,
        task_type,
        features,
        labels,
        min_model_version,
        train_with_local_model=False,
    ):
        if not self._var_created or self._params is None:
            # first-batch variable creation (init pass + report) is
            # seconds on a cold backend; without its own span the first
            # step's critical-path attribution would blame nothing
            with profiling.span("step/var_init"):
                self._run_model_call_before_training(features)
        for _ in range(self._max_minibatch_retry_num):
            if task_type == TaskType.EVALUATION:
                if min_model_version == -1:
                    if self._model_version < 0:
                        self.get_model(0, GetModelMethod.MINIMUM)
                elif self._model_version != min_model_version:
                    self.get_model(min_model_version, GetModelMethod.FIXED)
                if self._run_evaluation_task(features, labels):
                    break
            elif task_type == TaskType.TRAINING:
                if not train_with_local_model:
                    self.get_model(
                        max(self._model_version, min_model_version),
                        GetModelMethod.MINIMUM,
                    )
                accepted, min_model_version, loss = self._run_training_task(
                    features, labels
                )
                if accepted:
                    # float(loss) is a device sync — fetch only on the
                    # throttled steps (first accepted step, then every
                    # --loss_log_steps), never on the hot path
                    self._accepted_steps += 1
                    if self._loss_log_steps and (
                        self._accepted_steps % self._loss_log_steps == 1
                        or self._loss_log_steps == 1
                    ):
                        logger.info(
                            "Loss is %f (accepted step %d)",
                            float(loss),
                            self._accepted_steps,
                        )
                    break
            elif task_type == TaskType.PREDICTION:
                if self._model_version != min_model_version:
                    self.get_model(min_model_version, GetModelMethod.FIXED)
                if self._run_prediction_task(features):
                    break
            else:
                raise RuntimeError("Unrecognized task type, %s" % task_type)
        else:
            raise RuntimeError("Worker got stuck")
        return min_model_version

    def _process_minibatch_and_report(
        self,
        dataset_batch,
        task_type,
        model_version,
        train_with_local_model=False,
    ):
        err_msg = ""
        try:
            if self._job_type == JobType.PREDICTION_ONLY:
                features = dataset_batch
                labels = None
            else:
                features, labels = dataset_batch
            self._process_minibatch(
                task_type,
                features,
                labels,
                model_version,
                train_with_local_model,
            )
        except RuntimeError as err:
            err_msg = str(err)
            traceback.print_exc()
        except Exception as ex:
            err_msg = str(ex)
            traceback.print_exc()
            raise ex
        return err_msg

    @staticmethod
    def _lookahead_pairs(iterable):
        """Yield (batch, next_batch) with a one-item lookahead;
        next_batch is None on the last item. The dataset chain already
        runs ahead of consumption (``.prefetch(1)``), so materializing
        one more batch early adds no new accounting mode — the task
        ledger advances on report_record_done, never on iteration."""
        it = iter(iterable)
        try:
            cur = next(it)
        except StopIteration:
            return
        for nxt in it:
            yield cur, nxt
            cur = nxt
        yield cur, None

    @staticmethod
    def _batch_count(dataset_batch):
        # read shape[0] directly: np.asarray on a device_prefetched batch
        # would force a device->host materialization every step
        leaf = jax.tree_util.tree_leaves(dataset_batch)[0]
        shape = getattr(leaf, "shape", None)
        if shape is not None:
            return int(shape[0])
        return len(leaf)

    # -- evaluation / save-model tasks -------------------------------------

    def _process_eval_task(self, task):
        logger.info("the evaluation task_id: %d" % task.task_id)
        self._drain_ps_pushes()
        # eval boundary: queued training-task acks land before the
        # master observes this worker's evaluation results
        self._task_data_service.drain_acks()
        eval_info = self._task_data_service.get_validation_dataset(task)
        if not eval_info:
            return
        eval_dataset, model_version, task_id = eval_info
        eval_dataset = self._dataset_fn(
            eval_dataset,
            Mode.EVALUATION,
            self._task_data_service.data_reader.metadata,
        )
        eval_dataset = eval_dataset.batch(self._minibatch_size).prefetch(1)
        err_msg = ""
        for dataset_batch in eval_dataset:
            data_err_msg = self._process_minibatch_and_report(
                dataset_batch, TaskType.EVALUATION, model_version
            )
            if data_err_msg:
                err_msg = data_err_msg
                break
        if MetricsDictKey.MODEL_OUTPUT in self._evaluation_result:
            accepted, _ = self.report_evaluation_metrics(
                self._evaluation_result[MetricsDictKey.MODEL_OUTPUT],
                self._evaluation_result[MetricsDictKey.LABEL],
            )
            if not accepted:
                raise RuntimeError("Report evaluation metric failed!")
        self.report_task_result(task_id, err_msg)
        self._evaluation_result = {}

    def _maybe_streaming_export(self):
        """Export the dense graph when the version cadence is due.

        Runs on the worker thread between minibatches (never inside a
        step span): drains the push window first so the exported params
        reflect every completed push, writes the artifact under
        ``<export_dir>/v<version>`` with the MANIFEST last (the
        watcher's completeness marker, docs/export.md), then prunes
        artifacts beyond ``export_keep``. Failures log and retry at the
        next cadence point — a serving fleet losing ONE export just
        serves the previous version a little longer."""
        if (
            not self._export_every
            or self._export_dir is None
            or self._params is None
            or self._model_version < 0
            or self._model_version
            < self._last_export_version + self._export_every
        ):
            return
        version = self._model_version
        try:
            with profiling.span("step/export", version=version):
                self._drain_ps_pushes()
                from elasticdl_tpu.common.export import export_model

                # streaming exports are params-only artifacts (no
                # serving_fn member): the scorer rebuilds the forward
                # from the provenance metadata, and elastic-embedding
                # forwards cannot serialize anyway (docs/export.md).
                # Staged in a dot-dir (invisible to the watcher, which
                # keys on <name>/MANIFEST.json of listed entries) and
                # RENAMED into place: multiple workers share one
                # export_dir and the shared version clock, so two can
                # hit the same cadence point — in-place writes would
                # let B rewrite an artifact A already manifest-sealed.
                # The rename is atomic and fails on an existing
                # non-empty target: first exporter wins, the loser
                # discards its identical staging copy.
                final = os.path.join(self._export_dir, "v%010d" % version)
                staging = os.path.join(
                    self._export_dir,
                    ".staging-v%010d-w%s" % (version, self._worker_id),
                )
                export_model(
                    staging,
                    self._params,
                    version,
                    metadata=self._export_meta,
                )
                import shutil

                try:
                    os.rename(staging, final)
                except OSError:
                    # another worker exported this version first
                    shutil.rmtree(staging, ignore_errors=True)
            self._prune_exports()
        except Exception:  # noqa: BLE001 — next cadence point retries
            logger.warning(
                "streaming export of v%d failed; retrying at the next "
                "cadence point",
                version,
                exc_info=True,
            )
        # advance the cadence clock even on failure: a persistently
        # failing export (full disk) must not turn into an attempt per
        # minibatch
        self._last_export_version = version

    def _prune_exports(self):
        """Drop the oldest complete artifacts beyond ``export_keep``."""
        import shutil

        try:
            versions = sorted(
                d
                for d in os.listdir(self._export_dir)
                if d.startswith("v")
                and os.path.exists(
                    os.path.join(self._export_dir, d, "MANIFEST.json")
                )
            )
        except OSError:
            return
        for stale in versions[: -self._export_keep]:
            shutil.rmtree(
                os.path.join(self._export_dir, stale),
                ignore_errors=True,
            )
        # crash-leaked staging dirs: a staging entry for a version
        # BELOW the oldest retained export can only belong to a dead
        # writer (a live one's version is at worst slightly behind the
        # newest; the retention window deep is unreachable lag) — a
        # loser of the rename race cleans its own staging inline
        if versions:
            floor = versions[0]
            for entry in os.listdir(self._export_dir):
                if not entry.startswith(".staging-"):
                    continue
                if entry.split("-")[1] < floor:
                    shutil.rmtree(
                        os.path.join(self._export_dir, entry),
                        ignore_errors=True,
                    )

    def _process_save_model_task_if_needed(self):
        task, dataset = (
            self._task_data_service.get_save_model_task_and_dataset()
        )
        if task is None or dataset is None:
            return
        self._drain_ps_pushes()
        # checkpoint/export boundary: settle acks before persisting
        self._task_data_service.drain_acks()
        saved_model_path = task.extended_config.get(
            SaveModelConfig.SAVED_MODEL_PATH
        )
        saved_model_path = os.path.join(
            saved_model_path, str(int(time.time()))
        )
        logger.info("The path to export model is %s" % saved_model_path)
        # Export = latest master parameters as the standard artifact
        # (common/export.py: orbax params + manifest + legacy codec +,
        # for dense models, a serialized serving forward). Replaces the
        # reference's tf.saved_model.save (reference worker.py:695-715).
        self.get_model(
            max(self._model_version, 0), GetModelMethod.MINIMUM
        )
        from elasticdl_tpu.common.export import (
            example_batch_for_export,
            export_model,
            make_serving_fn,
        )

        example = None
        if not self._embedding_dims:
            # elastic-embedding forwards leave the graph for their KV
            # lookup (host callback) — not serializable; dense models
            # ship the source-free serving plane
            example = example_batch_for_export(
                dataset,
                self._dataset_fn,
                self._task_data_service.data_reader.metadata,
                self._minibatch_size,
                Mode.PREDICTION,
            )
        extra_named = None
        if self._embedding_dims and self._ps_client is None:
            # master-central-storage mode: the embedding tables live in
            # the MASTER's KV store, not in self._params — get_model
            # strips their export keys by design, so without this pull
            # the artifact would silently drop every table (the gap
            # flagged at master/servicer._export_embedding_tables)
            export_tables = getattr(
                self._stub, "export_embedding_tables", None
            )
            if export_tables is not None:
                extra_named = export_tables()
        export_model(
            saved_model_path,
            self._params,
            self._model_version,
            metadata=self._export_meta,
            serving_fn=(
                make_serving_fn(self._model, self._state)
                if example is not None
                else None
            ),
            example_features=example,
            extra_named=extra_named,
        )
        self.report_task_result(task_id=task.task_id, err_msg="")

    # -- top-level loops ----------------------------------------------------

    def _train_and_evaluate(self):
        train_with_local_model = False
        local_update_count = self._get_model_steps
        last_training_minibatch_failed = False
        evaluation_task_executed = False
        while True:
            dataset = self._task_data_service.get_dataset()
            if not dataset:
                break
            dataset = self._dataset_fn(
                dataset,
                Mode.TRAINING,
                self._task_data_service.data_reader.metadata,
            )
            dataset = dataset.batch(self._minibatch_size).prefetch(1)
            if self._var_created and not self._embedding_dims:
                # double-buffer batches onto the device so host->device
                # transfer overlaps the previous step's compute. Gated
                # off for elastic-embedding models: their id capture
                # (_prepare_embedding_batch) reads ids on host, and for
                # the first round (variables not yet created) where the
                # init pass also wants host arrays.
                dataset = dataset.device_prefetch()
            batches_seen = 0
            for dataset_batch, next_batch in self._lookahead_pairs(dataset):
                batches_seen += 1
                if next_batch is not None:
                    # overlapped comm plane: batch N+1's embedding pull
                    # fans out on the pipeline thread while batch N's
                    # jitted step runs below (docs/embedding_planes.md)
                    self._kick_embedding_prefetch(next_batch)
                if self._job_type == JobType.TRAINING_WITH_EVALUATION:
                    if self._evaluate_only():
                        evaluation_task_executed = True

                task = self._task_data_service.get_current_task()
                if (
                    evaluation_task_executed
                    or last_training_minibatch_failed
                    or local_update_count >= self._get_model_steps
                ):
                    local_update_count = 0
                    train_with_local_model = False
                else:
                    train_with_local_model = True

                batch_count = self._batch_count(dataset_batch)
                # the dispatcher's task trace id labels the train span
                # (and, under an open profiler trace, its annotation),
                # so timelines join pull/prefetch/decode/train across
                # processes (docs/observability.md). The "step"
                # span is the per-minibatch trace root the critical-path
                # breakdown (tools/tracetool.py) decomposes; its
                # children (pull_model/compute/grad_push/...) inherit
                # trace and parent from the thread-local context.
                trace_id = (task.extended_config or {}).get(
                    "trace_id", "untraced"
                )
                with profiling.span(
                    "step",
                    trace_id=trace_id,
                    task=getattr(task, "task_id", None),
                    examples=batch_count,
                ):
                    err_msg = self._process_minibatch_and_report(
                        dataset_batch,
                        task.type,
                        task.model_version,
                        train_with_local_model,
                    )
                self._telemetry.on_batch(batch_count)
                self._maybe_streaming_export()
                local_update_count += 1
                if err_msg:
                    last_training_minibatch_failed = True
                    if self._emb_pipeline is not None:
                        # the failed task requeues: its prefetched
                        # embedding pull is dropped here EXACTLY ONCE
                        # (pipeline contract) — whichever worker re-runs
                        # those records pulls fresh rows
                        self._emb_pipeline.invalidate()
                else:
                    last_training_minibatch_failed = False
                    if local_update_count < self._get_model_steps:
                        self._update_local_model()
                self._task_data_service.report_record_done(
                    batch_count, err_msg
                )
            del dataset
            if self._emb_pipeline is not None:
                # round boundary: a pull staged past the stream's end
                # belongs to no batch anybody will run
                self._emb_pipeline.invalidate()
            # task boundary: settle the async push window and the task
            # ack queue before the next round's eval/save-model
            # decisions see model/dispatch state
            self._drain_ps_pushes()
            self._task_data_service.drain_acks()
            self._log_input_stats()
            if self._job_type == JobType.TRAINING_WITH_EVALUATION:
                evaluation_task_executed = self._evaluate_only()
            self._process_save_model_task_if_needed()
            if batches_seen == 0:
                # WAIT round with no data yet: back off instead of spinning
                time.sleep(0.2)

    def _evaluate_only(self):
        evaluation_task_executed = False
        while True:
            task = self.get_task(TaskType.EVALUATION)
            if not task.shard_name:
                break
            self._process_eval_task(task)
            evaluation_task_executed = True
        return evaluation_task_executed

    def _predict_only(self):
        while True:
            dataset = self._task_data_service.get_dataset()
            if not dataset:
                break
            dataset = self._dataset_fn(
                dataset,
                Mode.PREDICTION,
                self._task_data_service.data_reader.metadata,
            )
            dataset = dataset.batch(self._minibatch_size).prefetch(1)
            for dataset_batch in dataset:
                task = self._task_data_service.get_current_task()
                batch_count = self._batch_count(dataset_batch)
                err_msg = self._process_minibatch_and_report(
                    dataset_batch, task.type, task.model_version
                )
                self._telemetry.on_batch(batch_count)
                self._task_data_service.report_record_done(
                    batch_count, err_msg
                )
            del dataset
            self._task_data_service.drain_acks()
            self._log_input_stats()

    def _log_input_stats(self):
        """Log + reset the input-plane counters at a stream boundary."""
        stats = self._task_data_service.stats
        snap = stats.snapshot()
        if snap["tasks"] or snap["records"]:
            logger.info(stats.format_line())
        stats.reset()

    def run(self):
        """Fetch tasks from the master and train/evaluate/predict."""
        try:
            if self._job_type == JobType.PREDICTION_ONLY:
                self._predict_only()
            elif self._job_type == JobType.EVALUATION_ONLY:
                self._evaluate_only()
            else:
                self._train_and_evaluate()
        finally:
            # the prefetch thread must not outlive the worker, crash
            # paths included (conftest's leak check would flag it)
            if self._emb_pipeline is not None:
                self._emb_pipeline.close()
        self._drain_ps_pushes()
        # nothing may stay queued when the worker exits: the master's
        # doing-set must drain for the job to finish
        self._task_data_service.drain_acks()
        # final telemetry flush so short jobs still land one snapshot
        self._telemetry.ship(self._stub, force=True)
