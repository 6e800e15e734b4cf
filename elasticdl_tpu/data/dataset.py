"""A tf.data-free streaming Dataset.

The reference hands user ``dataset_fn``s a ``tf.data.Dataset`` built from a
task-record generator (worker/task_data_service.py:126-188,
data/dataset_utils.py:4-24). This shim preserves the same fluent surface —
``map / filter / shuffle / batch / repeat / take / prefetch`` — over plain
Python iterators yielding numpy-structured elements, so model-zoo
``dataset_fn(dataset, mode, metadata)`` code ports contract-for-contract
without TensorFlow.

Elements are arbitrary pytrees (dicts/tuples) of np.ndarray-compatible
leaves; ``batch`` stacks leaf-wise. ``prefetch`` runs the upstream pipeline
in a daemon thread so host input overlaps TPU steps (the tf.data
``prefetch(1)`` role in reference worker.py:779).

Pipelined stages (docs/input_pipeline.md): ``map(fn, num_parallel_calls=N)``
decodes on a thread pool with a deterministic in-order merge and the same
cooperative-cancel discipline ``prefetch`` uses; ``batch`` assembles each
batch into preallocated per-leaf buffers filled in place (no per-element
``np.stack`` recursion). A Dataset can carry an
``input_stats.InputPlaneStats`` object; every transform propagates it and
charges its stage counter, so one object instruments a whole pipeline.
"""

import collections
import concurrent.futures
import queue
import random as _random
import threading
import time

import numpy as np


def _tree_stack(elements):
    """Stack a list of same-structure elements leaf-wise.

    Legacy per-element recursive assembly. Kept as the fallback for leaf
    types the preallocated fast path cannot host (bytes/str/object
    leaves, where a common dtype must be computed across the whole
    batch) and as the reference arm for equivalence tests.
    """
    first = elements[0]
    if isinstance(first, dict):
        return {
            k: _tree_stack([e[k] for e in elements]) for k in first
        }
    if isinstance(first, (tuple, list)):
        stacked = [
            _tree_stack([e[i] for e in elements]) for i in range(len(first))
        ]
        return tuple(stacked) if isinstance(first, tuple) else stacked
    return np.stack([np.asarray(e) for e in elements])


class _NoFastPath(Exception):
    """A leaf the vectorized batch assembly must not host."""


def _batch_buffers(first, n):
    """Same-structure tree of preallocated (n, *leaf.shape) buffers."""
    if isinstance(first, dict):
        return {k: _batch_buffers(v, n) for k, v in first.items()}
    if isinstance(first, (tuple, list)):
        bufs = [_batch_buffers(v, n) for v in first]
        return tuple(bufs) if isinstance(first, tuple) else bufs
    leaf = np.asarray(first)
    if leaf.dtype == object or leaf.dtype.kind in "USV":
        # strings/bytes/object need a common dtype computed across the
        # whole batch — np.stack's job, not a fixed-width buffer's
        raise _NoFastPath
    return np.empty((n,) + leaf.shape, leaf.dtype)


def _batch_fill(buf, element, i):
    """Write ``element``'s leaves into row ``i`` of the buffers in place."""
    if isinstance(buf, dict):
        for k in buf:
            _batch_fill(buf[k], element[k], i)
    elif isinstance(buf, (tuple, list)):
        for b, e in zip(buf, element):
            _batch_fill(b, e, i)
    else:
        leaf = np.asarray(element)  # no copy when already an ndarray
        if leaf.dtype != buf.dtype or leaf.shape != buf.shape[1:]:
            # a leaf whose dtype/shape differs from element 0's: raw
            # assignment would silently cast (int buffer truncating a
            # float leaf) or broadcast where np.stack would promote or
            # raise — only the legacy path has the right semantics
            raise _NoFastPath
        buf[i] = leaf


def _tree_assemble(elements):
    """Vectorized batch assembly: one preallocated buffer per leaf,
    filled row by row — no per-element ``np.stack`` recursion and no
    intermediate per-leaf element lists. Falls back to ``_tree_stack``
    for leaf types the fixed-width buffers cannot host (bytes/str/
    object) and for batches whose leaf dtypes/shapes vary across
    elements (np.stack's promotion semantics apply there)."""
    try:
        buffers = _batch_buffers(elements[0], len(elements))
        for i, e in enumerate(elements):
            _batch_fill(buffers, e, i)
    except _NoFastPath:
        return _tree_stack(elements)
    return buffers


class Dataset:
    """Lazily-evaluated record stream; each transform returns a new Dataset."""

    def __init__(self, gen_factory, stats=None):
        self._gen_factory = gen_factory
        # optional InputPlaneStats; inherited by every derived Dataset so
        # one object instruments the whole pipeline (map charges parse_s,
        # batch charges batch_s, prefetch charges consumer_starved_s)
        self._stats = stats

    @staticmethod
    def from_generator(gen_factory, stats=None):
        """gen_factory: zero-arg callable returning a fresh iterator."""
        return Dataset(gen_factory, stats=stats)

    @staticmethod
    def from_tensors(elements):
        elements = list(elements)
        return Dataset(lambda: iter(elements))

    def map(self, fn, num_parallel_calls=None):
        """Apply ``fn`` per element; with ``num_parallel_calls`` > 1 run it
        on a thread pool with a DETERMINISTIC IN-ORDER merge.

        Parallel semantics match the serial path exactly: elements come
        out in input order, and an exception raised by ``fn`` on element
        i surfaces to the consumer after element i-1, however the pool
        interleaved the calls. The pool is cooperatively cancelled when
        the consumer generator is closed/abandoned (same discipline as
        ``prefetch``): no new elements are pulled from the source and
        unconsumed futures are cancelled.
        """
        stats = self._stats
        # parse timing accumulates in generator locals and hits the
        # (locked) stats object once at the end, not per record — the
        # same discipline task_data_service._yield_records uses; with a
        # decode pool, per-record stats.add would make N threads
        # contend on one lock at exactly the stage being parallelized.
        if not num_parallel_calls or num_parallel_calls <= 1:

            def gen():
                if stats is None:
                    for x in self._gen_factory():
                        yield fn(x)
                    return
                parse_s = 0.0
                perf = time.perf_counter
                try:
                    for x in self._gen_factory():
                        t0 = perf()
                        out = fn(x)
                        parse_s += perf() - t0
                        yield out
                finally:
                    stats.add("parse_s", parse_s)

            return Dataset(gen, stats=stats)

        window = 2 * num_parallel_calls

        if stats is None:
            apply = fn
        else:

            def apply(x):
                # duration rides back with the result; the merge loop
                # accumulates it lock-free
                t0 = time.perf_counter()
                out = fn(x)
                return time.perf_counter() - t0, out

        def gen():
            pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=num_parallel_calls,
                thread_name_prefix="edl-map",
            )
            pending = collections.deque()
            parse_s = 0.0

            def resolve(future):
                # .result() re-raises fn's exception at the failing
                # element's ordinal position
                if stats is None:
                    return future.result()
                nonlocal parse_s
                dt, out = future.result()
                parse_s += dt
                return out

            try:
                for x in self._gen_factory():
                    pending.append(pool.submit(apply, x))
                    if len(pending) >= window:
                        yield resolve(pending.popleft())
                while pending:
                    yield resolve(pending.popleft())
            finally:
                # normal exhaustion, an fn error, or an abandoned
                # consumer: stop pulling from the source (the loop above
                # is consumer-driven, so exiting it IS the stop), drop
                # not-yet-started work, don't block on in-flight calls
                pool.shutdown(wait=False, cancel_futures=True)
                if stats is not None:
                    stats.add("parse_s", parse_s)

        return Dataset(gen, stats=stats)

    def filter(self, pred):
        def gen():
            for x in self._gen_factory():
                if pred(x):
                    yield x

        return Dataset(gen, stats=self._stats)

    def shuffle(self, buffer_size, seed=None, reshuffle_each_iteration=True):
        """Streaming buffer shuffle with tf.data semantics.

        Like tf.data, each iteration reshuffles by default: a seeded
        dataset is deterministic WITHIN one iteration, but a ``repeat``
        re-iteration draws a different order (epoch 2 must not replay
        epoch 1's order). ``reshuffle_each_iteration=False`` restores
        the identical-replay behavior.
        """
        iteration = collections.deque((0,))  # mutable epoch counter

        def gen():
            epoch = iteration[0]
            iteration[0] = epoch + 1
            if seed is None:
                rng = _random.Random()
            elif reshuffle_each_iteration:
                # distinct deterministic stream per iteration
                rng = _random.Random(seed * 0x9E3779B1 + epoch)
            else:
                rng = _random.Random(seed)
            buf = []
            for x in self._gen_factory():
                buf.append(x)
                if len(buf) >= buffer_size:
                    i = rng.randrange(len(buf))
                    buf[i], buf[-1] = buf[-1], buf[i]
                    yield buf.pop()
            rng.shuffle(buf)
            yield from buf

        return Dataset(gen, stats=self._stats)

    def batch(self, batch_size, drop_remainder=False, vectorized=True):
        """Group ``batch_size`` elements into one stacked pytree.

        ``vectorized`` (default) assembles each batch into preallocated
        per-leaf buffers filled in place — one pass, no per-element
        ``np.stack`` recursion; False keeps the legacy ``_tree_stack``
        path (the reference arm of tests/test_input_pipeline.py's
        equivalence cases). Both produce
        identical arrays for numeric pytrees; bytes/str/object leaves
        take the legacy path either way.
        """
        assemble = _tree_assemble if vectorized else _tree_stack
        stats = self._stats

        if stats is None:
            emit = assemble
        else:

            def emit(batch):
                t0 = time.perf_counter()
                out = assemble(batch)
                stats.add("batch_s", time.perf_counter() - t0)
                stats.count("batches")
                return out

        def gen():
            batch = []
            for x in self._gen_factory():
                batch.append(x)
                if len(batch) == batch_size:
                    yield emit(batch)
                    batch = []
            if batch and not drop_remainder:
                yield emit(batch)

        return Dataset(gen, stats=stats)

    def repeat(self, count=None):
        def gen():
            n = 0
            while count is None or n < count:
                it = self._gen_factory()
                empty = True
                for x in it:
                    empty = False
                    yield x
                if empty:
                    return
                n += 1

        return Dataset(gen, stats=self._stats)

    def take(self, n):
        def gen():
            for i, x in enumerate(self._gen_factory()):
                if i >= n:
                    return
                yield x

        return Dataset(gen, stats=self._stats)

    def prefetch(self, buffer_size=1):
        """Run the upstream pipeline in a background thread.

        The producer is COOPERATIVELY CANCELLED when the consumer
        generator is closed/garbage-collected (an elastic spare park
        abandons its round mid-stream): without the cancel, a producer
        blocked on a full queue would leak forever, and one mid-
        ``get_task`` could keep pulling new work for a consumer that is
        gone."""

        def gen():
            q = queue.Queue(maxsize=max(1, buffer_size))
            _END = object()
            cancel = threading.Event()

            def put_or_cancel(item):
                """True once ``item`` is enqueued; False if cancelled
                first. EVERY producer put goes through here — including
                the terminal _END and exception sentinels: an unbounded
                q.put of those would block forever when the consumer was
                abandoned with a full queue right as the source
                exhausted (or raised), the exact leak the cooperative
                cancel exists to prevent."""
                while not cancel.is_set():
                    try:
                        q.put(item, timeout=0.5)
                        return True
                    except queue.Full:
                        continue
                return False

            def produce():
                try:
                    for x in self._gen_factory():
                        if not put_or_cancel(x):
                            return
                    put_or_cancel(_END)
                except BaseException as e:  # propagate into consumer
                    put_or_cancel(e)

            t = threading.Thread(target=produce, daemon=True)
            t.start()
            stats = self._stats
            try:
                while True:
                    if stats is None:
                        item = q.get()
                    else:
                        # a consumer blocked here is STARVED: the device
                        # outran the host input pipeline
                        t0 = time.perf_counter()
                        item = q.get()
                        stats.add(
                            "consumer_starved_s",
                            time.perf_counter() - t0,
                        )
                    if item is _END:
                        return
                    if isinstance(item, BaseException):
                        raise item
                    yield item
            finally:
                # runs on normal exhaustion, close(), and GC of an
                # abandoned consumer — the producer exits at its next
                # queue-put or cancellation check
                cancel.set()

        return Dataset(gen, stats=self._stats)

    def device_prefetch(self, buffer_size=2, placement=None):
        """Move elements to device ahead of consumption (double buffering).

        ``jax.device_put`` dispatches asynchronously, so keeping
        ``buffer_size`` batches in flight overlaps host->device transfer
        with the device compute consuming the previous batch — the role
        ``flax.jax_utils.prefetch_to_device`` plays in pmap pipelines.
        ``placement`` is an optional ``jax.sharding.Sharding`` (or
        device) for multi-chip batch layouts; default is the default
        device.

        Call it LAST in the pipeline (after ``batch``/``prefetch``):
        downstream host-side transforms on device arrays would bounce
        every element back. No TPU-memory risk at sane sizes: in-flight
        elements are bounded by ``buffer_size``.
        """

        def gen():
            import collections

            import jax

            def put(x):
                if placement is None:
                    return jax.device_put(x)
                return jax.device_put(x, placement)

            buf = collections.deque()
            for x in self._gen_factory():
                buf.append(put(x))
                if len(buf) > max(1, buffer_size):
                    yield buf.popleft()
            while buf:
                yield buf.popleft()

        return Dataset(gen, stats=self._stats)

    def __iter__(self):
        return iter(self._gen_factory())

    def as_numpy_iterator(self):
        return iter(self)


def create_dataset_from_tasks(tasks, data_reader):
    """Dataset over the records of a fixed task list.

    Parity: reference data/dataset_utils.py:4-24.
    """

    def gen():
        for task in tasks:
            yield from data_reader.read_records(task)

    return Dataset.from_generator(gen)
