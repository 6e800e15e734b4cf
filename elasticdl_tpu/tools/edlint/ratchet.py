"""Per-rule allowlist ratchets. EVERY entry carries a reason.

``ALLOW[rule_id][repo-relative-path] = {"max": n, "reason": "..."}`` —
a per-file MAXIMUM occurrence count for that rule, the same ratchet
discipline scripts/greps_guard.py established (its entries migrated
here with their reasons when the regexes became AST rules). New code
that trips a rule must adopt the safe pattern or consciously extend
this file, with a reason, in the same review; ``edlint --stale``
reports entries wider than current use so the ratchet only shrinks.
"""

ALLOW = {
    "R1": {
        # in-mesh sites: run strictly after establish()/backend init,
        # where a wedge would already have surfaced through the
        # escapable probe (migrated from greps_guard ALLOWED_DEVICES)
        "elasticdl_tpu/parallel/elastic.py": {
            "max": 1,
            "reason": "in-mesh enumeration after establish(); the "
            "escapable probe already verified this transport",
        },
        "elasticdl_tpu/parallel/mesh.py": {
            "max": 1,
            "reason": "mesh construction runs after backend init; a "
            "wedge surfaces in the establish-path probe first",
        },
        "elasticdl_tpu/worker/allreduce_worker.py": {
            "max": 1,
            "reason": "in-mesh device count after the backend is "
            "established",
        },
        "__graft_entry__.py": {
            "max": 2,
            "reason": "CPU-mesh dry run: both sites run in a fresh "
            "interpreter pinned to the virtual CPU platform, where "
            "there is no device transport to wedge",
        },
    },
    "R2": {
        "elasticdl_tpu/common/async_checkpoint.py": {
            "max": 2,
            "reason": "deliberate bounded backpressure: submit() "
            "blocking the training thread beats pinning unbounded "
            "full-model host snapshots; close() puts its sentinel "
            "after join() proved the queue empty",
        },
        "elasticdl_tpu/common/escapable.py": {
            "max": 2,
            "reason": "Queue(maxsize=1) with exactly one put per "
            "sacrificial daemon thread: space is guaranteed, the put "
            "cannot block",
        },
    },
    "R3": {
        "elasticdl_tpu/data/dataset.py": {
            "max": 2,
            "reason": "prefetch consumer gets: the producer ALWAYS "
            "delivers a terminal _END or exception sentinel through "
            "put_or_cancel, so the get cannot outlive its producer "
            "(plain + stats-timed site)",
        },
    },
    "R5": {
        "elasticdl_tpu/master/journal.py": {
            "max": 4,
            "reason": "the dedicated _io lock exists ONLY to serialize "
            "the journal file between the writer thread and the "
            "flush()/close() drain path; no RPC handler or hot-path "
            "lock ever takes it (append is enqueue-only under _mu), so "
            "holding it across the segment write/fsync/rotate is the "
            "point, not a hang risk — the dispatcher's ledger lock "
            "never reaches an fsync (the R5 target this plane was "
            "built around)",
        },
        "elasticdl_tpu/master/evaluation_service.py": {
            "max": 1,
            "reason": "the eval-checkpoint write runs under the master "
            "servicer's model lock ON PURPOSE (add_evaluation_task's "
            "docstring): the version guard, the snapshot write and the "
            "guard update must be atomic or the timer thread and the "
            "step-based gradient path queue duplicate rounds for the "
            "same version — the same accepted stall as the servicer's "
            "own checkpoint entry below",
        },
        "elasticdl_tpu/master/servicer.py": {
            "max": 4,
            "reason": "checkpoint writes deliberately run inside the "
            "model lock: the save must be atomic with the version "
            "guard and the (model, opt_state) read-modify-replace, or "
            "a concurrent report_gradient tears the snapshot; the "
            "master-central mode accepts the stall (the PS/async path "
            "does not take this lock). Moving the IO out needs a deep "
            "model copy per checkpoint — tracked as a possible "
            "follow-up, not a silent hang risk",
        },
        "elasticdl_tpu/ps/optimizer_wrapper.py": {
            "max": 4,
            "reason": "one-time lazy slot-table creation under the "
            "apply lock: a tiered slot table's constructor re-attaches "
            "spilled segments from disk, but only on the FIRST apply "
            "touching that table after a relaunch — and slot state "
            "must exist before the apply that needs it, under the "
            "same lock, or a concurrent apply reads half-built slots. "
            "The three ensure_rows/get sites are the tiered PROMOTION "
            "contract (docs/tiered_store.md): a cold row this apply "
            "needs must be read back from its spill segment before "
            "the update math runs, and that read has to finish while "
            "the apply lock serializes it against the demoter "
            "retiring the same segment — moving it off-lock reintroduces "
            "the read-after-retire race the tier design exists to kill",
        },
        "elasticdl_tpu/ps/servicer.py": {
            "max": 1,
            "reason": "the same one-shot slot-table re-attach chain as "
            "optimizer_wrapper.py, seen through the sync "
            "push_gradient apply under the accumulation lock; every "
            "recurring IO (snapshot capture/write) already runs off "
            "this lock",
        },
        "elasticdl_tpu/ps/tiered_store.py": {
            "max": 2,
            "reason": "imprecise union, not real IO under _mu: "
            "Parameters._new_table rebinds `table = "
            "TieredEmbeddingTable(table, ...)`, so the flow-"
            "insensitive ctor-arg typing unions the wrapper into its "
            "own `inner` param and self._inner.snapshot()/get() "
            "appear to reach segment reads. By construction _inner is "
            "the untiered table; snapshot()'s docstring documents "
            "that segments are read with no lock held",
        },
    },
    "R8": {
        "elasticdl_tpu/common/export.py": {
            "max": 1,
            "reason": "idempotent lazy init: two scorer threads racing "
            "serve()'s first call both deserialize the same on-disk "
            "bytes and rebind _serving atomically — the loser's object "
            "is garbage, never a torn read; a lock here would serialize "
            "every serve() for a once-per-process cost",
        },
        "elasticdl_tpu/master/evaluation_service.py": {
            "max": 2,
            "reason": "_last_snapshot_version's guard update always "
            "runs under the MASTER servicer's model lock (the "
            "master_locking=False callers are gradient threads that "
            "already hold it — a calling convention the analyzer "
            "cannot see), and the unlocked read it pairs with is the "
            "documented cheap pre-filter that _snapshot_model_locked "
            "re-validates under that lock; _round is the publish/"
            "snapshot idiom — written under _lock, read as a one-shot "
            "local with a None guard",
        },
        "elasticdl_tpu/rpc/core.py": {
            "max": 1,
            "reason": "stub-cache setdefault is the commented "
            "benign-race idiom: two fan-out legs racing a method's "
            "first call both build a stub, setdefault keeps exactly "
            "one, the loser is garbage — never a torn entry",
        },
        "elasticdl_tpu/rpc/failover.py": {
            "max": 1,
            "reason": "_reconnect's single atomic field rebind is the "
            "documented drop-not-close design: a concurrent call that "
            "still reads the retired client just burns one more "
            "UNAVAILABLE retry and reconnects itself; locking the "
            "swap would hold a lock across channel construction",
        },
        "elasticdl_tpu/worker/telemetry.py": {
            "max": 2,
            "reason": "single-writer counters: only the training loop "
            "thread runs on_batch's += on _steps/_examples, and the "
            "snapshot reader computes display rates where one-batch "
            "staleness is tolerated by construction (the next interval "
            "absorbs it)",
        },
        "elasticdl_tpu/master/journal.py": {
            "max": 9,
            "reason": "RecoveryState.apply writes race nothing: "
            "replay()'s fold runs strictly BEFORE start() spawns the "
            "writer thread (the only other RecoveryState toucher, "
            "always under _mu), and post-start applies happen inside "
            "append()'s _mu hold. The happens-before edge is the "
            "start() call itself, which the analyzer's thread-root "
            "model cannot see; locktrace runs the journal suite with "
            "no inversion",
        },
        "elasticdl_tpu/common/k8s_client.py": {
            "max": 1,
            "reason": "close()'s `watcher, self._watcher = "
            "self._watcher, None` is the deliberate detach-then-stop "
            "idiom: the GIL makes the field swap safe enough, _watch "
            "snapshots the field ONCE into a local before streaming, "
            "and both orderings of the race are benign (the thread "
            "exits on a stopped watcher or on the early-None check). "
            "A lock here would be held across Watch.stop()'s HTTP "
            "teardown",
        },
        "elasticdl_tpu/master/servicer.py": {
            "max": 2,
            "reason": "phase ordering the analyzer cannot see: "
            "set_model_var runs in the init handshake, strictly "
            "before any worker reports gradients against the model "
            "dict it fills; get_task's _version read is a deliberate "
            "lock-free monotonic-int snapshot for the response header "
            "(GIL-atomic, staleness tolerated by the version guard "
            "on the report side)",
        },
        "elasticdl_tpu/ps/parameters.py": {
            "max": 2,
            "reason": "first-write-wins publish: init paths install "
            "dict entries under _lock and never mutate them after; "
            "readers do a GIL-atomic dict get and the pull protocol "
            "guarantees init-before-read (get_embedding_param raises "
            "on a missing name rather than reading a torn value)",
        },
        "elasticdl_tpu/ps/tiered_store.py": {
            "max": 3,
            "reason": "_reattach runs only from __init__ on a table "
            "no other thread can reach yet — Parameters publishes "
            "the finished table first-write-wins under ITS lock "
            "afterwards; the 'racing' roots are the same constructor "
            "path reached from two RPC entry points",
        },
        "elasticdl_tpu/serving/scorer.py": {
            "max": 1,
            "reason": "publish-last flag: prepare() writes every "
            "cache-entry field and sets _prepared=True LAST, under "
            "_mu; predict() only dereferences the fields after "
            "observing _prepared (or after calling prepare itself), "
            "so the GIL's program-order visibility makes every read "
            "see fully-written fields — the classic double-checked "
            "publish the flow-insensitive lockset pairing cannot see",
        },
        "elasticdl_tpu/worker/ps_client.py": {
            "max": 1,
            "reason": "single atomic publish of a callback reference "
            "at wiring time, before the data-plane threads that read "
            "it exist; _service_reinit snapshots the field into a "
            "local and None-checks it, so both race orderings are "
            "benign (miss one reinit round at worst, re-armed by the "
            "epoch flag)",
        },
    },
    "R6": {
        "elasticdl_tpu/native/__init__.py": {
            "max": 2,
            "reason": "__del__ best-effort close: raising in a "
            "destructor aborts interpreter teardown and logging "
            "machinery may already be finalized there",
        },
        "elasticdl_tpu/common/tensor.py": {
            "max": 1,
            "reason": "WireArena.__del__ backstop release: same "
            "destructor discipline as native/__init__.py — raising "
            "or logging during interpreter teardown is unsafe, and "
            "the explicit release()/close() paths are the loud ones",
        },
    },
    "R10": {
        "elasticdl_tpu/common/tensor.py": {
            "max": 5,
            "reason": "host-side codec normalizations + the bridge "
            "fallback, none a device-payload staging: "
            "Tensor.__init__'s bare asarray runs only on NON-device "
            "values (device arrays bypass via is_device_array); "
            "pytree_to_named_arrays' pair is the checkpoint/export "
            "contract (keep_device=True is the wire path and skips "
            "asarray for device leaves); named_arrays_to_pytree "
            "restores host checkpoints. device_host_view's one "
            "jax.device_get call is the bridge's own fallback — a "
            "genuinely sharded or cross-device buffer dlpack cannot "
            "view; it IS the single D2H",
        },
        "elasticdl_tpu/rpc/core.py": {
            "max": 3,
            "reason": "the three contract-required materializations: "
            "two bytes(pack_message(...)) transport handoffs (cygrpc's "
            "SendMessageOperation is typed exact `bytes`; the shm slot "
            "path skips them) and the bytes-kind field decode in "
            "unpack_message (callers expect hashable owned bytes; "
            "tensor payloads never ride that field kind)",
        },
        "elasticdl_tpu/rpc/wire_compression.py": {
            "max": 1,
            "reason": "the one required decode materialization: an f32 "
            "consumer cannot read a bf16 payload in place, so "
            "decompress_tensors upcasts exactly once per compressed "
            "tensor (the encode direction is fused into the frame "
            "write and allocates nothing)",
        },
        "elasticdl_tpu/ps/device_store.py": {
            "max": 1,
            "reason": "the device->disk snapshot drain "
            "(DeviceEmbeddingTable.snapshot) deliberately "
            "host-stages: one batched jax.device_get of the arena "
            "under the table lock. The fancy-index slot gather that "
            "follows allocates a fresh buffer by construction, so the "
            "old defensive .copy() is gone (docs/ps_device.md)",
        },
        "elasticdl_tpu/ps/tiered_store.py": {
            "max": 1,
            "reason": "the ONE contract-required tier-crossing copy: "
            "the demoter's victim capture (_demote_once) must own its "
            "bytes — a device inner's get() may hand back a host view "
            "of a gather buffer the next donated apply retires, and "
            "the segment write happens OFF-lock on the demoter "
            "thread, after applies have resumed. Promotion and every "
            "other tier move stay zero-extra-copy "
            "(docs/tiered_store.md)",
        },
    },
}
