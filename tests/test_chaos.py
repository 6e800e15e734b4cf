"""The PS recovery plane's reconnect protocol + the scripted fault
plane (docs/ps_recovery.md).

In-process and deterministic: servicers stand in for PS pods, a
relaunch is a servicer swap behind a stable stub (exactly the
same-id/same-address contract the instance manager provides), and
chaos scripts replay exact fault interleavings. Pins the four
client-side reconnect obligations — epoch detection, shard-selective
cache invalidation with a re-anchored version clock, in-flight push
window abandonment (dropped, NEVER resent), and model re-push on an
uninitialized relaunch — plus the scripted fault plane's determinism.
"""

import threading
import time

import numpy as np
import optax
import pytest

from elasticdl_tpu.common.tensor import Tensor
from elasticdl_tpu.ps.parameters import EmbeddingTableInfo, Parameters
from elasticdl_tpu.ps.servicer import PserverServicer
from elasticdl_tpu.ps.snapshot import ShardSnapshotter
from elasticdl_tpu.tools.chaos import (
    ChaosOp,
    ChaosPartitionError,
    FleetChaos,
    ScriptedFaultPS,
    seeded_schedule,
)
from elasticdl_tpu.utils import profiling
from elasticdl_tpu.worker.ps_client import PSClient


def make_servicer(epoch, snapshotter=None, restored=None, use_async=True):
    p = Parameters()
    return PserverServicer(
        p,
        1,
        optax.sgd(0.1),
        use_async=use_async,
        snapshotter=snapshotter,
        shard_epoch=epoch,
        restored_version=restored,
    )


class Swappable:
    """Stable stub fronting a swappable servicer — the same-id relaunch
    seam (workers keep their address; the incarnation behind changes)."""

    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, method):
        return getattr(self.inner, method)


def push_model(client, n_dense=4, dims=4):
    model = {
        "w%d" % i: np.full((2, 2), float(i + 1), np.float32)
        for i in range(n_dense)
    }
    client.push_model(model, [EmbeddingTableInfo("emb", dims)])


# ---------------------------------------------------------------------------
# the reconnect protocol
# ---------------------------------------------------------------------------


def test_epoch_change_invalidates_only_that_shards_cache(tmp_path):
    snap = ShardSnapshotter(str(tmp_path), every_versions=1)
    s0 = make_servicer(1, snapshotter=snap)
    s1 = make_servicer(21)
    shard0 = Swappable(s0)
    client = PSClient(
        [shard0, s1], hot_row_cache_rows=64, staleness_window=8
    )
    try:
        push_model(client)
        client.pull_embedding_vectors("emb", np.arange(6))
        assert len(client.hot_row_cache) == 6
        client.push_gradient(
            {},
            [
                Tensor(
                    "emb",
                    np.ones((2, 4), np.float32),
                    indices=np.array([0, 2]),
                )
            ],
            0,
        )
        snap.wait()

        # relaunch shard 0 restored from its snapshot, new epoch
        p2 = Parameters()
        snap2 = ShardSnapshotter(str(tmp_path), every_versions=1)
        restored = snap2.restore_into(p2)
        assert restored == 1
        shard0.inner = PserverServicer(
            p2, 1, optax.sgd(0.1), use_async=True,
            shard_epoch=2, restored_version=restored,
        )
        profiling.events.reset()
        ok, version, _ = client.pull_dense()
        assert ok
        # shard 0's (even-id) entries dropped, shard 1's kept
        probe = client.hot_row_cache.get_rows("emb", np.arange(6))
        assert [r is not None for r in probe] == [
            False, True, False, True, False, True,
        ]
        assert client.shard_epochs[0] == 2
        assert client.shard_epochs[1] == 21
        events = [
            e
            for e in profiling.events.tail()
            if e["kind"] == "ps_shard_restore"
        ]
        assert len(events) == 1
        assert events[0]["shard"] == 0
        assert events[0]["old_epoch"] == 1
        assert events[0]["new_epoch"] == 2
        assert events[0]["rollback_depth"] >= 0
        snap2.close()
    finally:
        client.close()
        snap.close()


def test_version_clock_reanchors_after_rollback(tmp_path):
    """The max-only note_version clock would hold the dead
    incarnation's high-water mark and turn every post-restore pull into
    an instant stale miss; invalidate_shard must re-anchor it."""
    snap = ShardSnapshotter(str(tmp_path), every_versions=2)
    s0 = make_servicer(1, snapshotter=snap)
    shard0 = Swappable(s0)
    client = PSClient([shard0], hot_row_cache_rows=64, staleness_window=1)
    try:
        push_model(client)
        # advance past the snapshot cadence; the last push (version 5)
        # is NOT snapshotted, so the relaunch rolls back to v4
        for i in range(5):
            client.push_gradient(
                {},
                [
                    Tensor(
                        "emb",
                        np.ones((1, 4), np.float32),
                        indices=np.array([0]),
                    )
                ],
                i,
            )
        snap.wait()
        p2 = Parameters()
        snap2 = ShardSnapshotter(str(tmp_path), every_versions=2)
        restored = snap2.restore_into(p2)
        assert restored is not None and restored < 5
        shard0.inner = PserverServicer(
            p2, 1, optax.sgd(0.1), use_async=True, shard_epoch=2,
        )
        client.pull_dense()  # detects the epoch change
        rows = client.pull_embedding_vectors("emb", np.arange(4))
        assert rows.shape == (4, 4)
        # rows pulled from the ROLLED-BACK version must be cache hits
        # on the very next probe (no permanent miss storm)
        hits_before = client.hot_row_cache.hits
        client.pull_embedding_vectors("emb", np.arange(4))
        assert client.hot_row_cache.hits >= hits_before + 4
        snap2.close()
    finally:
        client.close()
        snap.close()


def test_epoch_bumped_shard_never_gets_a_resent_push():
    """THE non-idempotency pin (ISSUE 10 satellite): an in-flight push
    that raced a shard relaunch is dropped — the restored incarnation
    must never see it again, and drain() must not re-raise its
    failure."""
    s0 = make_servicer(1)
    shard0 = Swappable(s0)
    client = PSClient([shard0], push_inflight=1)
    release = threading.Event()
    calls = {"push": 0}

    class GatedPS:
        """First push parks until released, then fails — the in-flight
        window racing a dying pod."""

        def __getattr__(self, method):
            inner = getattr(s0, method)
            if method != "push_gradient":
                return inner

            def push(req):
                calls["push"] += 1
                release.wait(timeout=5)
                raise RuntimeError("connection lost mid-push")

            return push

    try:
        push_model(client)
        shard0.inner = GatedPS()
        client.push_gradient(
            {},
            [
                Tensor(
                    "emb",
                    np.ones((1, 4), np.float32),
                    indices=np.array([0]),
                )
            ],
            0,
        )
        # the relaunch happens while that push is still in flight: wait
        # until the pool's thread has reached the parked stub (it lost
        # the race with the two constructors below on a slow wake-up,
        # and then pushed to the relaunched shard it was never sent to)
        in_flight = time.time() + 5
        while not calls["push"] and time.time() < in_flight:
            time.sleep(0.001)
        p2 = Parameters()
        relaunched = PserverServicer(
            p2, 1, optax.sgd(0.1), use_async=True, shard_epoch=2,
        )
        pushes_seen = []
        orig_push = relaunched.push_gradient
        relaunched.push_gradient = lambda req: pushes_seen.append(req) or (
            orig_push(req)
        )
        shard0.inner = relaunched
        # detection: a status reply from the new incarnation
        client._note_shard_reply(0, relaunched.ps_status({}))
        release.set()
        accepted, version = client.drain()  # must NOT raise
        assert accepted
        # the gated push died once and was never replayed anywhere
        assert calls["push"] == 1
        assert pushes_seen == []
        events = [
            e
            for e in profiling.events.tail()
            if e["kind"] == "ps_push_window_dropped"
        ]
        assert events, "the dropped window must be telemetered"
    finally:
        client.close()


def test_stale_reply_from_dead_incarnation_is_ignored():
    """Epochs are monotonic: a delayed reply from the DEAD incarnation
    (a fan-out leg that resolved after the relaunch was detected) must
    not regress the epoch record or spuriously re-run the reset
    against the live incarnation."""
    s0 = make_servicer(1)
    shard0 = Swappable(s0)
    client = PSClient([shard0], hot_row_cache_rows=16, staleness_window=8)
    try:
        push_model(client, n_dense=1)
        shard0.inner = make_servicer(2)
        client.pull_dense()  # detect the relaunch
        assert client.shard_epochs[0] == 2
        push_model(client, n_dense=1)  # re-init the empty incarnation
        gen = client._gen_snapshot()
        client.pull_embedding_vectors("emb", np.array([0]))
        assert len(client.hot_row_cache) == 1
        # the dead incarnation's delayed reply arrives now
        client._note_shard_reply(0, s0.ps_status({}))
        assert client.shard_epochs[0] == 2  # not regressed
        assert client._gen_snapshot() == gen  # no spurious reset
        assert len(client.hot_row_cache) == 1  # cache untouched
    finally:
        client.close()


def test_reinit_flag_survives_a_failed_repush():
    """A transient failure of the re-push callback must re-arm the
    reinit flag — losing it would wedge every later pull against the
    still-empty shard."""
    s0 = make_servicer(1)
    shard0 = Swappable(s0)
    client = PSClient([shard0])
    attempts = []

    def flaky_reset(shards):
        attempts.append(tuple(shards))
        if len(attempts) == 1:
            raise RuntimeError("shard still flapping")

    client.set_on_shard_reset(flaky_reset)
    try:
        push_model(client, n_dense=1)
        shard0.inner = make_servicer(2)  # empty relaunch
        client.pull_dense()  # detects; marks needs_reinit
        with pytest.raises(RuntimeError):
            client.pull_dense()  # first service attempt fails
        client.pull_dense()  # re-armed: runs again and succeeds
        assert attempts == [(0,), (0,)]
    finally:
        client.close()


def test_uninitialized_relaunch_triggers_model_repush():
    """Relaunch with NO snapshot: the shard reports uninitialized and
    the client's next data-plane call re-pushes the model + infos via
    the on_shard_reset callback (first-write-wins on live shards)."""
    s0 = make_servicer(1)
    s1 = make_servicer(11)
    shard0 = Swappable(s0)
    client = PSClient([shard0, s1])
    resets = []
    client.set_on_shard_reset(lambda shards: resets.append(tuple(shards)))
    try:
        push_model(client)
        shard0.inner = make_servicer(2)  # empty relaunch
        ok, _, _ = client.pull_dense()
        assert not ok  # uninitialized surfaces, never wedges
        assert resets == []  # marked during the pull; served on the NEXT call
        ok, _, _ = client.pull_dense()
        assert resets == [(0,)]
    finally:
        client.close()


def test_dead_shard_probe_detects_relaunch_before_retry():
    """A data-plane failure probes ps_status: when the shard is already
    back as a new incarnation, the reset runs BEFORE the worker's retry
    re-pulls — the retry sees an invalidated cache, not stale rows."""
    s0 = make_servicer(1)
    shard0 = Swappable(s0)
    client = PSClient([shard0], hot_row_cache_rows=16, staleness_window=8)

    class DeadOnData:
        """Data RPCs fail (pod died mid-relaunch); ps_status answers
        from the NEW incarnation (it came back between the failure and
        the probe)."""

        def __init__(self, new_servicer):
            self._new = new_servicer

        def __getattr__(self, method):
            if method == "ps_status":
                return self._new.ps_status

            def dead(req):
                raise RuntimeError("UNAVAILABLE: shard relaunching")

            return dead

    try:
        push_model(client, n_dense=1)
        client.pull_embedding_vectors("emb", np.array([0, 2]))
        assert len(client.hot_row_cache) == 2
        new_inc = make_servicer(2)
        shard0.inner = DeadOnData(new_inc)
        # uncached ids force a wire pull, which hits the dead data path
        with pytest.raises(RuntimeError):
            client.pull_embedding_vectors("emb", np.array([4, 6]))
        # the probe already ran the reset: epoch recorded, cache empty
        assert client.shard_epochs[0] == 2
        assert len(client.hot_row_cache) == 0
    finally:
        client.close()


# ---------------------------------------------------------------------------
# the scripted fault plane
# ---------------------------------------------------------------------------


def test_scripted_fault_ps_partition_window_is_deterministic():
    s = make_servicer(1)
    faulty = ScriptedFaultPS(
        s, [ChaosOp("partition", 0, at_call=2, n_calls=2)], shard=0
    )
    client = PSClient([faulty], fanout=False)
    push_model(client, n_dense=1)  # calls 0 (push_model)
    client.pull_dense()  # call 1
    for _ in range(2):  # calls 2, 3: the window
        with pytest.raises(ChaosPartitionError):
            client.pull_dense()
    ok, _, _ = client.pull_dense()  # call 4: window closed
    assert ok
    assert [op.kind for op, _ in faulty.executed] == [
        "partition",
        "partition",
    ]
    client.close()


def test_scripted_fault_ps_kill_at_version_latches_until_revive():
    s = make_servicer(1)
    faulty = ScriptedFaultPS(
        s, [ChaosOp("kill", 0, at_version=2)], shard=0
    )
    client = PSClient([faulty], fanout=False)
    push_model(client, n_dense=1)
    grad = [
        Tensor("emb", np.ones((1, 4), np.float32), indices=np.array([0]))
    ]
    client.push_gradient({}, grad, 0)  # version 1
    client.push_gradient({}, grad, 1)  # version 2
    with pytest.raises(ChaosPartitionError):
        client.push_gradient({}, grad, 2)  # at_version crossed: dead
    with pytest.raises(ChaosPartitionError):
        client.pull_dense()  # stays dead (latched)
    # the relaunch: restored incarnation behind the same stub. Its
    # version may still be >= at_version (a cadence snapshot can
    # publish exactly at the kill point) — the one-shot op must NOT
    # re-fire, or revive() could never succeed
    restored = make_servicer(2)
    restored._parameters.version = 5
    faulty.revive(restored)
    st = faulty.ps_status({})
    assert st["shard_epoch"] == 2
    client.pull_dense()  # counted call: would re-kill without the latch
    client.close()


def test_scripted_fault_ps_reject_window():
    s = make_servicer(1)
    faulty = ScriptedFaultPS(
        s, [ChaosOp("reject", 0, at_call=1, n_calls=1)], shard=0
    )
    client = PSClient([faulty], fanout=False)
    push_model(client, n_dense=1)
    grad = [
        Tensor("emb", np.ones((1, 4), np.float32), indices=np.array([0]))
    ]
    accepted, _ = client.push_gradient({}, grad, 0)
    assert not accepted  # forced rejection, still applied/forwarded
    accepted, _ = client.push_gradient({}, grad, 1)
    assert accepted
    client.close()


def test_seeded_schedule_is_reproducible():
    a = seeded_schedule(42, num_ps=4, max_version=9, n_ops=3)
    b = seeded_schedule(42, num_ps=4, max_version=9, n_ops=3)
    assert [(o.kind, o.shard, o.at_version) for o in a] == [
        (o.kind, o.shard, o.at_version) for o in b
    ]
    c = seeded_schedule(43, num_ps=4, max_version=9, n_ops=3)
    assert [(o.shard, o.at_version) for o in a] != [
        (o.shard, o.at_version) for o in c
    ] or [o.kind for o in a] != [o.kind for o in c]


def test_fleet_chaos_fires_once_at_version_crossing():
    killed = []

    class Manager:
        def kill_ps(self, shard):
            killed.append(("kill", shard))

        def terminate_ps(self, shard):
            killed.append(("term", shard))

    versions = {0: 0, 1: 0}

    def status_fn(shard):
        return {"version": versions[shard]}

    chaos = FleetChaos(
        Manager(),
        status_fn,
        [ChaosOp("kill", 0, at_version=3)],
        poll_s=0.01,
    ).start()
    try:
        import time

        deadline = time.monotonic() + 5
        versions[0] = 2
        time.sleep(0.05)
        assert killed == []  # below the trigger
        versions[0] = 3
        while not chaos.done() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert chaos.done()
        time.sleep(0.05)  # no double fire on later polls
        assert killed == [("kill", 0)]
    finally:
        chaos.stop()


def test_fleet_chaos_master_op_fires_at_done_count():
    """Scripted master outages (docs/master_recovery.md): a
    kill_master op triggers on the master journal's cumulative
    done-task count, polled through master_status — and fires once."""
    import time

    executed = []

    class Manager:
        def kill_master(self):
            executed.append("kill_master")

        def terminate_master(self):
            executed.append("term_master")

    status = {"version": 0, "journal": {"done": 0}}
    chaos = FleetChaos(
        Manager(),
        lambda shard: {},
        [ChaosOp("kill_master", -1, at_done=3)],
        poll_s=0.01,
        master_status_fn=lambda: status,
    ).start()
    try:
        time.sleep(0.05)
        assert executed == []
        status["journal"]["done"] = 3
        deadline = time.monotonic() + 5
        while not chaos.done() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert chaos.done()
        time.sleep(0.05)
        assert executed == ["kill_master"]
    finally:
        chaos.stop()


def test_local_instance_manager_supervises_master(tmp_path):
    """The external-supervisor form: SIGTERM's rc-75 drain relaunches
    the master WITHOUT spending the crash budget (PS-plane parity);
    SIGKILL relaunches on the budget."""
    import sys
    import time

    from elasticdl_tpu.master.local_instance_manager import (
        LocalInstanceManager,
    )

    ready = tmp_path / "master-ready"

    def master_cmd():
        return [
            sys.executable,
            "-c",
            "import signal, sys, time\n"
            "signal.signal(signal.SIGTERM, lambda *a: sys.exit(75))\n"
            # readiness marker AFTER the handler is installed: the
            # drain test must not SIGTERM a still-booting interpreter
            "open(%r, 'w').close()\n"
            "while True:\n"
            "    time.sleep(0.1)\n" % str(ready),
        ]

    def wait_ready(deadline_s=15):
        deadline = time.monotonic() + deadline_s
        while time.monotonic() < deadline:
            if ready.exists():
                return True
            time.sleep(0.05)
        return False

    class _NoTasks:
        def recover_tasks(self, worker_id):
            pass

    lim = LocalInstanceManager(
        _NoTasks(),
        num_workers=0,
        worker_command=lambda wid: [],
        master_command=master_cmd,
        max_relaunches=2,
        log_dir=str(tmp_path),
    )
    try:
        lim.start_master()
        assert wait_ready(), "supervised master never came up"
        assert lim.live_master()

        # graceful drain: exit 75, relaunched, budget untouched
        ready.unlink()
        lim.terminate_master()
        assert wait_ready(), "rc-75 drain must relaunch the master"
        assert lim.live_master()
        assert lim.exit_codes[("master", 0)] == 75
        assert lim._relaunches == 0, "rc-75 must not spend the budget"

        # hard kill: relaunched on the crash budget
        ready.unlink()
        lim.kill_master()
        assert wait_ready(), "SIGKILL must relaunch the master"
        assert lim.live_master()
        deadline = time.monotonic() + 5
        while lim._relaunches == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert lim._relaunches == 1
    finally:
        lim.stop_relaunch_and_remove_all_pods()
