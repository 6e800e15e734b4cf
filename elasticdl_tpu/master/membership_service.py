"""Membership epochs for the elastic allreduce plane.

The reference's elasticity loop is pod-level: the instance manager watches
pods and, on deletion, re-queues tasks and relaunches
(reference master/k8s_instance_manager.py:177-231). That suffices for PS
training because workers never talk to each other. The allreduce plane
adds a second requirement: every worker holds a slot in one global device
mesh, so membership changes must be *coordinated* — survivors and joiners
have to agree on a world (size, ranks, coordinator address) before any
collective can run.

This service is that agreement point. It lives in the master (the single
source of truth for task dispatch already) and speaks three verbs:

- ``register(worker_id, host)`` — a worker process announces itself;
  the world grows at the next epoch bump.
- ``remove(worker_id)`` — instance-manager death event; the world shrinks.
- ``get_world(worker_id)`` — poll: returns the current epoch's
  :class:`~elasticdl_tpu.parallel.distributed.WorldSpec` fields for that
  worker, or ``ready=False`` while the world is forming.

Epoch rules: the first world forms when ``expected`` workers have
registered (or ``form_grace_secs`` after the first registration, so a
crashed launch can't wedge the job). Every later membership change bumps
the epoch and recomputes the world as the sorted live set. Ranks are
assigned by ascending worker id; relaunched workers get fresh, higher ids
(reference next_worker_id semantics), so rank 0 is always the
longest-lived survivor — the state-broadcast source after a re-form.

Bump discipline: deaths bump the epoch *immediately* (push-based — the
instance manager's watch callback fires the moment a process/pod dies,
reference k8s_instance_manager.py:177-231, so recovery never waits out a
poll window). Growth is *coalesced*: a joiner that registers while a
formation is still in flight parks in a lobby and folds in at the next
bump — bumping mid-formation would strand members that already took the
ready spec inside a stale ``jax.distributed.initialize`` barrier, where
they burn the whole init timeout and then get fenced as unresponsive.
Formation completion is inferred from traffic that already exists: a
member's first ``awaiting=False`` poll of an epoch means it established
that world and is training (elastic_allreduce_worker polls that way once
per step).

Each epoch gets a fresh coordinator port so a stale coordination service
from the previous world can never be mistaken for the new one.
"""

import socket
import threading
import time

from elasticdl_tpu.common.log_utils import default_logger as logger


# How long a death bump may wait for a warmed standby's registration
# (one combined formation instead of shrink-then-grow). MUST stay well
# below the workers' failure-recovery poll window
# (ElasticAllReduceWorker epoch_poll_secs, default 10 s): survivors of
# the broken collective wait at most that long in _await_epoch_bump for
# the (deferred) bump before giving up and crashing out.
DEATH_BUMP_DEFER_SECS = 6.0


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


class StandbyPool:
    """Pre-warmed spare workers, parked until a death needs one.

    A standby process pays its cold start (jax import, module loading)
    up front and then polls :meth:`poll` with its token; it is invisible
    to membership until the instance manager :meth:`activate`-s it with
    a real worker id, at which point the poll returns that id and the
    standby proceeds into the ordinary worker path. This converts the
    relaunch cost of a kill — measured at ~45-50 s of a ~65 s total
    recovery, almost all of it a fresh process importing jax — into
    membership-only cost."""

    def __init__(self):
        self._lock = threading.Lock()
        self._parked = {}  # token -> assigned worker id (None = parked)

    def poll(self, token):
        """Standby heartbeat; registers the token on first call and
        returns the assigned worker id once activated (else None)."""
        with self._lock:
            if token not in self._parked:
                self._parked[token] = None
            return self._parked[token]

    def activate(self, worker_id):
        """Hand ``worker_id`` to any parked standby; returns its token,
        or None when no WARMED standby is available (a spawned-but-not-
        yet-polling process is still paying its cold start and would
        give no head start)."""
        with self._lock:
            for token, assigned in self._parked.items():
                if assigned is None:
                    self._parked[token] = worker_id
                    return token
            return None

    def forget(self, token):
        with self._lock:
            self._parked.pop(token, None)

    def parked_count(self):
        with self._lock:
            return sum(
                1 for v in self._parked.values() if v is None
            )


class MembershipService:
    def __init__(
        self,
        expected_workers,
        base_port=0,
        form_grace_secs=30.0,
        confirm_timeout_secs=None,
        stale_form_secs=None,
        world_size_multiple=1,
        journal=None,
    ):
        """``base_port=0`` picks ephemeral ports (single-host jobs, where
        the master and rank 0 share the host); on a cluster pass a fixed
        base and the coordinator binds ``base_port + epoch % 64`` on rank
        0's pod.

        World formation is **two-phase**: after an epoch bump, ``ready``
        stays False until every listed member has polled the new epoch
        from its await loop — only then do members call
        ``jax.distributed.initialize``, so no one enters the formation
        barrier while a peer is still finishing the previous epoch. A
        member that doesn't confirm within ``confirm_timeout_secs`` (it
        is dead, or wedged in a stale initialize) is dropped from the
        world and the epoch re-bumps with the responsive members; the
        laggard re-joins through its next poll. Without this, one stuck
        member makes the coordination service time out the formation
        barrier and *fatally terminate* every process that did register.

        ``world_size_multiple > 1``: every formed world's process count
        is rounded DOWN to a multiple (a pipelined model's stage count
        must divide the device mesh — a 3-process world cannot hold a
        2-stage pipe axis). The overflow members stay registered as hot
        SPARES: their polls return ``{"spare": True}``, they idle
        without holding a mesh slot (requeueing any pulled tasks), and
        the next bump that reaches the multiple folds them in.
        """
        self._expected = max(1, expected_workers)
        self._world_multiple = max(1, int(world_size_multiple))
        self._base_port = base_port
        self._form_grace_secs = form_grace_secs
        from elasticdl_tpu.parallel.distributed import (
            world_init_timeout,
        )

        if confirm_timeout_secs is None:
            # derived from the workers' initialize timeout so the
            # init-timeout < fence-window invariant survives tuning:
            # raising EDL_WORLD_INIT_TIMEOUT for a real multi-host pod
            # (cold coordinator/DNS can exceed the 10s single-host
            # default — see docs/distributed.md) widens the fence window
            # with it instead of silently inverting the ordering
            confirm_timeout_secs = world_init_timeout() + 5.0
        self._confirm_timeout = confirm_timeout_secs
        if stale_form_secs is None:
            # long enough for every member to burn a full initialize
            # timeout and re-poll (same knob the workers read)
            stale_form_secs = confirm_timeout_secs + world_init_timeout()
        self._stale_form_secs = stale_form_secs
        self._lock = threading.Lock()
        self._live = {}  # worker_id -> advertised host
        # a RELAUNCHED master re-seeds this past the journaled
        # high-water mark via seed_epoch() (docs/master_recovery.md):
        # survivors compare epochs for change detection, and a counter
        # reset to 0 could collide with a worker's remembered epoch
        # and hide the re-form
        self._epoch = 0
        # membership changes append to the master journal (enqueue
        # only; the journal thread owns all IO) so the next boot knows
        # that high-water mark
        self._journal = journal
        self._world = []  # [(worker_id, host)] of the current epoch
        self._coordinator = None
        self._formed_initial = False
        self._first_register_time = None
        self._confirmed = set()  # members that polled the current epoch
        self._world_ready = False
        self._bump_time = None
        self._last_poll = {}  # worker_id -> wall time of last poll
        self._fencer = None
        self._formed = set()  # members seen training in the current epoch
        self._lobby = {}  # joiners parked while a formation is in flight
        # drained/completed members (id -> epoch of the announce): no
        # re-registration, and the exits the announce covers are exempt
        # from the dead list. Pruned with the same epoch window as
        # _dead: the announcer observes its bump within one poll and
        # its watch exit-event arrives seconds later, so entries only
        # need to outlive a couple of epochs.
        self._departing = {}
        # ids removed because their PROCESS actually died (watch/fence),
        # as opposed to graceful drains/completions: the workers'
        # wedge-escape probe only fires when one of ITS world members is
        # here — a growth bump, a drain, or a clean exit must never
        # abort a healthy (slow) step. Maps id -> epoch at death so
        # entries can be pruned once no live member's world can still
        # reference them (serialized into every get_world reply).
        self._dead = {}
        self.standby = StandbyPool()
        self._pending_bump_deadline = None  # deferred death bump

    def set_fencer(self, fencer):
        """``fencer(worker_id)`` forcibly terminates a dropped member.

        A member can wedge in a blocking collective (a SIGKILLed peer's
        sockets don't always reset) — alive as a process, gone from the
        world. Unfenced it would hold its in-flight tasks forever; the
        instance manager's kill -> watch -> recover_tasks + relaunch path
        turns the wedge into an ordinary death.
        """
        self._fencer = fencer

    @property
    def epoch(self):
        return self._epoch

    def seed_epoch(self, floor):
        """Boot-time recovery: jump the epoch counter past a previous
        incarnation's journaled high-water mark (called before the RPC
        plane serves, so no poll races it)."""
        with self._lock:
            self._epoch = max(self._epoch, int(floor))

    def _formation_in_flight(self):
        """True while the current world is still coming up: either the
        confirm phase hasn't finished, or ready specs went out but not
        every member has been seen training yet."""
        if not self._world:
            return False
        ids = set(w for w, _ in self._world)
        return not self._world_ready or not ids <= self._formed

    def _bump_locked(self):
        self._pending_bump_deadline = None
        # prune deaths no lagging member's world can still reference:
        # members trail by at most a couple of epochs (their per-step
        # poll notices a bump within one step), so a 4-epoch window is
        # comfortably conservative while keeping the get_world payload
        # bounded over a long spot-fleet job with many deaths
        self._dead = {
            w: e for w, e in self._dead.items() if e >= self._epoch - 4
        }
        self._departing = {
            w: e
            for w, e in self._departing.items()
            if e >= self._epoch - 4
        }
        # any parked joiners ride along with whatever forced this bump
        self._live.update(self._lobby)
        self._lobby = {}
        self._epoch += 1
        self._world = sorted(self._live.items())
        if self._world_multiple > 1:
            # round DOWN to the multiple; overflow members stay live as
            # hot spares (their polls see {"spare": True})
            usable = (
                len(self._world)
                // self._world_multiple
                * self._world_multiple
            )
            if usable == 0 and self._world:
                # survivors < multiple: nothing can train until
                # relaunches/joiners refill the pool — say so, loudly,
                # each time it happens (this is a stall, not a crash)
                logger.warning(
                    "world rounds down to 0 of %d live members "
                    "(world_size_multiple=%d): training is PAUSED "
                    "until the pool refills",
                    len(self._world),
                    self._world_multiple,
                )
            self._world = self._world[:usable]
        self._confirmed = set()
        self._formed = set()
        self._world_ready = not self._world  # empty world: nothing to form
        self._bump_time = time.time()
        if self._world:
            rank0_host = self._world[0][1]
            port = (
                self._base_port + self._epoch % 64
                if self._base_port
                else _free_port()
            )
            self._coordinator = "%s:%d" % (rank0_host, port)
        else:
            self._coordinator = None
        logger.info(
            "membership epoch %d: world=%s coordinator=%s",
            self._epoch,
            [w for w, _ in self._world],
            self._coordinator,
        )

    def register(self, worker_id, host="localhost"):
        # join/leave events are emitted AFTER the lock releases: the
        # sink write in EventLog.emit is disk I/O, and holding the
        # membership lock across it would stall every concurrent
        # get_comm_world/register RPC (same discipline as the
        # dispatcher's report path)
        join_event = self._register_locked(worker_id, host)
        if join_event is not None:
            from elasticdl_tpu.utils import profiling

            profiling.events.emit("worker_join", _ship=False, **join_event)
            if self._journal is not None:
                self._journal.append(
                    "member",
                    event="join",
                    worker=worker_id,
                    epoch=join_event["epoch"],
                )

    def _register_locked(self, worker_id, host):
        """The state transition; returns worker_join event fields when
        a genuinely NEW (or re-hosted) member was added, else None."""
        with self._lock:
            if worker_id in self._departing:
                # a draining member keeps polling get_comm_world while it
                # waits to observe its own departure bump; re-registering
                # it (or parking it in the lobby) would re-grow the world
                # it is leaving
                return None
            self._dead.pop(worker_id, None)  # evidently alive
            if (
                self._live.get(worker_id) == host
                or self._lobby.get(worker_id) == host
            ):
                return None
            if self._first_register_time is None:
                self._first_register_time = time.time()
            # this point is only reached for a genuinely NEW (or
            # re-hosted) member — repeats returned above
            join_event = dict(
                worker_id=worker_id, host=host, epoch=self._epoch
            )
            if not self._formed_initial:
                self._live[worker_id] = host
                if len(self._live) >= self._expected:
                    self._formed_initial = True
                    self._bump_locked()
            elif self._formation_in_flight():
                # growth coalesces: bumping now would strand members that
                # already took the ready spec in a stale initialize
                # barrier. The joiner folds in at the next bump (formation
                # completing, a death, or the staleness valve below).
                # A member re-registering under a NEW host must not stay
                # in _live under the old one while parked — that would be
                # a double membership when the bump merges the lobby
                # (unreachable today: relaunches get fresh ids; guarded
                # in case id reuse is ever introduced).
                self._live.pop(worker_id, None)
                self._lobby[worker_id] = host
            else:
                self._live[worker_id] = host
                self._bump_locked()
            # post-transition epoch, captured under the lock: the epoch
            # this member actually serves in (a bumping join increments
            # it above), and the value journal recovery max()es over
            join_event["epoch"] = self._epoch
            return join_event

    # process exit codes whose *announced* exits are protocol-clean:
    # 0 = completion after global quiescence, 75 = graceful drain
    CLEAN_EXIT_CODES = (0, 75)

    def remove(
        self,
        worker_id,
        departing=False,
        defer_bump_secs=0,
        exit_code=None,
    ):
        """Drop a member and bump. ``departing=True`` is the graceful
        leave verb (worker-initiated, BEFORE its process exits — the
        drain announcement mid-job, or the completion announcement
        after global quiescence): the id is additionally blacklisted
        from re-registration, because a draining worker keeps polling
        until it observes the bump — the poll-and-register semantics
        would otherwise re-add it.

        ``exit_code`` is the process exit the instance manager's watch
        observed (None when it could not be determined). The ``dead``
        list feeds the survivors' wedge-escape abort probe, and a
        missing entry for a peer that really broke the collective is an
        indefinite formation deadlock (wedged survivors keep polling
        via the probe, so the confirm-timeout fencer never culls them).
        So the listing rule errs toward dead — an exit is exempt ONLY
        when the worker itself announced it beforehand:

        - rc 0/75 *announced* (the worker's ``leave_comm_world`` put
          the id in ``_departing``): protocol-clean leave — not
          listed; the victim reached global quiescence or participated
          in the drain pause, nobody is wedged on it.
        - rc 0/75 *unannounced*: listed. An unannounced rc 0 is user
          code calling sys.exit(0) mid-step; an unannounced rc 75 is a
          hard-leave whose announce RPC never landed (master
          transiently unreachable). Either way survivors' in-flight
          collectives hang on the vanished rank.
        - any other returncode (or None): listed, even after an
          announcement — a drained member keeps stepping until the
          consensus pause and a segfault in that window breaks the
          collective like any crash.

        ``defer_bump_secs > 0``: the instance manager is promoting a
        pre-warmed standby for this death, so the bump waits briefly for
        the replacement's registration — one N→N formation instead of an
        N→N-1 re-form (with its throwaway step compile) immediately
        followed by an N-1→N growth pause. The member is dropped from
        ``_live`` (and listed ``dead``) NOW, so survivors' wedge-escape
        probes still fire instantly; a second death, the replacement's
        register, or the deadline ends the deferral."""
        leave_event = self._remove_locked(
            worker_id, departing, defer_bump_secs, exit_code
        )
        if leave_event is not None:
            # emitted outside the lock — see register()
            from elasticdl_tpu.utils import profiling

            profiling.events.emit(
                "worker_leave", _ship=False, **leave_event
            )
            if self._journal is not None:
                self._journal.append(
                    "member",
                    event="leave",
                    worker=worker_id,
                    epoch=leave_event["epoch"],
                )

    def _remove_locked(
        self, worker_id, departing, defer_bump_secs, exit_code
    ):
        with self._lock:
            if departing:
                self._departing[worker_id] = self._epoch
            elif not (
                exit_code in self.CLEAN_EXIT_CODES
                and worker_id in self._departing
            ):
                # only ANNOUNCED protocol-clean exits are exempt; see
                # the listing rule in the docstring
                self._dead[worker_id] = self._epoch
            self._lobby.pop(worker_id, None)
            if worker_id not in self._live:
                return None
            del self._live[worker_id]
            leave_event = dict(
                worker_id=worker_id,
                departing=departing,
                exit_code=exit_code,
                epoch=self._epoch,
            )
            if self._formed_initial:
                if (
                    defer_bump_secs > 0
                    and self._pending_bump_deadline is None
                ):
                    self._pending_bump_deadline = (
                        time.time() + defer_bump_secs
                    )
                    logger.info(
                        "death of %d: bump deferred up to %.1fs for a "
                        "standby promotion",
                        worker_id,
                        defer_bump_secs,
                    )
                    return leave_event
                # push-based: deaths re-form immediately — survivors in
                # the broken collective fail fast and re-poll, so the
                # job never waits out a detection window
                self._pending_bump_deadline = None
                self._bump_locked()
            # post-transition epoch under the lock, same as register():
            # a bumping death attributes the leave to the epoch it
            # created, and the off-lock journal append below reuses it
            leave_event["epoch"] = self._epoch
            return leave_event

    def get_world(self, worker_id, host="localhost", awaiting=True):
        """Poll-and-register in one verb (workers call this in a loop).

        ``awaiting=True`` means the caller is parked in its await loop and
        will initialize as soon as ``ready`` — such polls confirm the
        epoch. Mid-training polls (epoch-change checks at batch
        boundaries) pass False: the worker has seen the bump but still
        has to leave its current world first.
        """
        self.register(worker_id, host)
        now = time.time()
        to_fence = []
        try:
            return self._get_world_locked(
                worker_id, now, awaiting, to_fence
            )
        finally:
            # fence outside the lock: a slow kill/pod-delete API call
            # must not stall every other member's poll
            if to_fence and self._fencer is not None:
                for w in to_fence:
                    try:
                        self._fencer(w)
                    except Exception:
                        logger.warning(
                            "fencing worker %d failed", w, exc_info=True
                        )

    def _get_world_locked(self, worker_id, now, awaiting, to_fence):
        with self._lock:
            self._last_poll[worker_id] = now
            if (
                self._pending_bump_deadline is not None
                and now >= self._pending_bump_deadline
            ):
                # the promoted standby never registered in time: stop
                # holding the survivors and re-form without it (it joins
                # later as ordinary growth)
                self._bump_locked()
            if not self._formed_initial:
                grace_over = (
                    self._first_register_time is not None
                    and now - self._first_register_time
                    > self._form_grace_secs
                )
                if grace_over and self._live:
                    logger.warning(
                        "forming world with %d/%d workers after grace",
                        len(self._live),
                        self._expected,
                    )
                    self._formed_initial = True
                    self._bump_locked()
                else:
                    return {"epoch": self._epoch, "ready": False, "dead": sorted(self._dead)}
            ids = [w for w, _ in self._world]
            if worker_id not in ids:
                # parked in the lobby, removed as dead but evidently
                # alive (register above re-adds / parks it), or a hot
                # SPARE a world_size_multiple round-down left out —
                # spares idle without a mesh slot and must requeue any
                # pulled tasks (the flag tells them)
                if self._lobby and self._world_ready:
                    # staleness valve: a formation that still hasn't
                    # completed this long after ready specs went out is
                    # going to break anyway — stop holding joiners
                    if now - self._bump_time > self._stale_form_secs:
                        self._bump_locked()
                return {
                    "epoch": self._epoch,
                    "ready": False,
                    "spare": worker_id in self._live,
                    "dead": sorted(self._dead),
                }
            if self._world_ready and not awaiting:
                # an awaiting=False poll is the training loop's per-step
                # epoch check: this member established the current world
                if worker_id not in self._formed:
                    self._formed.add(worker_id)
                    if not self._formation_in_flight() and self._lobby:
                        # formation done and joiners are waiting: grow now
                        self._bump_locked()
                        return {"epoch": self._epoch, "ready": False, "dead": sorted(self._dead)}
            if not self._world_ready:
                if awaiting:
                    self._confirmed.add(worker_id)
                if set(ids) <= self._confirmed:
                    self._world_ready = True
                elif now - self._bump_time > self._confirm_timeout:
                    # drop members that went quiet (dead or wedged in a
                    # stale initialize); they re-join via their next poll
                    lagging = [
                        w
                        for w in ids
                        if w not in self._confirmed
                        and now - self._last_poll.get(w, 0) > 2.0
                    ]
                    if lagging:
                        logger.warning(
                            "world %d: dropping unresponsive members %s",
                            self._epoch,
                            lagging,
                        )
                        for w in lagging:
                            self._live.pop(w, None)
                        self._bump_locked()
                        to_fence.extend(lagging)
                        return {"epoch": self._epoch, "ready": False, "dead": sorted(self._dead)}
                    self._bump_time = now  # responsive but slow: wait on
                if not self._world_ready:
                    return {"epoch": self._epoch, "ready": False, "dead": sorted(self._dead)}
            return {
                "epoch": self._epoch,
                "ready": True,
                "coordinator": self._coordinator,
                "num_processes": len(ids),
                "process_id": ids.index(worker_id),
                "members": ids,
                "dead": sorted(self._dead),
                # size hint for the workers' speculative compile plane:
                # the head count the next growth bump would form (live
                # members + lobby joiners). The epoch itself still
                # governs membership — this is advisory only, and a
                # hinted size that never materializes costs one dropped
                # background compile (docs/compile_plane.md).
                "live": len(self._live) + len(self._lobby),
            }
