"""Process-level instance manager: elastic workers without Kubernetes.

The reference's elasticity loop is: watch instances, and when one dies
re-queue its in-flight tasks and relaunch it
(k8s_instance_manager.py:177-231). This manager implements the same loop
over local subprocesses — the single-host analog used for elastic tests
(reference rung 2, SURVEY.md §4.3) and for multi-process jobs on one TPU
host. The k8s-backed manager (k8s_instance_manager.py here) shares the
same callback contract.
"""

import subprocess
import sys
import threading

from elasticdl_tpu.common.constants import InstanceManagerStatus
from elasticdl_tpu.common.log_utils import default_logger as logger


class LocalInstanceManager:
    def __init__(
        self,
        task_d,
        num_workers,
        worker_command,
        num_ps=0,
        ps_command=None,
        restart_policy="Always",
        max_relaunches=3,
        env=None,
        membership=None,
        log_dir=None,
        num_standby=0,
        master_command=None,
    ):
        """``worker_command(worker_id) -> argv``; ``ps_command(ps_id) ->
        argv``. Worker ids grow monotonically across relaunches like the
        reference's next_worker_id counter; PS relaunches keep their id
        (reference k8s_instance_manager.py:229-231). ``membership`` is the
        allreduce-plane MembershipService: worker exits additionally
        trigger a membership epoch so survivors re-form their collective
        world."""
        self._task_d = task_d
        self._membership = membership
        if membership is not None:
            membership.set_fencer(self.kill_worker)
        self._num_workers = num_workers
        self._worker_command = worker_command
        self._num_ps = num_ps
        self._ps_command = ps_command
        # external-supervisor form (docs/master_recovery.md): when this
        # manager runs OUTSIDE the master (the chaos harness / fleet
        # tests drive it from a driver process), it also owns
        # the master process — SIGKILL relaunches on the crash budget,
        # the rc-75 drain-journal-and-exit path relaunches budget-FREE
        # (PS-plane parity). ``master_command() -> argv``.
        self._master_command = master_command
        self._restart_policy = restart_policy
        self._max_relaunches = max_relaunches
        self._env = env
        self._log_dir = log_dir  # per-instance output files (tests/debug)
        # pre-warmed spares (elastic allreduce only): each pays its cold
        # start at spawn and parks in the membership StandbyPool; a
        # death promotes one instead of relaunching cold, converting the
        # ~45-50 s relaunch cost into membership-only recovery
        self._num_standby = num_standby if membership is not None else 0
        self._standby_refill_budget = max_relaunches

        self._lock = threading.Lock()
        self._procs = {}  # instance key -> Popen
        self._stopped_procs = []  # signalled by stop, not yet reaped
        self._rekeyed = {}  # id(proc) -> current key (standby promotions)
        self.exit_codes = {}  # instance key -> last observed returncode
        self._next_worker_id = 0
        self._relaunches = 0
        # watcher threads between "my process exited" and "its
        # replacement (if any) is in the proc table": while one is in
        # there, an empty worker table does not yet mean nobody is left
        self._deciding = 0
        self._stopping = False
        self._watchers = []
        self.status = InstanceManagerStatus.PENDING

    def _spawn(self, key, argv):
        if self._log_dir:
            import os

            os.makedirs(self._log_dir, exist_ok=True)
            out = open(
                os.path.join(self._log_dir, "%s-%s.log" % key), "ab"
            )
            proc = subprocess.Popen(
                argv, env=self._env, stdout=out, stderr=out
            )
            out.close()  # the child holds its own fd
        else:
            proc = subprocess.Popen(argv, env=self._env)
        watcher = threading.Thread(
            target=self._watch, args=(key, proc), daemon=True
        )
        # _spawn runs on the owner thread AND on watcher threads (the
        # relaunch path), so the watcher list rides the same lock as
        # the proc table (edlint R8)
        with self._lock:
            self._procs[key] = proc
            self._watchers.append(watcher)
        watcher.start()
        return proc

    def start_all_ps(self):
        for ps_id in range(self._num_ps):
            self._spawn(("ps", ps_id), self._ps_command(ps_id))

    def start_master(self):
        """Spawn the supervised master process (external-supervisor
        form only; a master-resident manager never supervises itself)."""
        if self._master_command is None:
            raise ValueError(
                "no master_command configured: this manager does not "
                "supervise a master process"
            )
        self._spawn(("master", 0), self._master_command())

    def start_workers(self):
        for _ in range(self._num_workers):
            self._start_worker()
        for _ in range(self._num_standby):
            self._start_standby()
        self.status = InstanceManagerStatus.RUNNING

    def _start_standby(self):
        with self._lock:
            if self._stopping:
                return None
            token = self._next_worker_id
            self._next_worker_id += 1
        argv = list(self._worker_command(token)) + ["--standby", "true"]
        self._spawn(("standby", token), argv)
        return token

    def _promote_standby(self):
        """Assign the next worker id to a WARMED standby; returns the
        new worker id, or None (caller falls back to a cold relaunch).
        The promoted process is re-keyed so fencing/kill/terminate by
        worker id reach it, and a fresh standby refills the pool."""
        if self._membership is None:
            return None
        with self._lock:
            new_id = self._next_worker_id
            self._next_worker_id += 1
        token = self._membership.standby.activate(new_id)
        if token is None:
            return None
        with self._lock:
            proc = self._procs.pop(("standby", token), None)
            if proc is None:
                # the standby died between activate and now: unassign
                # the token explicitly (the watch thread's forget may
                # not have run yet, and an assigned token must never
                # outlive its process)
                self._membership.standby.forget(token)
                return None
            self._procs[("worker", new_id)] = proc
            self._rekeyed[id(proc)] = ("worker", new_id)
        self._start_standby()
        return new_id

    def _start_worker(self):
        with self._lock:
            worker_id = self._next_worker_id
            self._next_worker_id += 1
        self._spawn(("worker", worker_id), self._worker_command(worker_id))
        return worker_id

    # -- the elasticity loop ------------------------------------------------

    def _watch(self, key, proc):
        returncode = proc.wait()
        with self._lock:
            key = self._rekeyed.pop(id(proc), key)
            self.exit_codes[key] = returncode
            if self._procs.get(key) is not proc or self._stopping:
                return
            del self._procs[key]
            self._deciding += 1
        try:
            self._on_exit(key, returncode)
        finally:
            with self._lock:
                self._deciding -= 1

    def _on_exit(self, key, returncode):
        """The elasticity decision for one exited instance: requeue its
        work, tell the membership, relaunch or promote a replacement."""
        kind, instance_id = key
        if kind == "standby":
            # a spare died before promotion: forget its token, refill —
            # on a bounded budget of its own (a deterministically-
            # crashing spare must not fork-loop the host, nor burn the
            # real workers' relaunch budget)
            if self._membership is not None:
                self._membership.standby.forget(instance_id)
            with self._lock:
                refill = (
                    not self._stopping
                    and self._standby_refill_budget > 0
                )
                if refill:
                    self._standby_refill_budget -= 1
            if refill:
                self._start_standby()
            else:
                logger.warning(
                    "standby %d died; refill budget exhausted or "
                    "stopping — pool not refilled",
                    instance_id,
                )
            return
        if kind == "worker":
            # reference k8s_instance_manager.py:207 — a dead worker's
            # in-flight tasks go back on the todo queue
            self._task_d.recover_tasks(instance_id)
            if self._membership is not None:
                # with a warmed standby about to be promoted, defer the
                # bump briefly: one combined formation instead of a
                # shrink re-form chased by a growth pause
                with self._lock:
                    budget_left = self._relaunches < self._max_relaunches
                will_promote = (
                    returncode not in (0,)
                    and self._restart_policy != "Never"
                    and self._membership.standby.parked_count() > 0
                    # exit 75 (drain) skips the budget; crashes consume
                    # it — deferring for a promotion the budget forbids
                    # would stall survivors 6 s for nothing
                    and (returncode == 75 or budget_left)
                )
                from elasticdl_tpu.master.membership_service import (
                    DEATH_BUMP_DEFER_SECS,
                )

                self._membership.remove(
                    instance_id,
                    defer_bump_secs=(
                        DEATH_BUMP_DEFER_SECS if will_promote else 0
                    ),
                    # membership exempts rc 0/75 from the wedge-escape
                    # dead list only when the worker announced the
                    # leave itself (_departing) — an unannounced exit
                    # of any code wedges survivors like a crash
                    exit_code=returncode,
                )
            if returncode == 0:
                logger.info("Worker %d completed", instance_id)
                return
            if returncode == 75:  # EX_TEMPFAIL: graceful preemption drain
                # benign: does NOT consume the crash-relaunch budget —
                # a spot fleet drains repeatedly and each drain is fine
                if self._restart_policy != "Never":
                    new_id = self._promote_standby()
                    if new_id is None:
                        new_id = self._start_worker()
                    logger.info(
                        "Worker %d drained under a preemption notice; "
                        "relaunched replacement as id %d",
                        instance_id,
                        new_id,
                    )
                else:
                    logger.info(
                        "Worker %d drained under a preemption notice "
                        "(restart policy Never: no replacement)",
                        instance_id,
                    )
                return
            logger.warning(
                "Worker %d exited with %d; recovering tasks",
                instance_id,
                returncode,
            )
            # check-and-spend atomically: two watcher threads racing
            # here would both pass an unlocked budget check and
            # over-relaunch past max_relaunches (edlint R8)
            spend = False
            if self._restart_policy != "Never":
                with self._lock:
                    if self._relaunches < self._max_relaunches:
                        self._relaunches += 1
                        spend = True
            if spend:
                new_id = self._promote_standby()
                if new_id is not None:
                    logger.info(
                        "Promoted a warmed standby as worker %d", new_id
                    )
                else:
                    new_id = self._start_worker()
                    logger.info("Relaunched worker as id %d", new_id)
        elif kind == "master":
            if returncode == 0:
                logger.info("Master completed (job finished)")
                return
            if returncode == 75:  # EX_TEMPFAIL: drain-journal-and-exit
                # the master flushed its dispatch journal under SIGTERM
                # (master.install_drain_handler) — benign, does NOT
                # consume the crash-relaunch budget, exactly the PS
                # plane's drain contract (docs/master_recovery.md)
                relaunch = False
                with self._lock:
                    relaunch = (
                        not self._stopping
                        and self._restart_policy != "Never"
                    )
                if relaunch:
                    logger.info(
                        "Master drained (exit 75); relaunching "
                        "(budget exempt)"
                    )
                    self._spawn(key, self._master_command())
                return
            spend = False
            with self._lock:
                if (
                    not self._stopping
                    and self._restart_policy != "Never"
                    and self._relaunches < self._max_relaunches
                ):
                    self._relaunches += 1
                    spend = True
            if spend:
                logger.warning(
                    "Master exited with %d; relaunching to replay its "
                    "journal",
                    returncode,
                )
                self._spawn(key, self._master_command())
            else:
                # a log that claims a relaunch that never happens sends
                # the operator hunting a boot that doesn't exist while
                # workers burn their failover budgets against a dead port
                logger.error(
                    "Master exited with %d; relaunch budget exhausted "
                    "(or stopping/Never policy) — NOT relaunching, the "
                    "job is headless",
                    returncode,
                )
        else:
            if returncode == 75:  # EX_TEMPFAIL: graceful drain
                # the PS drained a final shard snapshot under SIGTERM
                # (ps/parameter_server.py) — benign, does NOT consume
                # the crash-relaunch budget, mirroring the worker
                # plane's preemption-drain contract
                relaunch = False
                with self._lock:
                    relaunch = (
                        not self._stopping
                        and self._restart_policy != "Never"
                    )
                if relaunch:
                    logger.info(
                        "PS %d drained (exit 75); relaunching same id",
                        instance_id,
                    )
                    self._spawn(key, self._ps_command(instance_id))
                return
            logger.warning(
                "PS %d exited with %d; relaunching same id",
                instance_id,
                returncode,
            )
            spend = False
            with self._lock:
                if (
                    not self._stopping
                    and self._relaunches < self._max_relaunches
                ):
                    self._relaunches += 1
                    spend = True
            if spend:
                self._spawn(key, self._ps_command(instance_id))

    # -- control ------------------------------------------------------------

    def kill_worker(self, worker_id):
        """Fault injection / fencing: kill one live worker process.

        SIGABRT first (with PYTHONFAULTHANDLER=1 the dying process dumps
        every thread's stack to its log — the whole point of fencing a
        wedged member is learning WHERE it wedged), SIGKILL shortly
        after in case abort is blocked too."""
        import signal
        import threading

        with self._lock:
            proc = self._procs.get(("worker", worker_id))
        if proc:
            try:
                proc.send_signal(signal.SIGABRT)
            except OSError:
                pass

            def _finish(p=proc):
                try:
                    p.wait(timeout=2)
                except Exception:
                    p.kill()

            threading.Thread(target=_finish, daemon=True).start()

    def terminate_worker(self, worker_id):
        """Deliver a preemption notice (SIGTERM): the elastic worker
        drains gracefully — checkpoint, clean world leave, exit 75 —
        and the watch loop relaunches a replacement."""
        with self._lock:
            proc = self._procs.get(("worker", worker_id))
        if proc:
            proc.terminate()

    def kill_ps(self, ps_id):
        """Chaos/fault injection: SIGKILL one live PS process.

        The hard-crash path — no drain snapshot runs, so the relaunch
        restores the last CADENCE snapshot (or boots empty with
        durability off). The watch loop relaunches the same id on the
        crash budget, exactly like a k8s pod death
        (tools/chaos.py drives this for the scripted fleet faults)."""
        import signal

        with self._lock:
            proc = self._procs.get(("ps", ps_id))
        if proc:
            try:
                proc.send_signal(signal.SIGKILL)
            except OSError:
                pass

    def terminate_ps(self, ps_id):
        """Graceful PS preemption (SIGTERM): the shard drains a final
        snapshot and exits 75; the watch loop relaunches without
        spending the crash budget."""
        with self._lock:
            proc = self._procs.get(("ps", ps_id))
        if proc:
            proc.terminate()

    def kill_master(self):
        """Chaos/fault injection: SIGKILL the supervised master.

        The hard-crash path — no journal drain runs, so the relaunch
        replays whatever the batched-fsync cadence made durable (the
        bounded-loss contract, docs/master_recovery.md). The watch loop
        relaunches on the crash budget (tools/chaos.py drives this for
        scripted master outages)."""
        import signal

        with self._lock:
            proc = self._procs.get(("master", 0))
        if proc:
            try:
                proc.send_signal(signal.SIGKILL)
            except OSError:
                pass

    def terminate_master(self):
        """Graceful master preemption (SIGTERM): the master drains its
        dispatch journal and exits 75; the watch loop relaunches
        without spending the crash budget."""
        with self._lock:
            proc = self._procs.get(("master", 0))
        if proc:
            proc.terminate()

    def live_master(self):
        with self._lock:
            proc = self._procs.get(("master", 0))
        return proc is not None and proc.poll() is None

    def live_ps(self):
        with self._lock:
            return [
                k[1]
                for k, p in self._procs.items()
                if k[0] == "ps" and p.poll() is None
            ]

    def live_workers(self):
        with self._lock:
            return [
                k[1]
                for k, p in self._procs.items()
                if k[0] == "worker" and p.poll() is None
            ]

    def workers_exhausted(self):
        """True once workers were started and none is left or coming:
        no worker or standby process in the table and no watcher still
        deciding on a replacement (clean exits, a spent relaunch budget
        and the Never policy all end here). The master fails a job that
        still has tasks outstanding at that point, because nobody will
        ever take them."""
        with self._lock:
            return (
                self.status == InstanceManagerStatus.RUNNING
                and not self._stopping
                and self._deciding == 0
                and not any(
                    k[0] in ("worker", "standby") for k in self._procs
                )
            )

    def wait(self, timeout=None):
        """Block until every instance process has exited."""
        with self._lock:
            procs = list(self._procs.values())
        for proc in procs:
            try:
                proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                return False
        return True

    def stop_relaunch_and_remove_all_pods(self):
        self._stopping = True
        self.status = InstanceManagerStatus.FINISHED
        with self._lock:
            procs = list(self._procs.values())
            self._procs.clear()
            self._stopped_procs.extend(procs)
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()

    def wait_stopped(self, grace_secs):
        """After :meth:`stop_relaunch_and_remove_all_pods`: block until
        the processes it signalled have exited, SIGKILLing whatever is
        still alive after ``grace_secs``. A worker holds its accelerator
        until it exits, and an elastic worker answers SIGTERM by
        finishing what it is doing (landing a checkpoint, leaving the
        world), so a caller that is about to hand the machine to someone
        else waits here first."""
        import time

        with self._lock:
            procs, self._stopped_procs = self._stopped_procs, []
        deadline = time.monotonic() + grace_secs
        for proc in procs:
            try:
                proc.wait(timeout=max(0.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                logger.warning(
                    "instance pid %d still alive %.0fs after SIGTERM; "
                    "killing it",
                    proc.pid,
                    grace_secs,
                )
                proc.kill()
                proc.wait()
