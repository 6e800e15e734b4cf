"""One training job through the program's front door, ``edl train``.

The harness process stays off JAX and, while the worker trains, does
nothing but sleep and look at the events file once a second: the
master's RPC server (a thread of the ``edl train`` process) answers the
worker at every sync point, and a busy neighbour lengthens exactly that
gap.
"""

import glob
import json
import os
import signal
import subprocess
import sys
import time

import events as ev
from spec import HERE, ROOT

POLL_SECONDS = 1.0
# a job that has not produced the window by then never will (a first
# run, which compiles, needs about 200 s before its windows start)
JOB_LIMIT_SECONDS = 600
TEARDOWN_LIMIT_SECONDS = 150
MEMORY_REPLY_SECONDS = 10


class JobFailure(Exception):
    """The job could not be measured (no chip, died, hung)."""


# process groups this harness started and has not seen end: what the
# wall-clock limit has to kill
LIVE_GROUPS = set()


def kill_live_groups():
    for pgid in list(LIVE_GROUPS):
        try:
            os.killpg(pgid, signal.SIGKILL)
        except ProcessLookupError:
            pass


def run_child(command, env, timeout):
    """Run ``command`` in a process group of its own; returns (exit
    code, stdout). Killed, group and all, at ``timeout``."""
    proc = subprocess.Popen(
        command,
        cwd=ROOT,
        env=env,
        stdout=subprocess.PIPE,
        text=True,
        start_new_session=True,
    )
    LIVE_GROUPS.add(proc.pid)
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.communicate()
        raise JobFailure(
            "%s did not finish within %ds" % (command[1], timeout)
        ) from None
    finally:
        LIVE_GROUPS.discard(proc.pid)
    return proc.returncode, out


def write_records(data_dir, traffic, seed):
    """One epoch of token records from the seed: ``tasks_per_epoch``
    tasks' worth, ids drawn from a skewed (1/rank) unigram over
    ``token_ids`` ids so that a model which really applies its updates
    gets the loss down. The job loops over them by epochs."""
    import numpy as np

    from elasticdl_tpu.data.example import encode_example
    from elasticdl_tpu.data.recordio import create_recordio

    if traffic["unigram"] != "zipf-1":
        raise ValueError("unknown unigram %r" % traffic["unigram"])
    n = (
        traffic["tasks_per_epoch"]
        * traffic["minibatches_per_task"]
        * traffic["minibatch_size"]
    )
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, traffic["token_ids"] + 1)
    p /= p.sum()
    tokens = rng.choice(
        traffic["token_ids"], size=(n, traffic["seq_len"]), p=p
    ).astype(np.int64)
    os.makedirs(data_dir)
    with create_recordio(os.path.join(data_dir, "tokens.edlr")) as w:
        for row in tokens:
            w.write(encode_example({"tokens": row}))
    return n


def ensure_native_reader():
    """Build the C++ record reader from the committed sources unless
    the checkout holds it already (git does not carry the binary)."""
    from elasticdl_tpu.native import build

    if not os.path.exists(
        os.path.join(os.path.dirname(build.__file__), "libedl_native.so")
    ):
        build.build(verbose=False)


def cache_dir():
    """Where compiled programs are kept: jax's own variable if set,
    else one fixed path inside the checkout (the program's default)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache"
    )


def cache_files():
    """Compiled programs in the cache: path -> mtime."""
    out = {}
    for path in glob.glob(os.path.join(cache_dir(), "*")):
        if not path.endswith("-atime"):
            try:
                out[path] = os.stat(path).st_mtime
            except FileNotFoundError:
                pass
    return out


def child_env(platform_env, run_dir, trace_dir=None):
    env = dict(os.environ)
    env.update(platform_env)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(HERE, "worker_hooks"), ROOT]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir()
    env["EDL_BENCH_MEMORY_STATS_PATH"] = os.path.join(run_dir, "memory.json")
    if trace_dir:
        env["EDL_PROFILE_DIR"] = trace_dir
    return env


def train_command(config, traffic, data_dir, events_path):
    """The command a user types. Every flag not listed is at the
    program's default, ``--telemetry_report_secs`` among them."""
    model_params = ",".join(
        "%s=%s" % kv for kv in config["model_params"].items()
    )
    return [
        sys.executable, "-m", "elasticdl_tpu.cli", "train",
        "--job_name", "benchmark",
        "--distribution_strategy", "AllreduceStrategy",
        "--num_workers", "1",
        "--model_zoo", os.path.join(ROOT, "model_zoo"),
        "--model_def", config["model_def"],
        "--model_params", model_params,
        "--training_data", data_dir,
        "--minibatch_size", str(traffic["minibatch_size"]),
        "--num_minibatches_per_task", str(traffic["minibatches_per_task"]),
        # never runs dry: epochs are created lazily, one at a time
        "--num_epochs", "1000000",
        "--telemetry_events_path", events_path,
    ]  # fmt: skip


def _cmdline(pid):
    try:
        with open("/proc/%d/cmdline" % pid, "rb") as f:
            return f.read().replace(b"\0", b" ").decode(errors="replace")
    except OSError:
        return ""


class Job:
    """``edl train`` as a process group of its own."""

    def __init__(self, command, env, run_dir):
        self.run_dir = run_dir
        self.events_path = command[command.index("--telemetry_events_path") + 1]
        self.log_path = os.path.join(run_dir, "job.log")
        self._log = open(self.log_path, "wb")
        self.started_at = time.time()
        self.proc = subprocess.Popen(
            command,
            cwd=ROOT,
            env=env,
            stdout=self._log,
            stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        LIVE_GROUPS.add(self.proc.pid)

    def wait_for_window(self, seconds):
        """Sleep until the events hold a measured window of ``seconds``
        (see events.measured_windows); returns all events read."""
        deadline = self.started_at + JOB_LIMIT_SECONDS
        seen_size = -1
        while True:
            if self.proc.poll() is not None:
                raise JobFailure(
                    "edl train exited with code %r before the window was "
                    "measured; see %s" % (self.proc.returncode, self.log_path)
                )
            if time.time() > deadline:
                raise JobFailure(
                    "no measured window of %ss within %ds"
                    % (seconds, JOB_LIMIT_SECONDS)
                )
            time.sleep(POLL_SECONDS)
            try:
                size = os.stat(self.events_path).st_size
            except FileNotFoundError:
                continue
            if size == seen_size:
                continue
            seen_size = size
            events = ev.read_events(self.events_path)
            if ev.measured_windows(events, seconds) is not None:
                return events

    def ask_memory_stats(self):
        """Per-device ``memory_stats()`` of the job's worker, the
        process that holds the chip, or None if it did not answer. The
        worker is found by the marker its hook left
        (benchmark/worker_hooks): only a process that has the handler
        is ever signalled."""
        path = os.path.join(self.run_dir, "memory.json")
        armed = [
            int(marker.rsplit(".", 1)[1])
            for marker in glob.glob(path + ".armed.*")
        ]
        workers = [
            pid for pid in armed
            if "elasticdl_tpu.worker.main" in _cmdline(pid)
        ]  # fmt: skip
        if len(workers) != 1:
            return None
        os.kill(workers[0], signal.SIGUSR1)
        deadline = time.time() + MEMORY_REPLY_SECONDS
        while time.time() < deadline:
            if os.path.exists(path):
                with open(path) as f:
                    return json.load(f)
            time.sleep(0.1)
        return None

    def stop(self):
        """Ctrl-C, as a terminal delivers it: SIGINT to the job's whole
        process group. ``Master.run`` catches it and stops the master;
        the worker unwinds through ``run()``'s ``finally`` (closing
        telemetry ship, trace flush) and exits; ``edl train`` returns
        once the worker has gone. (SIGINT to ``edl train`` alone makes
        the master SIGTERM its worker, which then drains against a
        master that no longer answers until the 120 s grace kills it.)
        Returns the seconds that took; raises if anything of the job
        stays alive."""
        t0 = time.time()
        pgid = self.proc.pid  # start_new_session made it the group's id
        if self.proc.poll() is None:
            os.killpg(pgid, signal.SIGINT)
        try:
            self.proc.wait(timeout=TEARDOWN_LIMIT_SECONDS)
        except subprocess.TimeoutExpired:
            self.kill()
            raise JobFailure(
                "edl train still alive %ds after SIGINT"
                % TEARDOWN_LIMIT_SECONDS
            ) from None
        finally:
            self._log.close()
        # an orphan that has exited is a zombie until init reaps it
        deadline = time.time() + 5
        while self._group_alive(pgid) and time.time() < deadline:
            time.sleep(0.1)
        if self._group_alive(pgid):
            self.kill()
            raise JobFailure("edl train returned with a child still alive")
        LIVE_GROUPS.discard(pgid)
        return time.time() - t0

    @staticmethod
    def _group_alive(pgid):
        try:
            os.killpg(pgid, 0)
        except ProcessLookupError:
            return False
        return True

    def kill(self):
        """Last resort, on a failed run: nothing of the job survives."""
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        try:
            self.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass
        if not self._log.closed:
            self._log.close()
        LIVE_GROUPS.discard(self.proc.pid)
