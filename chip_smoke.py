#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py                 # the chip check
    python3 chip_smoke.py --rehearse-on-cpu   # same path, toy width, CPU

Leg 1 runs the system's main path once, through the entry point a user
calls: ``edl train --distribution_strategy AllreduceStrategy
--num_workers 1`` (``elasticdl_tpu.api.train``) in local mode. This
process is the master parent: in-process master, task dispatch, and it
never initialises a JAX backend (asserted at exit). The one
``elasticdl_tpu.worker.main`` child owns the chip (or all chips of the
host: one process, dp over every local device) and trains the zoo's
``transformer_lm`` at the published 110M width — minibatch 16, records of
1024 tokens generated here from a seed — for 16 optimizer steps over 8
dispatched tasks, ending in a sharded checkpoint.

Leg 2, in its own child once the worker has exited and released the
chip, runs the flash kernel forward and backward against
``reference_attention`` at the job's per-chip shape.

The evidence comes from the processes that held the chip, over channels
the job already has: the events the worker ships to the master's
``--telemetry_events_path`` file and the final checkpoint's manifest.
Stdout ends in two JSON lines: the report (what ran, on what, with
which kernels, reader and cache, the losses and the timings), then, as
the last line, the verdict and nothing else:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
the device as the worker's JAX reported it. The exit code is 0 only if
every check held; on a machine with no chip nothing is printed to
stdout and the code is not 0 (the chip check asks JAX for the TPU by
name, so start-up fails instead of landing on the CPU).

``--rehearse-on-cpu`` drives the same code at a toy width on two virtual
CPU devices with the kernels interpreted, so chip time goes to the
chip's problems only.
"""

import argparse
import glob
import json
import math
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

STEPS = 16
MINIBATCHES_PER_TASK = 2  # 8 tasks
SEED = 20260926
# this script's own limit, inside the driver's 1200 s
WALL_LIMIT_S = 1080

CHIP = {
    # the 110M configuration README publishes, at its full width
    "model_params": (
        "vocab_size=32768,num_layers=12,num_heads=12,head_dim=64,"
        "embed_dim=768,mlp_dim=3072,dtype=bfloat16"
    ),
    "seq_len": 1024,
    "minibatch": 16,
    "heads": 12,
    # the TPU by name: with the platform left open a TPU that fails to
    # start leaves JAX on the CPU without a word
    "env": {"JAX_PLATFORMS": "tpu"},
    "platform": "tpu",
    "attention": "pallas",
}
REHEARSAL = {
    "model_params": (
        "vocab_size=256,num_layers=2,num_heads=2,head_dim=64,"
        "embed_dim=128,mlp_dim=256,dtype=bfloat16"
    ),
    "seq_len": 1024,  # the shortest length the model hands the kernel
    "minibatch": 4,
    "heads": 2,
    "env": {
        "JAX_PLATFORMS": "cpu",
        "EDL_DIST_PLATFORM": "cpu",
        "EDL_LOCAL_DEVICES": "2",
        # a caller's virtual-device count (the test suite's 8) must not
        # outvote EDL_LOCAL_DEVICES
        "XLA_FLAGS": "",
    },
    "platform": "cpu",
    "attention": "pallas-interpret",
}


class SmokeFailure(Exception):
    pass


def _check(ok, message):
    if not ok:
        raise SmokeFailure(message)


def _children():
    """Pids whose parent is this process (the worker, the kernel leg)."""
    me = str(os.getpid())
    out = []
    for stat in glob.glob("/proc/[0-9]*/stat"):
        try:
            with open(stat) as f:
                # pid (comm) state ppid ...; comm may hold spaces
                fields = f.read().rsplit(")", 1)[1].split()
        except (OSError, IndexError):
            continue
        if fields[1] == me:
            out.append(int(stat.split("/")[2]))
    return out


def _kill_children():
    for pid in _children():
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass


def _on_wall_limit(signum, frame):
    print(
        "chip_smoke: FAILED: wall-clock limit of %ds reached" % WALL_LIMIT_S,
        file=sys.stderr,
        flush=True,
    )
    _kill_children()
    os._exit(124)


def _write_records(data_dir, cfg):
    """STEPS * minibatch records of seq_len tokens, from the seed. The
    tokens follow a skewed unigram over 64 ids, so a model that really
    applies its updates gets the loss down within the run."""
    import numpy as np

    from elasticdl_tpu.data.example import encode_example
    from elasticdl_tpu.data.recordio import create_recordio

    rng = np.random.default_rng(SEED)
    p = 1.0 / np.arange(1, 65)
    p /= p.sum()
    with create_recordio(os.path.join(data_dir, "tokens.edlr")) as w:
        for _ in range(STEPS * cfg["minibatch"]):
            tokens = rng.choice(64, size=cfg["seq_len"], p=p)
            w.write(encode_example({"tokens": tokens.astype(np.int64)}))


def _build_native_reader():
    """Build the C++ record reader from the committed sources, so the
    run never depends on a binary that merely happens to lie in the
    tree; without a compiler the Python reader serves, by name."""
    from elasticdl_tpu.native import build

    try:
        build.build(verbose=False)
    except (OSError, subprocess.CalledProcessError) as e:
        print(
            "chip_smoke: native reader not built (%s); running on the "
            "Python reader" % e,
            file=sys.stderr,
        )
        os.environ["EDL_DISABLE_NATIVE"] = "1"


def _events(path):
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def _train_leg(cfg, scratch):
    data_dir = os.path.join(scratch, "data")
    ckpt_dir = os.path.join(scratch, "ckpt")
    events_path = os.path.join(scratch, "events.jsonl")
    os.makedirs(data_dir)
    _write_records(data_dir, cfg)

    from elasticdl_tpu import api

    t0 = time.time()
    rc = api.train(
        [
            "--job_name", "chip-smoke",
            "--distribution_strategy", "AllreduceStrategy",
            "--num_workers", "1",
            "--model_zoo", os.path.join(REPO, "model_zoo"),
            "--model_def", "transformer_lm.transformer_lm.custom_model",
            "--model_params", cfg["model_params"],
            "--training_data", data_dir,
            "--minibatch_size", str(cfg["minibatch"]),
            "--num_minibatches_per_task", str(MINIBATCHES_PER_TASK),
            "--num_epochs", "1",
            "--checkpoint_dir", ckpt_dir,
            "--checkpoint_steps", str(STEPS),
            "--telemetry_events_path", events_path,
            "--telemetry_report_secs", "0.05",
        ]
    )  # fmt: skip
    job_seconds = time.time() - t0
    _check(not _children(), "edl train returned with a child still alive")
    # never trust rc alone: everything below comes from the worker
    _check(rc == 0, "edl train exited with code %r" % rc)

    events = _events(events_path)
    built = [e for e in events if e["kind"] == "step_built"]
    _check(len(built) == 1, "expected one step_built event, got %d" % len(built))
    built = built[0]
    _check(
        built["platform"] == cfg["platform"],
        "worker trained on platform %r, not %r"
        % (built["platform"], cfg["platform"]),
    )
    _check(
        built["attention"] == cfg["attention"],
        "the step was built with %r attention, not %r"
        % (built["attention"], cfg["attention"]),
    )
    if cfg["platform"] == "tpu":
        # the lowered step itself holds the Mosaic kernels
        _check(
            built["tpu_custom_calls"] > 0
            and built["pallas_interpreted"] == 0,
            "no TPU custom call in the lowered step: %r" % built,
        )

    dispatched = [e for e in events if e["kind"] == "task_done"]
    want_tasks = STEPS // MINIBATCHES_PER_TASK
    _check(
        len(dispatched) == want_tasks,
        "%d tasks completed, expected %d" % (len(dispatched), want_tasks),
    )

    windows = [e for e in events if e["kind"] == "train_window"]
    steps = sum(e["steps"] for e in windows)
    _check(
        steps == STEPS,
        "worker reported %d steps for %d records of minibatch %d"
        % (steps, STEPS * cfg["minibatch"], cfg["minibatch"]),
    )
    _check(
        all(e["nonfinite"] == 0 for e in windows)
        and all(
            math.isfinite(e["first_loss"]) and math.isfinite(e["last_loss"])
            for e in windows
        ),
        "non-finite loss: %r" % windows,
    )
    first_loss, last_loss = windows[0]["first_loss"], windows[-1]["last_loss"]
    _check(
        last_loss < first_loss,
        "loss did not go down: %.4f -> %.4f" % (first_loss, last_loss),
    )
    _check(
        all(e["state_on_devices"] == built["device_count"] for e in windows),
        "a train-state leaf is missing from some device of the mesh: %r"
        % [(e["state_on_devices"], built["device_count"]) for e in windows],
    )

    manifests = glob.glob(os.path.join(ckpt_dir, "ckpt_v*", "manifest-*.json"))
    _check(len(manifests) == 1, "expected one checkpoint manifest: %r" % manifests)
    with open(manifests[0]) as f:
        manifest = json.load(f)
    _check(
        manifest["version"] == STEPS and manifest["leaves"],
        "final checkpoint is v%r with %d leaves, expected v%d"
        % (manifest["version"], len(manifest["leaves"]), STEPS),
    )

    establish = [e for e in events if e["kind"] == "resize_end"]
    _check(len(establish) == 1, "expected one establish, got %d" % len(establish))
    establish = establish[0]
    return {
        "platform": built["platform"],
        "device_kind": built["device_kind"],
        "device_count": built["device_count"],
        "mesh": built["mesh"],
        "steps": steps,
        "tasks": len(dispatched),
        "first_loss": first_loss,
        "last_loss": last_loss,
        "attention": built["attention"],
        "tpu_custom_calls": built["tpu_custom_calls"],
        "mosaic_kernels": built["mosaic_kernels"],
        "record_reader": built["record_reader"],
        "compile_cache_dir": built["compile_cache_dir"],
        "checkpoint_version": manifest["version"],
        "establish_seconds": round(
            sum(establish[k] for k in ("world_s", "init_s", "place_s", "compile_s")),
            3,
        ),
        # the first window holds the step's trace and compile
        "first_window_seconds": windows[0]["seconds"],
        "last_window_seconds": windows[-1]["seconds"],
        "job_seconds": round(job_seconds, 3),
    }


_KERNEL_LEG = r"""
import json, sys
import numpy as np
import jax, jax.numpy as jnp
from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.parallel.ring_attention import reference_attention

b, l, h, d, want_interpret = json.loads(sys.argv[1])
interpret = fa.kernel_interpret_mode()
assert interpret is want_interpret, "interpret=%r" % interpret
keys = jax.random.split(jax.random.PRNGKey(0), 4)
q, k, v, g = (
    jax.random.normal(key, (b, l, h, d), jnp.float32).astype(jnp.bfloat16)
    for key in keys
)

def fwd_bwd(attention):
    def run(q, k, v, g):
        out, vjp = jax.vjp(attention, q, k, v)
        return (out,) + vjp(g)
    return jax.jit(run)

flash = fwd_bwd(lambda q, k, v: fa.flash_attention(q, k, v, True))
if not interpret:
    text = flash.lower(q, k, v, g).as_text()
    for name in (fa.FWD_KERNEL, fa.BWD_DQ_KERNEL, fa.BWD_DKV_KERNEL):
        assert name in text and "tpu_custom_call" in text, name
got = flash(q, k, v, g)
want = fwd_bwd(lambda q, k, v: reference_attention(q, k, v, causal=True))(q, k, v, g)
errors = {}
for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
    a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
    assert a.shape == r.shape and np.isfinite(a).all(), name
    # both sides round their result to bfloat16 (8 significant bits):
    # two units in the last place at the top of the reference's range
    tolerance = 2 * 2.0 ** -7 * float(np.abs(r).max())
    errors[name] = [float(np.abs(a - r).max()), tolerance]
    assert errors[name][0] <= tolerance, (name, errors[name])
device = jax.devices()[0]
print(json.dumps({
    "interpret": interpret,
    "shape": [b, l, h, d],
    "max_abs_error_and_tolerance": errors,
    "platform": device.platform,
    "device_kind": device.device_kind,
}))
"""


def _kernel_leg(cfg, per_chip_batch):
    """Flash forward + backward against reference_attention, in a child
    of its own: the worker has exited, so the chip is free again."""
    shape = [per_chip_batch, cfg["seq_len"], cfg["heads"], 64]
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            _KERNEL_LEG,
            json.dumps(shape + [cfg["platform"] != "tpu"]),
        ],
        env=env,
        stdout=subprocess.PIPE,
        text=True,
        timeout=600,
    )
    _check(proc.returncode == 0, "kernel leg exited with %d" % proc.returncode)
    leg = json.loads(proc.stdout.strip().splitlines()[-1])
    _check(
        leg["platform"] == cfg["platform"],
        "kernel leg ran on %r" % leg["platform"],
    )
    return leg


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--rehearse-on-cpu",
        action="store_true",
        help="toy width on two virtual CPU devices, kernels interpreted",
    )
    args = parser.parse_args(argv)
    cfg = REHEARSAL if args.rehearse_on_cpu else CHIP

    signal.signal(signal.SIGALRM, _on_wall_limit)
    signal.alarm(WALL_LIMIT_S)
    t0 = time.time()
    sys.path.insert(0, REPO)
    # children (the worker, the kernel leg) inherit the environment
    os.environ.update(cfg["env"])
    try:
        _build_native_reader()
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as scratch:
            result = _train_leg(cfg, scratch)
        result["kernel_leg"] = _kernel_leg(
            cfg, cfg["minibatch"] // result["device_count"]
        )
        _check(
            result["kernel_leg"]["device_kind"] == result["device_kind"],
            "the two legs ran on different devices",
        )
        # this process was the master parent throughout: it must have
        # left the chip to its children
        from jax._src import xla_bridge

        _check(
            not xla_bridge.backends_are_initialized(),
            "the smoke parent initialised a JAX backend",
        )
    except SmokeFailure as e:
        print("chip_smoke: FAILED: %s" % e, file=sys.stderr, flush=True)
        return 1
    finally:
        signal.alarm(0)
        _kill_children()
    result["rehearsal"] = bool(args.rehearse_on_cpu)
    result["wall_seconds"] = round(time.time() - t0, 3)
    print(json.dumps(result))
    # the verdict, alone on the last line: these keys and no others
    verdict = {
        "ok": True,
        "device": {
            "platform": result["platform"],
            "kind": result["device_kind"],
            "count": result["device_count"],
        },
    }
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
