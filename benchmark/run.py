#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration trains through ``edl train`` (a process of its
own: in-process master, one ``worker.main`` child that owns the chip or
all four) on records made from the seed. This process never initialises
a JAX backend. The first two sync windows the worker reports are
warm-up; the measured window is the whole ``train_window`` events after
them until their seconds reach ``--seconds``. Then the job is stopped
with the Ctrl-C ``Master.run`` catches, and, once the chip is free, a
comparison child checks the configuration against the plain reference.

stdout ends in two JSON lines: a report (per-window seconds, teardown,
the comparison's errors, every check), then the contract's object with
its keys and no others. With ``--trace 1`` the worker runs under
``EDL_PROFILE_DIR`` and the per-layer metrics and ``breakdown`` are
reported; end-to-end numbers of a traced run are not.

Without a TPU, or in a directory that holds the benchmark and not the
program, it prints no result and exits non-zero.
"""

import time

T0 = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import events as ev  # noqa: E402
import job as jobs  # noqa: E402
import spec  # noqa: E402

# the TPU by name: with the platform left open, a TPU that fails to
# start leaves JAX on the CPU without a word
CHIP_ENV = {"JAX_PLATFORMS": "tpu"}
CHIP_PLATFORM = "tpu"
WALL_LIMIT_SECONDS = 1150  # inside the 1200 s a compiling run may take
COMPARE_LIMIT_SECONDS = 400
TRACE_STEPS = 16
# a traced run measures this much and no more: the profiler's Python
# tracer writes millions of events a minute (227 MB for a 20 s window on
# one chip, 41 s to flush at exit), what is reduced is the last
# TRACE_STEPS steps, and its end-to-end numbers are not used
TRACE_WINDOW_SECONDS = 8.0


class RunFailure(Exception):
    """No result can be reported."""


def _say(message):
    print("benchmark: " + message, file=sys.stderr, flush=True)


def _run_dir(workload):
    """A fixed directory inside the checkout, emptied at every run."""
    path = os.path.join(spec.ROOT, ".bench_runs", workload)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def _compare(loaded, seed, env):
    """The comparison child's verdict (benchmark/compare.py), and the
    seconds the child took: every run of every later check pays them."""
    config_path = os.path.join(loaded["run_dir"], "config.json")
    with open(config_path, "w") as f:
        json.dump(loaded["config"], f)
    t0 = time.time()
    rc, out = jobs.run_child(
        [
            sys.executable,
            os.path.join(HERE, "compare.py"),
            "--config", config_path,
            "--seq-len", str(loaded["traffic"]["seq_len"]),
            "--seed", str(seed),
        ],  # fmt: skip
        env,
        COMPARE_LIMIT_SECONDS,
    )
    if rc != 0:
        raise RunFailure("the comparison child exited with %d" % rc)
    return json.loads(out.strip().splitlines()[-1]), time.time() - t0


def _checks(loaded, events, windows, warm_end, cache_after_window):
    """Every way a run can be wrong though it ran: name -> bool."""
    cell, traffic = loaded["cell"], loaded["traffic"]
    built = ev.of_kind(events, "step_built")[0]
    layers = loaded["config"]["model_params"]["num_layers"]
    flash = traffic["expect_attention"] != "xla"
    window_end = ev.emitted_at(windows[-1])
    return {
        "one_establish": len(ev.of_kind(events, "resize_end")) == 1,
        "no_compile_in_window": not [
            path
            for path, mtime in cache_after_window.items()
            if warm_end < mtime <= window_end
        ],
        "losses_finite": all(
            w["nonfinite"] == 0
            and math.isfinite(w["first_loss"])
            and math.isfinite(w["last_loss"])
            for w in ev.of_kind(events, "train_window")
        ),
        "state_on_every_chip": all(
            w["state_on_devices"] == cell["chips"] for w in windows
        ),
        "mesh_is_data_parallel": built["mesh"] == "data=%d" % cell["chips"],
        "attention_as_named": built["attention"] == traffic["expect_attention"]
        and built["tpu_custom_calls"] == (3 * layers if flash else 0)
        and built["pallas_interpreted"] == 0,
        "no_task_failed": not ev.of_kind(events, "task_requeued"),
    }


def run_cell(
    loaded,
    seed,
    seconds,
    trace,
    platform_env=CHIP_ENV,
    platform=CHIP_PLATFORM,
):
    """Run the cell once. Returns (report, result): the diagnostic line
    and the contract's line. Raises RunFailure / jobs.JobFailure where
    there is nothing to report."""
    cell, traffic, config = loaded["cell"], loaded["traffic"], loaded["config"]
    if trace:
        seconds = min(seconds, TRACE_WINDOW_SECONDS)
    run_dir = loaded["run_dir"] = _run_dir(cell["name"])
    trace_dir = os.path.join(run_dir, "trace") if trace else None
    env = jobs.child_env(platform_env, run_dir, trace_dir)

    records = jobs.write_records(os.path.join(run_dir, "data"), traffic, seed)
    jobs.ensure_native_reader()
    cache_before = jobs.cache_files()
    job = jobs.Job(
        jobs.train_command(
            config,
            traffic,
            os.path.join(run_dir, "data"),
            os.path.join(run_dir, "events.jsonl"),
        ),
        env,
        run_dir,
    )
    bench_prep_s = job.started_at - T0
    try:
        events = job.wait_for_window(seconds)
        cache_after_window = jobs.cache_files()
        memory = job.ask_memory_stats()
        teardown_s = job.stop()
    except BaseException:
        job.kill()
        raise
    # what the master wrote while it stopped (late task reports)
    events = ev.read_events(job.events_path)

    built = ev.of_kind(events, "step_built")
    if len(built) != 1:
        raise RunFailure("expected one step_built event, got %d" % len(built))
    built = built[0]
    if built["platform"] != platform:
        raise RunFailure(
            "the worker trained on platform %r, not %r"
            % (built["platform"], platform)
        )
    if built["device_count"] != cell["chips"]:
        raise RunFailure(
            "the worker drove %d device(s); the cell asks for %d"
            % (built["device_count"], cell["chips"])
        )
    if platform == CHIP_PLATFORM and not memory:
        # the contract's line has to carry device.memory_peak_bytes
        raise RunFailure("the worker did not report its device memory")

    windows = ev.measured_windows(events, seconds)
    warm_end = ev.warmup_end(events)
    window_s = sum(w["seconds"] for w in windows)
    steps = sum(w["steps"] for w in windows)
    tokens_per_step = traffic["minibatch_size"] * traffic["seq_len"]
    # a total over every measured window, not a median of windows: a
    # stall is something the job's user pays for, so it stays in
    rate = steps * tokens_per_step / window_s / cell["chips"]
    setup_s = warm_end - T0
    window_end = ev.emitted_at(windows[-1])
    reports = [
        e
        for kind in ("task_done", "task_requeued")
        for e in ev.of_kind(events, kind)
        if warm_end <= e["ts"] <= window_end
    ]

    checks = _checks(loaded, events, windows, warm_end, cache_after_window)
    comparison, compare_s = _compare(loaded, seed, env)
    checks["agrees_with_reference"] = bool(
        comparison["agree"] and comparison["platform"] == platform
    )
    if comparison["device_kind"] != built["device_kind"]:
        raise RunFailure("job and comparison ran on different devices")

    device = {
        "platform": built["platform"],
        "kind": built["device_kind"],
        "count": built["device_count"],
        "memory_peak_bytes": max(
            (d["stats"].get("peak_bytes_in_use", 0) for d in memory or []),
            default=0,
        ),
    }
    run = {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "events": events,
        "windows": windows,
        "window_start": warm_end,
        "device_kind": built["device_kind"],
        "tokens_per_s_per_chip": rate,
        "setup_s": setup_s,
        "bench_prep_s": bench_prep_s,
        "cache_files_added": len(set(cache_after_window) - set(cache_before)),
        "trace": None,
    }
    breakdown = None
    per_layer = {}
    if trace:
        import trace_reduce

        xplane = trace_reduce.find_xplane(trace_dir)
        if xplane is None:
            raise RunFailure("the worker wrote no trace under %s" % trace_dir)
        reduced = trace_reduce.reduce_trace(
            xplane, ev.steps_before(events, windows[-1]), TRACE_STEPS
        )
        if reduced is None or reduced["busy_s"] <= 0:
            raise RunFailure("the trace does not hold the steady slice")
        run["trace"] = reduced
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
    for m in loaded["per_layer"]:
        value = spec.load_reader(m["name"]).read(run)
        if value is not None:
            per_layer[m["name"]] = {"value": value, "unit": m["unit"]}
        elif trace:
            # the driver refuses a traced line that lacks a metric
            # BENCHMARK.json gives this cell: say which one it was
            _say("per-layer metric %s found nothing to read" % m["name"])
    if trace:
        metrics = per_layer
    else:
        metrics = {
            m["name"]: {"value": run[m["name"]], "unit": m["unit"]}
            for m in loaded["end_to_end"]
        }

    report = {
        "workload": cell["name"],
        "seed": seed,
        "seconds_asked": seconds,
        "trace": bool(trace),
        "records": records,
        "window_s": window_s,
        "window_steps": steps,
        "window_seconds_each": [w["seconds"] for w in windows],
        "warmup_seconds_each": [
            w["seconds"]
            for w in ev.of_kind(events, "train_window")[: ev.WARMUP_WINDOWS]
        ],
        "tokens_per_step": tokens_per_step,
        "tokens_per_s_per_chip": rate,
        "setup_s": setup_s,
        "bench_prep_s": bench_prep_s,
        "teardown_s": teardown_s,
        "compare_s": compare_s,
        "first_loss": ev.of_kind(events, "train_window")[0]["first_loss"],
        "last_loss": windows[-1]["last_loss"],
        "record_reader": built["record_reader"],
        "compile_cache_dir": built["compile_cache_dir"],
        "cache_files_added": run["cache_files_added"],
        "per_layer": {k: v["value"] for k, v in per_layer.items()},
        "checks": checks,
        "comparison": comparison,
        "memory": memory,
        "wall_s": time.time() - T0,
    }
    result = {
        "correct": all(checks.values()),
        "attempted": len(reports),
        "failed": sum(e["kind"] == "task_requeued" for e in reports),
        "metrics": metrics,
        "device": device,
    }
    if breakdown:
        result["breakdown"] = breakdown
    return report, result


def _on_wall_limit(signum, frame):
    _say("FAILED: wall-clock limit of %ds reached" % WALL_LIMIT_SECONDS)
    jobs.kill_live_groups()
    os._exit(124)


def main(argv=None, benchmark_file=spec.BENCHMARK_FILE, **platform):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    signal.signal(signal.SIGALRM, _on_wall_limit)
    signal.alarm(WALL_LIMIT_SECONDS)
    try:
        loaded = spec.load_cell(args.workload, benchmark_file)
        if not os.path.isdir(os.path.join(spec.ROOT, "elasticdl_tpu")):
            raise RunFailure(
                "the program (elasticdl_tpu/) is not beside the benchmark"
            )
        sys.path.insert(0, spec.ROOT)
        report, result = run_cell(
            loaded, args.seed, args.seconds, args.trace, **platform
        )
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            raise RunFailure("the harness process initialised a JAX backend")
    except (RunFailure, jobs.JobFailure, spec.SpecError) as e:
        _say("FAILED: %s" % e)
        return 1
    finally:
        signal.alarm(0)
    for name, ok in report["checks"].items():
        if not ok:
            _say("check failed: %s" % name)
    print(json.dumps(report))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
