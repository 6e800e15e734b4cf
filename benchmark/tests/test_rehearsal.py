"""One rehearsal of the whole harness on the CPU at a toy size: the same
``run.main`` the command calls, handed the CPU's environment in place of
the TPU's. The command line has no such switch: ``run.py`` itself only
ever asks for the TPU (test_contract.py)."""

import json
import os
import subprocess
import sys

from conftest import BENCHMARK

DRIVER = """
import sys
sys.path.insert(0, %r)
import run
sys.exit(run.main(
    ["--workload", "toy-l128", "--seed", "5", "--seconds", "3", "--trace", "0"],
    benchmark_file=%r,
    platform_env={"JAX_PLATFORMS": "cpu", "EDL_DIST_PLATFORM": "cpu",
                  "EDL_LOCAL_DEVICES": "1", "XLA_FLAGS": ""},
    platform="cpu",
))
"""


def test_rehearsal_on_cpu():
    toy = os.path.join(BENCHMARK, "tests", "data", "BENCHMARK.toy.json")
    got = subprocess.run(
        [sys.executable, "-c", DRIVER % (BENCHMARK, toy)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, timeout=600,
    )  # fmt: skip
    assert got.returncode == 0, got.stderr[-3000:]
    report, result = (json.loads(line) for line in got.stdout.splitlines()[-2:])
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True, report["checks"]
    assert set(result["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    assert result["device"]["platform"] == "cpu"  # and so never a result
    assert result["attempted"] > 0 and result["failed"] == 0
    # whole windows only, and at least what was asked
    assert report["window_s"] >= 3.0
    assert report["window_s"] == sum(report["window_seconds_each"])
    assert report["window_steps"] == 8 * len(report["window_seconds_each"])
    # the rate is the total over every measured window, stalls and all
    assert result["metrics"]["tokens_per_s_per_chip"]["value"] == (
        report["window_steps"] * report["tokens_per_step"] / report["window_s"]
    )
    assert len(report["warmup_seconds_each"]) == 2
    # the yardstick's own share of set-up is small and reported
    assert 0 < report["bench_prep_s"] < report["setup_s"] / 2
    assert report["per_layer"]["bench_prep_s"] == report["bench_prep_s"]
    assert report["teardown_s"] < 60
    # what the comparison costs is in the report, by phase
    assert report["compare_s"] > sum(report["comparison"]["seconds"].values()) > 0
    assert report["last_loss"] < report["first_loss"]
