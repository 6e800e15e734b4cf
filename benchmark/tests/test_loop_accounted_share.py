"""``loop_accounted_share``: the reader's arithmetic on windows with and
without the program's phase fields, and the metric on the report line of
a CPU rehearsal of the whole harness (a toy benchmark file of its own
lists it; the rehearsal's driver is test_rehearsal.py's)."""

import json
import os
import subprocess
import sys

import pytest

import spec
from conftest import BENCHMARK
from test_rehearsal import DRIVER

sys.path.insert(0, spec.ROOT)  # the program, for the list the reader copies
from elasticdl_tpu.utils.profiling import STEP_PHASES as PHASES  # noqa: E402


@pytest.fixture(scope="module")
def reader():
    return spec.load_reader("loop_accounted_share")


def test_reader_names_the_programs_phases_and_is_reported_everywhere(reader):
    assert reader.PHASES == PHASES and len(PHASES) == 8
    with open(os.path.join(os.path.dirname(BENCHMARK), "BENCHMARK.json")) as f:
        (entry,) = [
            m for m in json.load(f)["per_layer"]
            if m["name"] == "loop_accounted_share"
        ]  # fmt: skip
    # reported in every cell, those a later PR adds too
    assert "workloads" not in entry


def test_a_bare_window_accounts_for_nothing(reader):
    """A program without the phase clocks (this PR's parent): 0.0, which
    is true of it, and never None."""
    run = {"windows": [{"seconds": 1.5, "steps": 8}, {"seconds": 1.4, "steps": 8}]}
    assert reader.read(run) == 0.0


@pytest.mark.parametrize("phase", PHASES)
def test_each_phase_field_counts_once(reader, phase):
    run = {"windows": [{"seconds": 2.0, "steps": 8, phase + "_s": 0.5}]}
    assert reader.read(run) == 25.0


def test_share_is_a_total_over_the_measured_windows(reader):
    full = dict.fromkeys((p + "_s" for p in PHASES), 0.125)
    run = {
        "windows": [
            dict(full, seconds=1.0, steps=8),  # wholly accounted
            dict(seconds=3.0, steps=8, fetch_s=1.0, slowest_call_s=1.0),
        ]
    }
    # (1.0 + 1.0) of 4.0 s; slowest_call_s is no phase
    assert reader.read(run) == 50.0


def test_rehearsal_line_carries_the_share():
    toy = os.path.join(BENCHMARK, "tests", "data", "BENCHMARK.toy-loop.json")
    got = subprocess.run(
        [sys.executable, "-c", DRIVER % (BENCHMARK, toy)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, timeout=600,
    )  # fmt: skip
    assert got.returncode == 0, got.stderr[-3000:]
    report, result = (json.loads(line) for line in got.stdout.splitlines()[-2:])
    assert result["correct"] is True, report["checks"]
    share = report["per_layer"]["loop_accounted_share"]
    # the clocks are disjoint pieces of each window's seconds (which are
    # rounded to a millisecond), and cover nearly all of a steady window
    assert 80.0 < share <= 100.0 + 100.0 * 1e-3 * len(
        report["window_seconds_each"]
    ) / report["window_s"]
