"""``BENCHMARK.json`` against the contract's limits and against the
files it names; the harness taking additions as data; and ``run.py``
refusing to report anything where there is no TPU or no program."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import spec
from conftest import BENCHMARK

ROOT = os.path.dirname(BENCHMARK)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_limits(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }  # fmt: skip
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert bench["paths"] == ["benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    # a full check with all 24 cells has to fit its 43,200 s
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 2 <= len(bench["workloads"]) <= 24
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert c["file"].startswith("benchmark/")
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_end_to_end_metrics_are_the_two(bench):
    assert [m["name"] for m in bench["end_to_end"]] == [
        "tokens_per_s_per_chip",
        "setup_s",
    ]
    for m in bench["end_to_end"]:
        assert set(m) == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1 and UNIT.match(m["unit"])


def test_every_per_layer_metric_has_its_reader_and_agrees_with_it(bench):
    assert sorted(m["name"] for m in bench["per_layer"]) == spec.reader_names()
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves",
        }  # fmt: skip
        reader = spec.load_reader(m["name"])
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
            reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER, reader.MOVES,
        )  # fmt: skip
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert set(m.get("workloads", cells)) <= cells


def test_every_cell_loads(bench):
    for w in bench["workloads"]:
        loaded = spec.load_cell(w["name"])
        params = loaded["config"]["model_params"]
        assert loaded["traffic"]["seq_len"] <= loaded["config"]["context_length"]
        assert params["num_heads"] * params["head_dim"] == params["embed_dim"]
        assert {m["name"] for m in loaded["end_to_end"]} == {
            "tokens_per_s_per_chip", "setup_s",
        }  # fmt: skip
        assert loaded["per_layer"]
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell")


def test_additions_are_data(tmp_path, bench):
    """A later PR adds a configuration, a traffic mix and a cell by
    adding files and entries; no file that exists is edited. Shown on a
    copy of the benchmark."""
    copy = tmp_path / "checkout"
    shutil.copytree(BENCHMARK, copy / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    # the new files
    shutil.copy(copy / "benchmark/configs/lm-125m.json", copy / "benchmark/configs/lm-other.json")
    shutil.copy(copy / "benchmark/traffic/l512-tok16k.json", copy / "benchmark/traffic/l512-other.json")
    # the new entries
    bench = json.loads(json.dumps(bench))
    bench["configs"].append(
        dict(bench["configs"][0], name="lm-other", file="benchmark/configs/lm-other.json")
    )
    bench["workloads"].append(
        {"name": "other-l512", "config": "lm-other", "traffic": "l512-other", "chips": 1, "why": "x"}
    )
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))
    found = subprocess.run(
        [
            sys.executable, "-c",
            "import sys; sys.path.insert(0, 'benchmark'); import spec;"
            "c = spec.load_cell('other-l512');"
            "print(c['config']['model_params']['num_layers'], c['traffic']['seq_len'],"
            " len(c['per_layer']))",
        ],  # fmt: skip
        cwd=copy, stdout=subprocess.PIPE, text=True, check=True,
    )  # fmt: skip
    # the new cell reports every metric that lists no cells; one that
    # exists only in some cells (kernels, collectives) names them
    everywhere = [m for m in bench["per_layer"] if "workloads" not in m]
    assert 0 < len(everywhere) < len(bench["per_layer"])
    assert found.stdout.split() == ["12", "512", str(len(everywhere))]


def _traced_run(loaded):
    """What run.py hands the readers after a traced run of the cell, by
    what the cell's own files say it runs: the flash kernels where the
    traffic expects them, collectives where there is more than one chip."""
    cell, traffic = loaded["cell"], loaded["traffic"]
    params = loaded["config"]["model_params"]
    op_s, steps = {"fusion_bf16_8_16_": 1.0}, 16
    if traffic["expect_attention"] == "pallas":
        shape = "bf16_%d_%d_%d_" % (
            traffic["minibatch_size"] // cell["chips"] * params["num_heads"],
            traffic["seq_len"],
            params["head_dim"],
        )
        for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
            op_s["edl_flash_%s_%s" % (kernel, shape)] = 0.3
    collective_s = 0.1 if cell["chips"] > 1 else 0.0
    window = {"kind": "train_window", "id": 3, "seconds": 1.5, "steps": 8, "ts": 103.0}
    return dict(
        loaded,
        events=[
            {"kind": "resize_end", "world_s": 1, "init_s": 2, "place_s": 3, "compile_s": 0, "ts": 50.0},
            dict(window, id=1, seconds=20.0, ts=100.0),
            {"kind": "task_done", "dispatch_to_report_s": 2.5, "ts": 102.0},
            window,
        ],
        windows=[window],
        window_start=101.5,
        device_kind="TPU v5 lite",
        tokens_per_s_per_chip=9e4,
        setup_s=60.0,
        bench_prep_s=0.5,
        cache_files_added=0,
        trace={
            "steps": steps,
            "busy_s": sum(op_s.values()) + collective_s,
            "window_s": 3.0,
            "op_s": op_s,
            "op_calls": {name: steps * params["num_layers"] for name in op_s},
            "collective_s": collective_s,
            "collective_exposed_s": collective_s,
        },
    )  # fmt: skip


@pytest.mark.parametrize(
    "workload", [w["name"] for w in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]
)  # fmt: skip
def test_a_traced_line_holds_each_metric_of_its_cell(workload):
    """The driver refuses a ``--trace 1`` line that lacks a per-layer
    metric ``BENCHMARK.json`` gives the cell (it refused this benchmark
    once for that). So a metric whose reader finds nothing in some cell
    lists the cells where it does, and no reader is silent in a cell
    that is listed."""
    loaded = spec.load_cell(workload)
    run = _traced_run(loaded)
    asked = {m["name"] for m in loaded["per_layer"]}
    silent = {n for n in asked if spec.load_reader(n).read(run) is None}
    assert not silent
    left_out = set(spec.reader_names()) - asked
    spoke = {n for n in left_out if spec.load_reader(n).read(run) is not None}
    assert not spoke


def _run(cwd, *args):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # the harness names the TPU itself
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *args],
        cwd=cwd, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=300,
    )  # fmt: skip


def test_nothing_is_reported_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files
    under ``paths``: another exit code than 0, and no result."""
    shutil.copytree(BENCHMARK, tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    got = _run(tmp_path, "--workload", "lm125m-l512", "--seed", "1", "--seconds", "2", "--trace", "0")
    assert got.returncode != 0 and got.stdout == ""
    assert "not beside the benchmark" in got.stderr


def test_nothing_is_reported_without_a_tpu():
    """run.py end to end here, where JAX finds no TPU: the worker dies
    at backend start, ``edl train`` fails the job, and the harness
    prints no result, exits non-zero and leaves no process behind."""
    got = _run(ROOT, "--workload", "lm125m-l512", "--seed", "1", "--seconds", "2", "--trace", "0")
    assert got.returncode != 0 and got.stdout == ""
    assert "FAILED" in got.stderr
    left = subprocess.run(
        ["pgrep", "-f", "elasticdl_tpu.worker.main|elasticdl_tpu.cli train"],
        stdout=subprocess.PIPE, text=True,
    )  # fmt: skip
    assert left.stdout.strip() == ""
