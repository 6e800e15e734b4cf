"""What a run is made of, found by name: the cell in ``BENCHMARK.json``,
its configuration and traffic files, and the per-layer metric readers.

Nothing here names a cell, a configuration, a traffic mix or a metric:
a later PR adds files and ``BENCHMARK.json`` entries, and edits nothing.
"""

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCHMARK_FILE = os.path.join(ROOT, "BENCHMARK.json")


class SpecError(Exception):
    pass


def _load_json(path):
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError("missing file %s" % path) from None


def load_cell(workload, benchmark_file=BENCHMARK_FILE):
    """The cell named ``workload``: its ``BENCHMARK.json`` entry, the
    configuration as run and the traffic parameters, plus the names of
    the end-to-end and per-layer metrics this cell reports."""
    root = os.path.dirname(os.path.abspath(benchmark_file))
    bench = _load_json(benchmark_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(
            "no workload %r in %s (it has: %s)"
            % (workload, benchmark_file, ", ".join(sorted(cells)))
        )
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise SpecError("workload %r names no listed config" % workload)
    config = _load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = _load_json(
        os.path.join(
            root, bench["paths"][0], "traffic", cell["traffic"] + ".json"
        )
    )

    def reported_here(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "end_to_end": [
            m for m in bench["end_to_end"] if reported_here(m)
        ],
        "per_layer": [m for m in bench["per_layer"] if reported_here(m)],
    }


def load_reader(name):
    """The reader of per-layer metric ``name``:
    ``benchmark/layer_metrics/<name>.py``, a module with ``LAYER``,
    ``UNIT``, ``SOURCE``, ``BETTER``, ``MOVES`` and ``read(run)``."""
    readers = os.path.join(HERE, "layer_metrics")
    if readers not in sys.path:
        sys.path.insert(0, readers)  # readers share _common.py
    path = os.path.join(readers, name + ".py")
    if not os.path.exists(path):
        raise SpecError("no reader %s for per-layer metric %r" % (path, name))
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + name.replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reader_names():
    return sorted(
        f[:-3]
        for f in os.listdir(os.path.join(HERE, "layer_metrics"))
        if f.endswith(".py") and not f.startswith("_")
    )
