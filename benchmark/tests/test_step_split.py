"""The readers of the device step's classes (layer_metrics/_split.py and
the six metrics on it), on the trace recorded from the chip:
tests/data/lm125m-l2048.2steps.xplane.pb.gz holds steps 40 and 41 of cell
lm125m-l2048 (PR 23), and the map a traced worker would have written
beside it is made here by hand over some dozens of its instruction
names. Everything runs on the CPU; nothing here is a measurement."""

import gzip
import json
import os
import sys

import pytest

from conftest import BENCHMARK

import spec
import trace_reduce

sys.path.insert(0, os.path.join(BENCHMARK, "layer_metrics"))
import _split  # noqa: E402

CELL = "lm125m-l2048"
STEPS = 2
READERS = (
    "fwd_ms_per_step", "bwd_ms_per_step", "remat_ms_per_step",
    "optimizer_ms_per_step", "mixed_ms_per_step", "step_split_share",
)  # fmt: skip
# instruction names of the recorded step by the front of the name, and
# the class the hand-made map gives each: the kernels are what they
# are; the others stand in (the recording is older than the scopes)
BY_PREFIX = {
    "edl_flash_fwd.": "fwd",
    "edl_flash_bwd_dq.": "bwd",
    "edl_flash_bwd_dkv.": "bwd",
    "slice_reduce_fusion.": "remat",
    "convolution_add_fusion.": "optimizer",
    "multiply_multiply_fusion.": "bwd+optimizer",
    "maximum_bitcast_fusion.": "bwd+fwd",
    "broadcast_in_dim.": "reduce",
}


def _instruction(event_name):
    return event_name.split(" = ", 1)[0].lstrip("%")


@pytest.fixture(scope="module")
def recorded_file(tmp_path_factory):
    source = os.path.join(BENCHMARK, "tests", "data", "lm125m-l2048.2steps.xplane.pb.gz")
    path = tmp_path_factory.mktemp("recorded") / "recorded.xplane.pb"
    with gzip.open(source) as f:
        path.write_bytes(f.read())
    return str(path)


@pytest.fixture(scope="module")
def by_hand(recorded_file):
    """(the map's ``ops``, {prefix: ns of its ops in the two steps}): the
    ops of the recorded step module by name, each one's own duration.
    None of the chosen ops has another nested in it (they are fusions
    and kernels of the entry computation), so self time is duration."""
    from jax.profiler import ProfileData

    (plane,) = [
        p for p in ProfileData.from_file(recorded_file).planes
        if trace_reduce.DEVICE_PLANE.match(p.name)
    ]  # fmt: skip
    ops, ns = {}, dict.fromkeys(BY_PREFIX, 0.0)
    for e in trace_reduce._line(plane, trace_reduce.OPS_LINE).events:
        name = _instruction(e.name)
        for prefix, held in BY_PREFIX.items():
            if name.startswith(prefix):
                ops[name] = held
                ns[prefix] += e.duration_ns
    return ops, ns


@pytest.fixture
def checkout(tmp_path, monkeypatch, recorded_file):
    """A checkout's run directory of the cell with the trace in it; the
    map is written by the test. Returns the trace directory."""
    _split._walk.cache_clear()
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    trace_dir = tmp_path / ".bench_runs" / CELL / "trace"
    directory = trace_dir / "plugins" / "profile" / "1"
    directory.mkdir(parents=True)
    os.symlink(recorded_file, directory / "recorded.xplane.pb")
    return trace_dir


def _write_map(trace_dir, ops, module="jit_per_device"):
    (trace_dir / _split.MAP_NAME).write_text(json.dumps({"module": module, "ops": ops}))
    _split._walk.cache_clear()


def _run(recorded_file, traced=True, mapped=True):
    """What run.py hands the readers: two windows of one step each, the
    slice the two recorded steps."""
    windows = [
        {"kind": "train_window", "id": i, "seconds": 0.2, "steps": 1, "ts": 100.0 + i}
        for i in (1, 2)
    ]
    built = {"kind": "step_built", "ts": 60.0}
    if mapped:
        built.update(step_ops_named=70, step_ops_total=4547)
    return dict(
        spec.load_cell(CELL),
        events=[built] + windows,
        windows=windows[1:],
        trace=trace_reduce.reduce_trace(recorded_file, last_step=2, n_steps=STEPS) if traced else None,
    )  # fmt: skip


def _read(run):
    return {name: spec.load_reader(name).read(run) for name in READERS}


def test_each_class_sums_to_the_self_times_counted_by_hand(checkout, recorded_file, by_hand):
    ops, ns = by_hand
    assert 60 <= len(ops) <= 150  # some dozens of the step's 4,826 ops
    _write_map(checkout, ops)
    run = _run(recorded_file)
    got = _read(run)

    def ms(*prefixes):
        return sum(ns[p] for p in prefixes) / STEPS / 1e6

    assert got["fwd_ms_per_step"] == pytest.approx(ms("edl_flash_fwd."), rel=1e-9)
    # the forward kernel: 24 calls, 32.876 ms in the two steps (test_trace_reduce.py)
    assert got["fwd_ms_per_step"] == pytest.approx(32.876 / 2, abs=0.001)
    assert got["bwd_ms_per_step"] == pytest.approx(ms("edl_flash_bwd_dq.", "edl_flash_bwd_dkv."), rel=1e-9)
    assert got["remat_ms_per_step"] == pytest.approx(ms("slice_reduce_fusion."), rel=1e-9)
    assert got["optimizer_ms_per_step"] == pytest.approx(ms("convolution_add_fusion."), rel=1e-9)
    # two pairs, one metric: a mixed op is summed whole, whatever it mixes
    assert got["mixed_ms_per_step"] == pytest.approx(
        ms("multiply_multiply_fusion.", "maximum_bitcast_fusion."), rel=1e-9
    )
    assert min(got.values()) > 0
    # `reduce` has no metric and is in no other: the share is the five
    five = sum(v for k, v in got.items() if k != "step_split_share")
    step_device_ms = spec.load_reader("step_device_ms").read(run)
    assert got["step_split_share"] == pytest.approx(100 * five / step_device_ms, rel=1e-9)
    assert 0 < five < step_device_ms


def test_a_name_the_map_lacks_lowers_the_share_and_nothing_else(checkout, recorded_file, by_hand):
    ops, _ = by_hand
    _write_map(checkout, ops)
    whole = _read(_run(recorded_file))
    # one backward kernel's instruction out of the map; and a name in
    # the map that no op of the trace bears, which changes nothing
    dropped = next(n for n in ops if n.startswith("edl_flash_bwd_dkv."))
    fewer = {n: c for n, c in ops.items() if n != dropped}
    fewer["fusion.999999"] = "optimizer"
    _write_map(checkout, fewer)
    got = _read(_run(recorded_file))
    lost = whole["bwd_ms_per_step"] - got["bwd_ms_per_step"]
    assert lost > 0.5  # one call a step of a kernel of 1.4 ms
    for name in READERS:
        if name not in ("bwd_ms_per_step", "step_split_share"):
            assert got[name] == whole[name]
    step_device_ms = spec.load_reader("step_device_ms").read(_run(recorded_file))
    assert whole["step_split_share"] - got["step_split_share"] == pytest.approx(
        100 * lost / step_device_ms, rel=1e-6
    )


def test_only_the_train_steps_module_is_classed(checkout, recorded_file, by_hand):
    """The slice also holds the tiny per-step programs, whose instruction
    names the step's module may bear too: ``pad_add_fusion`` is an op of
    ``jit__threefry_seed`` here. A map that names it classes nothing of
    another module; and a map of another module than the one the slice
    is made of is an error, not a zero."""
    ops, _ = by_hand
    _write_map(checkout, ops)
    whole = _read(_run(recorded_file))
    _write_map(checkout, dict(ops, **{"pad_add_fusion": "optimizer"}))
    assert _read(_run(recorded_file)) == whole
    _write_map(checkout, ops, module="jit_another_step")
    with pytest.raises(RuntimeError, match="no execution of that module"):
        _read(_run(recorded_file))


def test_the_xplane_is_read_once_a_process(checkout, recorded_file, by_hand, monkeypatch):
    from jax import profiler

    _write_map(checkout, by_hand[0])
    reads = []
    from_file = profiler.ProfileData.from_file
    monkeypatch.setattr(
        profiler.ProfileData, "from_file",
        staticmethod(lambda path: reads.append(path) or from_file(path)),
    )  # fmt: skip
    run = _run(recorded_file)
    reads.clear()
    _read(run)
    assert len(reads) == 1


def test_a_program_that_wrote_no_map_reads_zero(checkout, recorded_file):
    """The parent commit, and the contract test's fixture: the program
    named nothing, which is true of it, and a traced line may not lack
    a metric its cell is given."""
    assert _read(_run(recorded_file, mapped=False)) == dict.fromkeys(READERS, 0.0)


def test_an_untraced_run_reads_nothing(checkout, recorded_file):
    assert _read(_run(recorded_file, traced=False)) == dict.fromkeys(READERS, None)


def test_a_promised_map_that_is_missing_is_an_error(checkout, recorded_file, by_hand):
    run = _run(recorded_file)
    for name in READERS:  # the trace is there, the map is not
        with pytest.raises(RuntimeError, match="edl_step_ops.json is not there"):
            spec.load_reader(name).read(run)
    _write_map(checkout, by_hand[0])
    os.remove(checkout / "plugins" / "profile" / "1" / "recorded.xplane.pb")
    with pytest.raises(RuntimeError, match="the trace is not there"):
        spec.load_reader("fwd_ms_per_step").read(run)


def test_the_programs_own_join_reads_the_same(checkout, recorded_file, by_hand):
    """``tracetool --step-split`` is the same join for a person, in the
    program (which the benchmark imports nothing of): on one trace and
    one map the two agree to the nanosecond."""
    sys.path.insert(0, os.path.dirname(BENCHMARK))  # the program, beside the benchmark
    from elasticdl_tpu.tools import tracetool

    _write_map(checkout, by_hand[0])
    got = _read(_run(recorded_file))
    # the recording's third execution is cut (no op): the tool leaves a
    # trace's last execution out, and reads the two whole steps
    told = tracetool.step_split(str(checkout))
    assert (told["module"], told["devices"], told["steps"]) == ("jit_per_device", 1, STEPS)
    for bucket in ("fwd", "bwd", "remat", "optimizer", "mixed"):
        assert told["ms_per_step"][bucket] == pytest.approx(got[bucket + "_ms_per_step"], rel=1e-9)
    assert set(told["mixed_pairs"]) == {"bwd+optimizer", "bwd+fwd"}
    assert sum(told["mixed_pairs"].values()) == pytest.approx(told["ms_per_step"]["mixed"], rel=1e-9)
    assert told["ms_per_step"]["reduce"] > 0 and told["ms_per_step"]["unnamed"] > 0
    # every op of the two steps is in one bucket: the buckets sum to the busy time
    assert sum(told["ms_per_step"].values()) == pytest.approx(
        spec.load_reader("step_device_ms").read(_run(recorded_file)), rel=1e-4
    )
    assert told["top"]["fwd"][0][0] == "edl_flash_fwd_bf16_96_2048_64_"
    assert told["top"]["fwd"][0][3] == 12  # calls a step
