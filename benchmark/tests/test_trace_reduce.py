"""The reduction from trace to numbers, on a trace whose truth is known
because it was written by hand (as an XSpace text proto, read through
the same ``ProfileData`` the real ``.xplane.pb`` goes through)."""

import os
import sys

import pytest

import trace_reduce
from conftest import BENCHMARK

sys.path.insert(0, os.path.join(BENCHMARK, "tools"))
import xspace_text  # noqa: E402

US = 1000  # ns: the hand-made trace counts in microseconds
PERIOD, STEPS = 1000, 20

# one step on a device, [start, end) in us from the step's start
OPS = [
    ("fusion.1", 0, 300, "%fusion.1 = bf16[8,16]{1,0} fusion(%p0)"),
    ("edl_flash_fwd.2", 300, 500,
     "%edl_flash_fwd.2 = (bf16[4,1024,64]{2,1,0}, f32[4,1024]{1,0}) custom-call(%q)"),
    ("all-reduce.3", 500, 700, ""),
    ("fusion.4", 600, 650, "%fusion.4 = f32[2]{0} fusion(%a)"),  # hides 50 of it
    ("while.5", 700, 900, "%while.5 = (s32[]) while(%t)"),
    ("fusion.6", 710, 790, "%fusion.6 = bf16[4]{0} fusion(%b)"),  # inside the while
    ("fusion.7", 800, 880, "%fusion.7 = bf16[4]{0} fusion(%c)"),
]  # fmt: skip


def _device(plane_id, ordinal, shift):
    plane = xspace_text.Plane(plane_id, "/device:TPU:%d" % ordinal)
    plane.line(
        "XLA Modules",
        # a small module first: the step is the one with most time
        [("jit_convert(7)", (shift - 50) * US, (shift - 40) * US, {})]
        + [
            ("jit_step(42)", (shift + k * PERIOD) * US, (shift + k * PERIOD + 900) * US, {})
            for k in range(STEPS)
        ],
    )
    plane.line(
        "XLA Ops",
        [
            (name, (shift + k * PERIOD + s) * US, (shift + k * PERIOD + e) * US,
             {"long_name": long_name} if long_name else {})
            for k in range(STEPS)
            for name, s, e, long_name in OPS
        ],
    )  # fmt: skip
    return plane


def _host():
    plane = xspace_text.Plane(9, "/host:CPU")
    events = [("$worker.py:1 run", 0, STEPS * PERIOD * US, {})]
    for k in range(STEPS):
        events.append(("PjitFunction(step)", (k * PERIOD + 895) * US, (k * PERIOD + 990) * US, {}))
        if k % 8 == 7:
            events.append(("edl/sync", (k * PERIOD + 890) * US, (k * PERIOD + 1000) * US, {}))
    plane.line("worker-main", events)
    return plane


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "hand.xplane.pb"
    path.write_bytes(
        xspace_text.to_xplane_bytes([_device(1, 0, 0), _device(2, 1, 5), _host()])
    )
    return str(path)


def test_slice_busy_and_idle(xplane):
    got = trace_reduce.reduce_trace(xplane, last_step=18, n_steps=16)
    assert got["devices"] == 2 and got["steps"] == 16
    # steps 3..18 are executions 2..17: from the start of execution 2
    # to the start of execution 18
    assert got["window_s"] == pytest.approx(16 * PERIOD * US / 1e9)
    # the ops cover [0, 900) of every 1000: busy is a union, the nested
    # and overlapping ops count once
    assert got["busy_s"] == pytest.approx(16 * 900 * US / 1e9)


def test_per_op_self_time_under_stable_names(xplane):
    got = trace_reduce.reduce_trace(xplane, last_step=18, n_steps=16)
    per_step = {k: v / 16 * 1e9 / US for k, v in got["op_s"].items()}
    assert per_step == pytest.approx(
        {
            "fusion_bf16_8_16_": 300,
            "edl_flash_fwd_bf16_4_1024_64_": 200,
            "all-reduce": 150,  # 200 less the fusion inside it
            "fusion_f32_2_": 50,
            "while_s32_": 40,  # 200 less its two bodies
            "fusion_bf16_4_": 160,  # fusion.6 and fusion.7 are one name
        }
    )
    assert got["op_calls"]["fusion_bf16_4_"] == 32
    assert got["device_ops"][0][0] == "fusion_bf16_8_16_"
    assert sum(per_step.values()) == pytest.approx(900)


def test_collectives_and_their_exposed_part(xplane):
    got = trace_reduce.reduce_trace(xplane, last_step=18, n_steps=16)
    assert got["collective_s"] == pytest.approx(16 * 200 * US / 1e9)
    assert got["collective_exposed_s"] == pytest.approx(16 * 150 * US / 1e9)


def test_idle_gaps_are_named_by_the_host(xplane):
    got = trace_reduce.reduce_trace(xplane, last_step=18, n_steps=16)
    gaps = dict((name, s * 1e9 / US) for name, s in got["idle_gaps"])
    # 16 gaps of 100 us; the two at sync points lie wholly inside
    # edl/sync, the others are covered most by the dispatch; the Python
    # frame that spans everything names nothing
    assert gaps == pytest.approx({"PjitFunction_step_": 1400, "edl/sync": 200})


def test_a_trace_that_ends_before_the_slice_gives_nothing(xplane):
    assert trace_reduce.reduce_trace(xplane, last_step=20, n_steps=16) is None
    assert trace_reduce.reduce_trace(xplane, last_step=8, n_steps=16) is None


def test_stable_name():
    assert trace_reduce.stable_name("fusion.123", "%fusion.123 = bf16[8,2047]{1,0} fusion()") == "fusion_bf16_8_2047_"
    assert trace_reduce.stable_name("copy.1") == "copy"
    # the TPU's trace names an op by its whole HLO line
    assert (
        trace_reduce.stable_name(
            "%edl_flash_bwd_dkv.23 = (bf16[96,2048,64]{2,1,0:T(8,128)(2,1)}, bf16[96,2048,64]{2,1,0}) custom-call(%a)"
        )
        == "edl_flash_bwd_dkv_bf16_96_2048_64_"
    )


# -- a recorded trace ------------------------------------------------------
# tests/data/lm125m-l2048.2steps.xplane.pb.gz: steps 40 and 41 of a traced
# run of cell lm125m-l2048 on one v5e chip (PR 23), cut down by
# tools/cut_trace.py. Step 40 ends a sync window, so one of the two gaps
# is the host waiting for the window's losses.


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import gzip

    source = os.path.join(
        BENCHMARK, "tests", "data", "lm125m-l2048.2steps.xplane.pb.gz"
    )
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(source) as f:
        path.write_bytes(f.read())
    return trace_reduce.reduce_trace(str(path), last_step=2, n_steps=2)


def test_recorded_trace_slice(recorded):
    assert recorded["devices"] == 1
    assert recorded["window_s"] == pytest.approx(0.370994295, rel=1e-6)
    assert recorded["busy_s"] == pytest.approx(0.319727005, rel=1e-6)
    # self times partition the busy time: nothing counted twice
    assert sum(recorded["op_s"].values()) == pytest.approx(recorded["busy_s"], rel=1e-9)
    assert recorded["collective_s"] == 0.0  # one chip


def test_recorded_trace_names_the_kernels_and_the_gaps(recorded):
    # 12 layers x 2 steps of each kernel, named by kernel and shape
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        assert recorded["op_calls"]["edl_flash_%s_bf16_96_2048_64_" % kernel] == 24
    assert [name for name, _ in recorded["device_ops"][:3]] == [
        "edl_flash_bwd_dkv_bf16_96_2048_64_",
        "edl_flash_bwd_dq_bf16_96_2048_64_",
        "edl_flash_fwd_bf16_96_2048_64_",
    ]
    gaps = dict(recorded["idle_gaps"])
    assert gaps["np.asarray_jax.Array_"] == pytest.approx(0.027392771, rel=1e-6)
    assert "PjitFunction_per_device_" in gaps
    assert sum(gaps.values()) == pytest.approx(
        recorded["window_s"] - recorded["busy_s"], rel=1e-6
    )


def test_recorded_trace_roofline_reader(recorded):
    sys.path.insert(0, os.path.join(BENCHMARK, "layer_metrics"))
    import _common

    run = {"trace": recorded, "device_kind": "TPU v5 lite"}
    # 24 calls x 2 x 96 x 2048^2 x 64 FLOPs / 197e12 = 6.279 ms least,
    # 32.876 ms measured
    assert _common.flash_roofline(run, "edl_flash_fwd") == pytest.approx(19.10, abs=0.01)
    assert _common.flash_roofline(run, "edl_flash_bwd_dq") == pytest.approx(25.05, abs=0.05)
    assert _common.flash_roofline(run, "edl_flash_nothing") is None
