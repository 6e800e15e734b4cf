"""The cell ``granite4h-vp8-l2048``: a traced line holds exactly the
cell's metrics, read from what this cell's step holds (the scan's loops
in a hand-made trace, the one attention layer's three flash kernels,
the state-space facts on ``step_built``, the device bytes on
``resize_end``); the configuration's cut and its parameters against
hand counts; the cost module against a count by hand; and the readers'
silence on a program that has no such layer or field (the parent
commit, on which the driver runs them too)."""

import json
import os
import sys

import pytest

import spec
from conftest import BENCHMARK

sys.path.insert(0, os.path.join(BENCHMARK, "tools"))
sys.path.insert(0, os.path.join(BENCHMARK, "layer_metrics"))
import xspace_text  # noqa: E402

CELL = "granite4h-vp8-l2048"
NEW_METRICS = {
    "ssd_ms_per_step", "flash_granite_ms_per_step", "establish_device_bytes_over_state",
}  # fmt: skip
STEPS, MAMBA_LAYERS, LENGTH = 16, 9, 2048
PARAMETERS = 772_160_448
FLASH_OPS = {
    "edl_flash_%s_bf16_64_2048_64_" % k: s
    for k, s in (("fwd", 0.020), ("bwd_dq", 0.012), ("bwd_dkv", 0.016))
}
US = 1000
PERIOD = 1000  # us a step in the hand-made trace
# the scan's loops as the chip's trace names them (PR 34's traced run):
# forward and recomputed forward carry the state, the backward loop its
# cotangent, all f32 (batch, heads, head_dim, state)
FORWARD = "%%while.%d = (s32[]{:T(128)}, f32[2,64,64,128]{3,2,1,0:T(8,128)S(1)}, bf16[8,2,256,64,64]{2,4,3,1,0:T(8,128)(2,1)}, f32[8,2,64,64,128]{4,3,2,1,0:T(8,128)S(1)}, pred[256,256]{1,0:T(8,128)(4,1)S(1)}) while(%%tuple.%d), condition=%%c, body=%%b"
BACKWARD = "%%while.%d = (s32[]{:T(128)}, f32[2,64,64,128]{3,2,1,0:T(8,128)S(1)}, bf16[8,2,256,1,64,64]{2,5,4,1,3,0:T(8,128)(2,1)S(1)}, f32[8,2,1,64,256]{3,1,4,2,0:T(2,128)S(1)}, f32[8,2,64,64,128]{4,3,2,1,0:T(8,128)}) while(%%tuple.%d), condition=%%c, body=%%b"
# one step's ops on the device, [start, end) in us from the step's start:
# two forward loops and a backward one, ops nested in the first, a loop
# that carries no such state, and a kernel
TRACE_OPS = [
    (FORWARD % (1, 1), 0, 100),
    ("%fusion.53 = f32[2,64,256]{2,1,0} fusion(%a, %b), kind=kOutput", 10, 60),
    ("%fusion.54 = bf16[8,2,256,64,64]{2,4,3,1,0} fusion(%c), kind=kLoop", 60, 90),
    (FORWARD % (2, 2), 100, 200),
    (BACKWARD % (3, 3), 200, 450),
    ("%while.9 = (s32[], f32[8,128]{1,0}, f32[8,2,64,64,128]{4,3,2,1,0}) while(%tuple.9), condition=%c2, body=%b2", 450, 500),
    ("%edl_flash_fwd.3 = (bf16[64,2048,64]{2,1,0}, f32[64,1,2048]{2,1,0}) custom-call(%q)", 500, 800),
]  # fmt: skip


def _planes(ops):
    plane = xspace_text.Plane(1, "/device:TPU:0")
    plane.line(
        "XLA Modules",
        [("jit_step(42)", k * PERIOD * US, (k * PERIOD + 900) * US, {}) for k in range(41)],
    )  # fmt: skip
    plane.line(
        "XLA Ops",
        [
            (name, (k * PERIOD + lo) * US, (k * PERIOD + hi) * US, {})
            for k in range(41)
            for name, lo, hi in ops
        ],
    )
    return xspace_text.to_xplane_bytes([plane])


@pytest.fixture
def trace_file(tmp_path, monkeypatch):
    """A trace of 41 steps (five windows of 8 and the one after) where ``_ssd.scan_loops_s`` looks for the
    run's own: under the checkout's run directory of the cell."""
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    directory = tmp_path / ".bench_runs" / CELL / "trace" / "plugins" / "profile" / "1"
    directory.mkdir(parents=True)
    path = directory / "hand.xplane.pb"
    path.write_bytes(_planes(TRACE_OPS))
    return str(path)


def _traced_run(scanning=True):
    """What run.py hands the readers after a traced run of the cell."""
    loaded = spec.load_cell(CELL)
    op_s = {"fusion_bf16_8_16_": 1.0}
    op_calls = {"fusion_bf16_8_16_": STEPS}
    windows = [
        {"kind": "train_window", "id": i, "seconds": s, "steps": 8, "ts": 100.0 + i}
        for i, s in ((1, 60.0), (2, 1.7), (3, 1.7), (4, 1.7), (5, 1.7))
    ]
    built = {"kind": "step_built", "ts": 60.0}
    established = {"kind": "resize_end", "world_s": 1, "init_s": 2, "place_s": 3, "compile_s": 0, "ts": 50.0}
    if scanning:
        for name, seconds in FLASH_OPS.items():
            op_s[name], op_calls[name] = seconds, STEPS * (2 if "fwd" in name else 1)
        built.update(
            expert_layers=0, attention_layers=1, remat_layers=1, mamba_layers=MAMBA_LAYERS,
            ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_chunk=256,
        )  # fmt: skip
        established["state_device_bytes"] = 9_281_834_496
    return dict(
        loaded,
        events=[established, built, {"kind": "task_done", "dispatch_to_report_s": 2.5, "ts": 104.0}] + windows,
        windows=windows[2:],
        window_start=102.5,
        device_kind="TPU v5 lite",
        tokens_per_s_per_chip=1.9e4,
        setup_s=130.0,
        bench_prep_s=0.5,
        cache_files_added=0,
        trace={
            "steps": STEPS,
            "busy_s": sum(op_s.values()),
            "window_s": 3.5,
            "op_s": op_s,
            "op_calls": op_calls,
            "collective_s": 0.0,
            "collective_exposed_s": 0.0,
        },
    )  # fmt: skip


def _bench():
    with open(os.path.join(os.path.dirname(BENCHMARK), "BENCHMARK.json")) as f:
        return json.load(f)


def test_a_traced_line_holds_exactly_the_cells_metrics(trace_file):
    loaded = spec.load_cell(CELL)
    run = _traced_run()
    asked = {m["name"] for m in loaded["per_layer"]}
    assert NEW_METRICS <= asked
    # the dense kernels' readers, the grouped products' and the
    # selection's list their own cells
    assert not {m for m in asked if m.startswith(("edl_", "flash_ms", "flash_sel", "select_", "gmm_", "moe_"))}
    values = {name: spec.load_reader(name).read(run) for name in asked}
    assert not [name for name, value in values.items() if value is None]
    bench = _bench()
    everywhere = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert asked == everywhere | NEW_METRICS
    moves = {"establish_device_bytes_over_state": "setup_s"}
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == moves.get(m["name"], "tokens_per_s_per_chip")
            reader = spec.load_reader(m["name"])
            assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.BETTER, reader.MOVES) == (
                m["layer"], m["unit"], m["source"], m["better"], m["moves"],
            )  # fmt: skip
    assert 0 < values["mfu"] < 100
    # the new entries are the last of their lists: nothing was put in front
    assert [m["name"] for m in bench["per_layer"]][-3:] == [
        "ssd_ms_per_step", "flash_granite_ms_per_step", "establish_device_bytes_over_state",
    ]  # fmt: skip
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "granite-4.0-h-micro-vp8"


def test_the_cell_states_its_cut():
    loaded = spec.load_cell(CELL)
    config, traffic, cell = loaded["config"], loaded["traffic"], loaded["cell"]
    assert cell["chips"] == 1 and cell["traffic"] == "l2048-tok4k"
    assert (traffic["seq_len"], traffic["minibatch_size"], traffic["expect_attention"]) == (2048, 2, "pallas")
    # one attention layer: forward (twice: the layer is recomputed), dq,
    # dkv; the scan is XLA's
    assert config["tpu_custom_calls"] == {"pallas": 4}
    (entry,) = [c for c in _bench()["configs"] if c["name"] == config["name"]]
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers", "layer_types", "vocab_size"]
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/granite-4.0-h-micro-vp8.json"
    params = config["model_params"]
    # every published width is as published, and is what the model is given
    assert (params["embed_dim"], params["mlp_dim"]) == (
        config["hidden_size"], config["shared_intermediate_size"],
    ) == (2048, 8192)  # fmt: skip
    assert config["intermediate_size"] == 8192
    assert (params["num_heads"], params["num_kv_heads"], params["head_dim"]) == (
        config["num_attention_heads"], config["num_key_value_heads"], 2048 // 32,
    ) == (32, 8, 64)  # fmt: skip
    assert (
        params["ssm_heads"], params["ssm_head_dim"], params["ssm_state"],
        params["ssm_groups"], params["ssm_conv_kernel"], params["ssm_chunk"],
    ) == (
        config["mamba_n_heads"], config["mamba_d_head"], config["mamba_d_state"],
        config["mamba_n_groups"], config["mamba_d_conv"], config["mamba_chunk_size"],
    ) == (64, 64, 128, 1, 4, 256)  # fmt: skip
    assert config["mamba_expand"] * config["hidden_size"] == params["ssm_heads"] * params["ssm_head_dim"]
    assert config["mamba_conv_bias"] is True and config["mamba_proj_bias"] is False
    assert (
        params["attention_scale"], params["embedding_multiplier"],
        params["residual_multiplier"], params["logits_scaling"], params["norm_eps"],
    ) == (
        config["attention_multiplier"], config["embedding_multiplier"],
        config["residual_multiplier"], config["logits_scaling"], config["rms_norm_eps"],
    ) == (1 / 64, 12, 0.22, 8, 1e-5)  # fmt: skip
    assert config["position_embedding_type"] == "nope"
    assert params["rope"] is False and params["qk_norm"] is False
    assert config["tie_word_embeddings"] is True and "tie_head" not in params
    # no routed expert anywhere: every layer has the dense MLP
    assert config["num_local_experts"] == config["num_experts_per_tok"] == 0
    assert params["num_dense_layers"] == len(params["layer_pattern"]) == config["num_hidden_layers"] == 10
    # what is cut is named, with the published number beside it
    letters = {"mamba": "m", "attention": "a"}
    assert "".join(letters[kind] for kind in config["layer_types"]) == params["layer_pattern"] == "mmmmmammmm"
    assert config["published"]["num_hidden_layers"] == 40 == 4 * config["num_hidden_layers"]
    assert params["vocab_size"] == config["vocab_size"] == config["published"]["vocab_size"] // 8 == 12544
    assert traffic["token_ids"] <= params["vocab_size"]
    assert config["context_length"] == config["max_position_embeddings"] == 131072
    # what the file sets beside the published sizes, each under ``assumed``
    assert params["remat_layers"] is True
    assert {"head_dim", "ssm_initialisation", "conv_taps", "dtype", "optimizer", "remat_layers", "weights"} <= set(config["assumed"])
    assert "8 chips" in config["deployment"] and "four stages" in config["deployment"]
    assert config["reference"] == "granite_hybrid_reference"
    assert config["cost"] == "granite_hybrid_share"


def test_parameters_held_against_the_hand_count():
    config = spec.load_cell(CELL)["config"]
    params, held = config["model_params"], config["held_here"]["parameters"]
    d, f, v = 2048, 8192, 12544
    inner, conv_width = 64 * 64, 64 * 64 + 2 * 128
    in_proj = d * (inner + conv_width + 64)
    mamba = in_proj + conv_width * 4 + conv_width + 3 * 64 + inner + inner * d
    attention = d * 2048 + 2 * d * 512 + 2048 * d
    swiglu = 3 * d * f
    assert (in_proj, inner * d) == (17_432_576, 8_388_608)
    assert (mamba, attention, swiglu) == (25_847_232, 10_485_760, 50_331_648)
    mamba_layer, attention_layer = mamba + swiglu + 2 * d, attention + swiglu + 2 * d
    assert (mamba_layer, attention_layer) == (76_182_976, 60_821_504)
    period = 9 * mamba_layer + attention_layer
    assert period == 746_468_288 and v * d + d == 25_692_160
    assert period + v * d + d == PARAMETERS
    assert held == dict(
        held, mamba_mixer=mamba, attention_mixer=attention, swiglu=swiglu,
        mamba_layer=mamba_layer, attention_layer=attention_layer,
        one_period_9_mamba_1_attention=period, vocabulary_slice_and_final_norm=v * d + d,
        total=PARAMETERS,
    )  # fmt: skip
    cost = spec.load_cost("granite_hybrid_share")
    assert cost.parameters(params) == PARAMETERS
    # what a token meets in a product: the projections, the MLPs, the head's slice
    products = 9 * (in_proj + inner * d) + attention + 10 * swiglu + v * d
    assert cost.matmul_params(params) == products == 771_883_008


def test_cost_module_against_a_count_by_hand():
    params = spec.load_cell(CELL)["config"]["model_params"]
    cost = spec.load_cost("granite_hybrid_share")
    # a token of a chunk of 256 has (256 + 1) / 2 earlier positions on
    # average, itself among them: c_t . b_s once (one group, 128), the
    # pair's weight times x_s a head (64 x 64); the state read and
    # written once a head (64 x 128 each way)
    scan = 128.5 * (2 * 128 + 2 * 64 * 64) + 4 * 128 * 64 * 64
    assert cost.scan_forward_flops_per_token(params, LENGTH) == scan == 3_182_720
    # a chunk is never longer than the sequence
    assert cost.scan_forward_flops_per_token(params, 64) == 32.5 * (2 * 128 + 2 * 64 * 64) + 4 * 128 * 64 * 64
    by_hand = 6 * 771_883_008 + 6 * LENGTH * 32 * 64 + 3 * 9 * scan
    assert cost.train_flops_per_token(params, LENGTH) == by_hand
    assert round(by_hand / 1e6) == 4742
    # the scan is under 2% of what a token requires, attention's pairs half a percent
    assert 0.018 < 3 * 9 * scan / by_hand < 0.019
    with pytest.raises(ValueError, match="knows layers m and a"):
        cost.parameters(dict(params, layer_pattern="mmmmmcmmmm"))
    with pytest.raises(ValueError, match="dense MLP behind each"):
        cost.parameters(dict(params, num_dense_layers=2))


def test_the_new_readers_arithmetic(trace_file):
    run = _traced_run()
    # the three loops that carry the f32 state, whole: 100 + 100 + 250 us
    # a step; the ops nested in them are not counted again, the loop
    # that carries the stacked states only not at all
    assert spec.load_reader("ssd_ms_per_step").read(run) == pytest.approx(0.45)
    assert spec.load_reader("flash_granite_ms_per_step").read(run) == pytest.approx(
        1e3 * (0.020 + 0.012 + 0.016) / STEPS
    )
    assert spec.load_reader("flash_granite_ms_per_step").read(run) == spec.load_reader(
        "flash_ms_per_step"
    ).read(run)
    share = spec.load_reader("establish_device_bytes_over_state").read(run)
    assert share == pytest.approx(9_281_834_496 / (12 * PARAMETERS)) and 1.0 < share < 1.01
    twice = dict(run, events=[dict(e) for e in run["events"]])
    twice["events"][0]["state_device_bytes"] = 2 * 9_281_834_496
    assert spec.load_reader("establish_device_bytes_over_state").read(twice) > 2.0


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_reader_is_silent_on_a_program_without_the_layer(name, trace_file):
    """The parent has no such layer, fact or field: the reader returns
    nothing and does not raise, traced or not, with a trace file or
    without."""
    run = _traced_run(scanning=False)
    assert spec.load_reader(name).read(dict(run, trace=None)) is None
    assert spec.load_reader(name).read(run) is None
    os.remove(trace_file)
    assert spec.load_reader(name).read(run) is None


def test_the_establish_reader_is_silent_where_the_backend_reports_no_memory(trace_file):
    run = _traced_run()
    run["events"][0]["state_device_bytes"] = None  # the CPU
    assert spec.load_reader("establish_device_bytes_over_state").read(run) is None
    # and in a cell whose cost module counts no parameters
    other = dict(run, config=dict(run["config"], cost="dense_tied_lm"))
    assert spec.load_reader("establish_device_bytes_over_state").read(other) is None


def test_a_scan_the_reader_cannot_find_is_an_error(trace_file):
    """The program says it scans (``mamba_layers`` on ``step_built``): a
    trace without the loops, or no trace file, is no quiet None."""
    reader, run = spec.load_reader("ssd_ms_per_step"), _traced_run()
    assert reader.read(dict(run, trace=None)) is None  # not traced: nothing asked
    with open(trace_file, "wb") as f:
        f.write(_planes([op for op in TRACE_OPS if "f32[2,64,64,128]" not in op[0]]))
    with pytest.raises(RuntimeError, match="9 state-space layers.*no `while` loop"):
        reader.read(run)
    os.remove(trace_file)
    with pytest.raises(RuntimeError, match="none found"):
        reader.read(run)


def test_the_scans_loops_are_told_by_what_they_carry():
    import _ssd

    built = {"ssm_heads": 64, "ssm_head_dim": 64, "ssm_state": 128}
    loop = _ssd._scan_loop(built, 2)
    assert loop.match(FORWARD % (1, 1)) and loop.match(BACKWARD % (3, 3))
    assert loop.match("%while = (s32[]{:T(128)}, f32[2,64,64,128]{3,2,1,0}, pred[256,256]{1,0}) while(%t)")
    for other in (
        # another batch, the stacked states alone, an op that is no loop
        "%while.9 = (s32[], f32[8,128]{1,0}, f32[8,2,64,64,128]{4,3,2,1,0}) while(%tuple.9)",
        "%while.2 = (s32[], f32[4,64,64,128]{3,2,1,0}) while(%t)",
        "%fusion.54 = f32[2,64,64,128]{3,2,1,0} fusion(%c), kind=kLoop",
        "%edl_flash_fwd.3 = (bf16[64,2048,64]{2,1,0}) custom-call(%while.1)",
    ):
        assert not loop.match(other)
    assert _ssd._scan_loop(built, 4).match("%while.2 = (s32[], f32[4,64,64,128]{3,2,1,0}) while(%t)")
