"""The cell ``keyevl2-ep8-l8192``: a traced line holds exactly the
cell's metrics, read from what this cell's step holds (the three flash
kernels under a selection, four layers of them; the selection's loops in
a hand-made trace; the selection's facts and counters on its events);
the new readers' and cost modules' arithmetic against hand counts; and
the readers' silence on a program that has no such kernel, fact or loop
(the parent commit, on which the driver runs them too)."""

import json
import os
import sys

import pytest

import flash_sel_cost
import flops
import spec
from conftest import BENCHMARK

sys.path.insert(0, os.path.join(BENCHMARK, "tools"))
sys.path.insert(0, os.path.join(BENCHMARK, "layer_metrics"))
import xspace_text  # noqa: E402

CELL = "keyevl2-ep8-l8192"
NEW_METRICS = {
    "select_ms_per_step", "flash_sel_ms_per_step", "edl_flash_sel_fwd_roofline",
    "edl_flash_sel_bwd_dq_roofline", "edl_flash_sel_bwd_dkv_roofline",
}  # fmt: skip
STEPS, LAYERS, HEADS, LENGTH, TOPK = 16, 4, 32, 8192, 2048
KEPT, CAUSAL = 14_681_088, 33_558_528  # a sequence: hand counts below
SEL_OPS = {
    "edl_flash_sel_%s_bf16_32_8192_128_" % k: s
    for k, s in (("fwd", 0.30), ("bwd_dq", 0.40), ("bwd_dkv", 0.55))
}
US = 1000
PERIOD = 1000  # us a step in the hand-made trace
# one step's ops on the device, [start, end) in us from the step's start:
# a selection loop per layer span (two here), ops nested in the first,
# a loop that carries no int8 blocks, and a kernel
LOOP = "%%while.%d = (s32[]{:T(128)}, s8[4,1,512,%d]{3,2,0,1:T(8,128)(4,1)}, s32[4]{0}, f32[1,8192,16,64]{1,3,2,0}) while(%%tuple.%d), condition=%%c, body=%%b"
TRACE_OPS = [
    (LOOP % (1, 2048, 1), 0, 100),
    ("%fusion.53 = f32[2048,512]{1,0} fusion(%a, %b), kind=kOutput", 10, 60),
    ("%fusion.54 = s8[1,512,2048]{2,1,0} fusion(%c), kind=kLoop", 60, 90),
    (LOOP % (2, 8192, 2), 100, 350),
    ("%while.9 = (s32[], f32[8,128]{1,0}) while(%tuple.9), condition=%c2, body=%b2", 350, 450),
    ("%edl_flash_sel_fwd.3 = (bf16[32,8192,128]{2,1,0}, f32[32,1,8192]{2,1,0}) custom-call(%q)", 450, 800),
]  # fmt: skip


@pytest.fixture
def trace_file(tmp_path, monkeypatch):
    """A trace of 21 steps where ``_sel.trace_file`` looks for the
    run's own: under the checkout's run directory of the cell."""
    plane = xspace_text.Plane(1, "/device:TPU:0")
    plane.line(
        "XLA Modules",
        [("jit_step(42)", k * PERIOD * US, (k * PERIOD + 900) * US, {}) for k in range(21)],
    )  # fmt: skip
    plane.line(
        "XLA Ops",
        [
            (name, (k * PERIOD + lo) * US, (k * PERIOD + hi) * US, {})
            for k in range(21)
            for name, lo, hi in TRACE_OPS
        ],
    )
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    directory = tmp_path / ".bench_runs" / CELL / "trace" / "plugins" / "profile" / "1"
    directory.mkdir(parents=True)
    path = directory / "hand.xplane.pb"
    path.write_bytes(xspace_text.to_xplane_bytes([plane]))
    return str(path)


def _traced_run(selecting=True):
    """What run.py hands the readers after a traced run of the cell."""
    loaded = spec.load_cell(CELL)
    op_s = {"fusion_bf16_8_16_": 1.0}
    op_calls = {"fusion_bf16_8_16_": STEPS}
    windows = [
        {"kind": "train_window", "id": i, "seconds": s, "steps": 4, "ts": 100.0 + i}
        for i, s in ((1, 20.0), (2, 1.2), (3, 1.2), (4, 1.2), (5, 1.2))
    ]
    built = {"kind": "step_built", "ts": 60.0}
    if selecting:
        for name, seconds in SEL_OPS.items():
            op_s[name], op_calls[name] = seconds, STEPS * LAYERS
        for w in windows:
            w.update(
                sel_pairs_kept=4 * LAYERS * KEPT, sel_pairs_causal=4 * LAYERS * CAUSAL,
                moe_rows_here=4 * LAYERS * 8192, moe_rows_routed=4 * LAYERS * 65536,
                moe_rows_max_expert=4 * 640, moe_rows_mean_expert=4 * 512.0,
            )  # fmt: skip
        built.update(
            expert_layers=LAYERS, experts_held=16, experts_routed=128,
            sparse_layers=LAYERS, select_topk=TOPK, indexer_heads=16,
        )  # fmt: skip
    return dict(
        loaded,
        events=[
            {"kind": "resize_end", "world_s": 1, "init_s": 2, "place_s": 3, "compile_s": 0, "ts": 50.0},
            built,
            {"kind": "task_done", "dispatch_to_report_s": 2.5, "ts": 104.0},
        ] + windows,
        windows=windows[2:],
        window_start=102.5,
        device_kind="TPU v5 lite",
        tokens_per_s_per_chip=2e4,
        setup_s=150.0,
        bench_prep_s=0.5,
        cache_files_added=0,
        trace={
            "steps": STEPS,
            "busy_s": sum(op_s.values()),
            "window_s": 6.4,
            "op_s": op_s,
            "op_calls": op_calls,
            "collective_s": 0.0,
            "collective_exposed_s": 0.0,
        },
    )  # fmt: skip


def test_a_traced_line_holds_exactly_the_cells_metrics(trace_file):
    loaded = spec.load_cell(CELL)
    run = _traced_run()
    asked = {m["name"] for m in loaded["per_layer"]}
    assert NEW_METRICS <= asked
    # the dense kernels' readers and the grouped products' list their own cells
    assert not {m for m in asked if m.startswith(("edl_flash_fwd", "edl_flash_bwd", "flash_ms", "edl_gmm", "edl_tgmm"))}
    values = {name: spec.load_reader(name).read(run) for name in asked}
    assert not [name for name, value in values.items() if value is None]
    # every metric that lists no cells is reported here too
    with open(os.path.join(os.path.dirname(BENCHMARK), "BENCHMARK.json")) as f:
        bench = json.load(f)
    everywhere = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert asked == everywhere | NEW_METRICS
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s_per_chip"
            reader = spec.load_reader(m["name"])
            assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.BETTER) == (
                m["layer"], m["unit"], m["source"], m["better"],
            )  # fmt: skip
    assert 0 < values["mfu"] < 100
    for name in NEW_METRICS:
        if name.endswith("roofline"):
            assert 0 < values[name] < 100


def test_the_cell_states_its_cut():
    loaded = spec.load_cell(CELL)
    config, traffic, cell = loaded["config"], loaded["traffic"], loaded["cell"]
    assert cell["chips"] == 1 and cell["traffic"] == "l8192-tok8k-ids4k"
    assert {k: traffic[k] for k in (
        "seq_len", "minibatch_size", "minibatches_per_task", "tasks_per_epoch",
        "token_ids", "unigram", "expect_attention",
    )} == {
        "seq_len": 8192, "minibatch_size": 1, "minibatches_per_task": 16,
        "tasks_per_epoch": 2, "token_ids": 4096, "unigram": "zipf-1",
        "expect_attention": "pallas",
    }  # fmt: skip
    # a layer: three kernels under a selection, and no grouped product
    # (every held expert over every token: plain matrix products)
    assert config["model_params"]["expert_apply"] == "masked"
    assert config["tpu_custom_calls"] == {"pallas": 3 * LAYERS}
    with open(os.path.join(os.path.dirname(BENCHMARK), "BENCHMARK.json")) as f:
        (entry,) = [c for c in json.load(f)["configs"] if c["name"] == config["name"]]
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == config["source"]
    params = config["model_params"]
    # every published width is as published, and is what the model is given
    assert (params["embed_dim"], params["expert_dim"], params["head_dim"]) == (
        config["hidden_size"], config["moe_intermediate_size"], config["head_dim"],
    ) == (2048, 768, 128)  # fmt: skip
    assert (params["num_heads"], params["num_kv_heads"], params["num_experts_per_tok"]) == (
        config["num_attention_heads"], config["num_key_value_heads"], config["num_experts_per_tok"],
    ) == (32, 4, 8)  # fmt: skip
    sa = config["sa_config"]
    assert (params["indexer_heads"], params["indexer_dim"], params["select_topk"]) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"],
    ) == (16, 64, 2048)  # fmt: skip
    assert sa["indexer_num_kv_heads"] == 1
    assert (params["rope_theta"], params["norm_eps"]) == (config["rope_theta"], config["rms_norm_eps"])
    assert params["tie_head"] is config["tie_word_embeddings"] is False
    assert params["routing"] == "softmax" and config["norm_topk_prob"] is True
    # what the file sets beside the published sizes, each under ``assumed``
    assert "remat_experts" not in params and "expert_bias_rate" not in params
    assert {"expert_apply", "routing", "indexer", "loss"} <= set(config["assumed"])
    assert "expert_bias" not in config["assumed"]  # the source has none: none is made up
    # what is cut is named, with the published number beside it
    assert params["experts_held"] == config["num_experts"] == 16
    assert params["num_experts"] == config["published"]["num_experts"] == config["num_local_experts"] == 128
    assert len(params["layer_pattern"]) == config["num_hidden_layers"] == 4
    assert set(params["layer_pattern"]) == {"s"} and params["num_dense_layers"] == 0
    assert params["vocab_size"] == config["vocab_size"] == config["published"]["vocab_size"] // 8 == 18992
    # the selection does something at this length, and only over the top-k
    assert traffic["seq_len"] == 4 * params["select_topk"]
    assert traffic["token_ids"] <= params["vocab_size"]


def test_parameters_held_against_the_hand_count():
    params = spec.load_cell(CELL)["config"]["model_params"]
    d, f, v = 2048, 768, 18992
    attention = d * 4096 + 2 * d * 512 + 4096 * d
    indexer = d * (16 * 64 + 64 + 16)
    norms = 2 * d + 2 * 128
    layer = attention + indexer + d * 128 + 16 * 3 * d * f + norms
    assert (attention, indexer, 3 * d * f) == (18_874_368, 2_260_992, 4_718_592)
    total = 4 * layer + 2 * v * d + d
    assert total == 465_390_592  # 465.4M
    cost = spec.load_cost("keye_sparse_moe_share")
    always = 4 * (attention + d * 128) + v * d
    assert cost.matmul_params(params) == always == 115_441_664
    # 8 a token, an eighth of them here, in each of 4 layers
    assert cost.expert_params_per_token(params) == 4 * 8 / 8 * 3 * d * f
    # query t reads min(t + 1, 2048) keys
    assert cost.pairs_kept(8192, 2048) == (KEPT, CAUSAL)
    assert KEPT == 2048 * 2049 // 2 + 6144 * 2048 and CAUSAL == 8192 * 8193 // 2
    assert 0.437 < KEPT / CAUSAL < 0.438
    assert cost.pairs_kept(1024, 2048) == (1024 * 1025 // 2,) * 2
    flops_token = cost.train_flops_per_token(params, 8192)
    by_hand = (
        6 * (always + 4 * 3 * d * f)
        + 12 * KEPT / 8192 * 32 * 128 * 4
        + 4 * (2 * indexer + 2 * 16 * 64 * CAUSAL / 8192)
    )
    assert flops_token == pytest.approx(by_hand) and round(flops_token / 1e6) == 1210
    config = spec.load_cell(CELL)["config"]
    assert config["cost"] == "keye_sparse_moe_share"
    assert config["reference"] == "keye_sparse_moe_reference"


def test_the_new_readers_arithmetic(trace_file):
    run = _traced_run()
    assert spec.load_reader("flash_sel_ms_per_step").read(run) == pytest.approx(
        1e3 * (0.30 + 0.40 + 0.55) / STEPS
    )
    # the two loops that carry int8 blocks, whole: 100 + 250 us a step;
    # the ops nested in them are not counted again, the third loop not at all
    assert spec.load_reader("select_ms_per_step").read(run) == pytest.approx(0.35)
    calls = STEPS * LAYERS
    for kernel, matmuls, seconds in (("fwd", 2, 0.30), ("bwd_dq", 3, 0.40), ("bwd_dkv", 4, 0.55)):
        name = "edl_flash_sel_" + kernel
        cost = flash_sel_cost.selected_kernel_cost(name, HEADS, LENGTH, 128, TOPK, HEADS)
        # over the KEPT pairs, and the selection's bytes once a sequence
        assert cost[0] == matmuls * 2 * HEADS * KEPT * 128
        tensors, rows = {"fwd": (4, 1), "bwd_dq": (5, 2), "bwd_dkv": (6, 2)}[kernel]
        assert cost[1] == HEADS * LENGTH * (tensors * 128 * 2 + rows * 4) + LENGTH**2
        least, bound = flops.roofline(*cost, "TPU v5 lite")
        assert bound == "compute"
        share = spec.load_reader(name + "_roofline").read(run)
        assert share == pytest.approx(100 * calls * least / seconds) and 0 < share < 100
        # a kernel as fast as the dense one at ITS roofline reads 44%
        dense = flops.flash_kernel_cost(name.replace("_sel", ""), HEADS, LENGTH, 128)
        assert cost[0] / dense[0] == pytest.approx(KEPT / (LENGTH**2 / 2))
        assert cost[0] / dense[0] < 0.4376
    # two sequences' heads in one call: two selections' bytes
    two = flash_sel_cost.selected_kernel_cost("edl_flash_sel_fwd", 2 * HEADS, LENGTH, 128, TOPK, HEADS)
    one = flash_sel_cost.selected_kernel_cost("edl_flash_sel_fwd", HEADS, LENGTH, 128, TOPK, HEADS)
    assert two == (2 * one[0], 2 * one[1])


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_reader_is_silent_on_a_program_without_a_selection(name, trace_file):
    """The parent has no such kernel, fact or loop: the reader returns
    nothing and does not raise, traced or not, with a trace file or
    without."""
    run = _traced_run(selecting=False)
    assert spec.load_reader(name).read(dict(run, trace=None)) is None
    assert spec.load_reader(name).read(run) is None
    os.remove(trace_file)
    assert spec.load_reader(name).read(run) is None


def test_a_selection_the_reader_cannot_find_is_an_error(trace_file, tmp_path):
    """The program says it selects (``select_topk`` on ``step_built``):
    a trace without the loops, or no trace file, is no quiet None."""
    reader, run = spec.load_reader("select_ms_per_step"), _traced_run()
    assert reader.read(dict(run, trace=None)) is None  # not traced: nothing asked
    plane = xspace_text.Plane(1, "/device:TPU:0")
    plane.line(
        "XLA Modules",
        [("jit_step(42)", k * PERIOD * US, (k * PERIOD + 900) * US, {}) for k in range(21)],
    )  # fmt: skip
    plane.line(
        "XLA Ops",
        [
            (name, (k * PERIOD + lo) * US, (k * PERIOD + hi) * US, {})
            for k in range(21)
            for name, lo, hi in TRACE_OPS
            if not name.startswith("%while.1 ") and not name.startswith("%while.2 ")
        ],
    )
    with open(trace_file, "wb") as f:
        f.write(xspace_text.to_xplane_bytes([plane]))
    with pytest.raises(RuntimeError, match="select_topk=2048.*no `while` loop"):
        reader.read(run)
    os.remove(trace_file)
    with pytest.raises(RuntimeError, match="none found"):
        reader.read(run)


def test_the_selections_loops_are_told_by_what_they_carry(trace_file):
    import _sel

    assert _sel._SELECT_LOOP.match(LOOP % (1, 2048, 1))
    assert _sel._SELECT_LOOP.match("%while = (s32[]{:T(128)}, s8[16,1,512,8192]{3,2,0,1:T(8,128)(4,1)}, s32[16]")
    for other in (
        "%while.9 = (s32[], f32[8,128]{1,0}) while(%tuple.9)",
        "%fusion.54 = s8[1,512,2048]{2,1,0} fusion(%c), kind=kLoop",
        "%while.2 = (s32[], s8[512,2048]{1,0}) while(%t)",
        "%edl_flash_sel_fwd.3 = (bf16[32,8192,128]{2,1,0}) custom-call(%while.1)",
    ):
        assert not _sel._SELECT_LOOP.match(other)
