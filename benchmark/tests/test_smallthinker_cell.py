"""The cell ``smallthinker-ep8-l16384``: the configuration file's
parameter count by hand, the cost module and ``flash_win_cost`` against
hand arithmetic, a traced line holding exactly the cell's metrics (read
from what this cell's step holds: the three flash kernels under a
window in three layers, the plain three in one), the new readers'
silence on a program without a window (the parent commit, on which the
driver runs them too), and the float8 control refused at a toy size."""

import json
import os
import sys

import pytest

import compare
import flash_win_cost
import flops
import spec
from conftest import BENCHMARK

sys.path.insert(0, os.path.join(BENCHMARK, "layer_metrics"))

CELL = "smallthinker-ep8-l16384"
CONFIG = "smallthinker-21b-a3b-ep8"
NEW_METRICS = {
    "flash_win_ms_per_step", "edl_flash_win_fwd_roofline",
    "edl_flash_win_bwd_dq_roofline", "edl_flash_win_bwd_dkv_roofline",
    "flash_global_ms_per_step",
}  # fmt: skip
STEPS, HEADS, LENGTH, WINDOW = 16, 28, 16384, 4096
BAND, CAUSAL = 58_722_304, 134_225_920  # a sequence: hand counts below
PARAMETERS = 370_547_200
# seconds in the slice: the windowed kernels of three layers, the plain
# ones of one
WIN_OPS = {
    "edl_flash_win_%s_bf16_28_16384_128_" % k: (s, calls)
    for k, s, calls in (("fwd", 0.6, 3), ("bwd_dq", 0.9, 3), ("bwd_dkv", 1.1, 3))
}
PLAIN_OPS = {
    "edl_flash_%s_bf16_28_16384_128_" % k: (s, calls)
    for k, s, calls in (("fwd", 0.4, 1), ("bwd_dq", 0.6, 1), ("bwd_dkv", 0.7, 1))
}


def _bench():
    with open(os.path.join(os.path.dirname(BENCHMARK), "BENCHMARK.json")) as f:
        return json.load(f)


def _traced_run(windowed=True):
    """What run.py hands the readers after a traced run of the cell."""
    loaded = spec.load_cell(CELL)
    op_s = {"fusion_bf16_8_16_": 1.0}
    op_calls = {"fusion_bf16_8_16_": STEPS}
    for name, (seconds, calls) in PLAIN_OPS.items():
        op_s[name], op_calls[name] = seconds, STEPS * calls
    windows = [
        {"kind": "train_window", "id": i, "seconds": s, "steps": 4, "ts": 100.0 + i}
        for i, s in ((1, 20.0), (2, 3.2), (3, 3.2), (4, 3.2), (5, 3.2))
    ]
    built = {"kind": "step_built", "ts": 60.0}
    if windowed:
        for name, (seconds, calls) in WIN_OPS.items():
            op_s[name], op_calls[name] = seconds, STEPS * calls
        for w in windows:
            w.update(
                moe_rows_here=4 * 4 * 12288, moe_rows_routed=4 * 4 * 98304,
                moe_rows_max_expert=4 * 2000, moe_rows_mean_expert=4 * 1536.0,
            )  # fmt: skip
        built.update(
            expert_layers=4, experts_held=8, experts_routed=64,
            window_layers=3, attention_window=WINDOW,
            window_pairs_kept=BAND, window_pairs_causal=CAUSAL,
            router_input="operator_norm", expert_act="relu",
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
            "window_s": 12.8,
            "op_s": op_s,
            "op_calls": op_calls,
            "collective_s": 0.0,
            "collective_exposed_s": 0.0,
        },
    )  # fmt: skip


def test_the_cells_exact_list_of_metrics():
    loaded = spec.load_cell(CELL)
    asked = {m["name"] for m in loaded["per_layer"]}
    bench = _bench()
    everywhere = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert asked == everywhere | NEW_METRICS
    # the dense kernels' readers (whose prefix would catch nothing of a
    # window's), the grouped products', the selection's and the scan's
    # list their own cells
    assert not {
        m for m in asked
        if m.startswith(("edl_flash_fwd", "edl_flash_bwd", "flash_ms", "flash_sel", "flash_granite", "select_", "gmm_", "edl_gmm", "edl_tgmm", "moe_", "ssd_"))
    }  # fmt: skip
    assert {m["name"] for m in loaded["end_to_end"]} == {"tokens_per_s_per_chip", "setup_s"}
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s_per_chip"
            reader = spec.load_reader(m["name"])
            assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.BETTER, reader.MOVES) == (
                m["layer"], m["unit"], m["source"], m["better"], m["moves"],
            )  # fmt: skip
    # appended, nothing put in front; no accepted list gained this cell
    assert [m["name"] for m in bench["per_layer"]][-5:] == [
        "flash_win_ms_per_step", "edl_flash_win_fwd_roofline",
        "edl_flash_win_bwd_dq_roofline", "edl_flash_win_bwd_dkv_roofline",
        "flash_global_ms_per_step",
    ]  # fmt: skip
    assert bench["workloads"][-1]["name"] == CELL and bench["configs"][-1]["name"] == CONFIG
    assert sum(CELL in m.get("workloads", []) for m in bench["per_layer"]) == 5


def test_a_traced_line_holds_each_of_the_cells_metrics():
    run = _traced_run()
    asked = {m["name"] for m in spec.load_cell(CELL)["per_layer"]}
    values = {}
    for name in asked:
        try:
            values[name] = spec.load_reader(name).read(run)
        except Exception:  # a reader of the real trace file: not this test's
            assert name not in NEW_METRICS
    assert not [name for name in NEW_METRICS if values[name] is None]
    assert 0 < values["mfu"] < 100
    for name in NEW_METRICS:
        if name.endswith("roofline"):
            assert 0 < values[name] < 100


def test_the_cell_states_its_cut():
    loaded = spec.load_cell(CELL)
    config, traffic, cell = loaded["config"], loaded["traffic"], loaded["cell"]
    assert cell["chips"] == 1 and cell["traffic"] == "l16384-tok16k-ids4k"
    assert {k: traffic[k] for k in (
        "seq_len", "minibatch_size", "minibatches_per_task", "tasks_per_epoch",
        "token_ids", "unigram", "expect_attention",
    )} == {
        "seq_len": 16384, "minibatch_size": 1, "minibatches_per_task": 16,
        "tasks_per_epoch": 2, "token_ids": 4096, "unigram": "zipf-1",
        "expect_attention": "pallas",
    }  # fmt: skip
    (entry,) = [c for c in _bench()["configs"] if c["name"] == config["name"]]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size",
        "rope_layout", "sliding_window_layout",
    ]  # fmt: skip
    assert entry["source"] == config["source"]
    params = config["model_params"]
    # every published width is as published, and is what the model is given
    assert (params["embed_dim"], params["expert_dim"], params["head_dim"]) == (
        config["hidden_size"], config["moe_ffn_hidden_size"], config["head_dim"],
    ) == (2560, 768, 128)  # fmt: skip
    assert (params["num_heads"], params["num_kv_heads"], params["num_experts_per_tok"]) == (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["moe_num_active_primary_experts"],
    ) == (28, 4, 6)  # fmt: skip
    assert params["attention_window"] == config["sliding_window_size"] == 4096
    assert traffic["seq_len"] == config["max_position_embeddings"] == config["context_length"]
    assert (params["rope_theta"], params["norm_eps"]) == (config["rope_theta"], config["rms_norm_eps"])
    assert params["tie_head"] is config["tie_word_embeddings"] is False
    assert params["routing"] == "softmax" and config["norm_topk_prob"] is True
    assert config["moe_primary_router_apply_softmax"] is True and config["rope_scaling"] is None
    # the layouts, cut to one period, are what the pattern and the
    # positions by kind say: 0 a global layer without positions, 1 a
    # window layer that rotates
    assert config["sliding_window_layout"] == config["rope_layout"] == [0, 1, 1, 1]
    assert params["layer_pattern"] == "awww" and params["num_dense_layers"] == 0
    assert params["rope"] is False and params["window_rope"] is True and params["qk_norm"] is False
    assert len(params["layer_pattern"]) == config["num_hidden_layers"] == 4
    assert config["published"]["num_hidden_layers"] == 52
    # what is cut is named, with the published number beside it
    assert params["experts_held"] == config["moe_num_primary_experts"] == 8
    assert params["num_experts"] == config["published"]["moe_num_primary_experts"] == 64
    assert params["first_expert_held"] == 0
    assert params["vocab_size"] == config["vocab_size"] == config["published"]["vocab_size"] // 8 == 18992
    assert traffic["token_ids"] <= params["vocab_size"]
    # what no config key states, each under ``assumed``
    assert params["router_input"] == "operator_norm" and params["expert_act"] == "relu"
    assert {"router_input", "expert_act", "secondary_experts", "routing", "expert_apply", "remat_layers", "window"} <= set(config["assumed"])
    assert "expert_bias" not in config["assumed"] and "expert_bias_rate" not in params
    # a layer: three kernels, nothing recomputed; every held expert over
    # every token has no grouped product
    assert "remat_layers" not in params and params["expert_apply"] == "masked"
    assert config["tpu_custom_calls"] == {"pallas": 3 * 4}
    assert config["cost"] == "smallthinker_moe_share"
    assert config["reference"] == "smallthinker_moe_reference"


def test_parameters_held_against_the_hand_count():
    params = spec.load_cell(CELL)["config"]["model_params"]
    d, f, v = 2560, 768, 18992
    q, kv = 28 * 128, 4 * 128
    assert (d * q, d * kv) == (9_175_040, 1_310_720)
    attention = 2 * d * q + 2 * d * kv
    expert = 3 * d * f
    layer = attention + d * 64 + 8 * expert + 2 * d
    assert (attention, d * 64, expert, layer) == (20_971_520, 163_840, 5_898_240, 68_326_400)
    assert v * d == 48_619_520
    assert 4 * layer + 2 * v * d + d == PARAMETERS
    cost = spec.load_cost("smallthinker_moe_share")
    assert cost.parameters_held(params) == PARAMETERS
    # what a token meets in a product: the projections and routers, the head's slice
    assert cost.matmul_params(params) == 4 * (attention + d * 64) + v * d == 133_160_960
    # 6 a token, an eighth of them here, in each of 4 layers
    assert cost.expert_params_per_token(params) == 4 * 6 / 8 * expert == 17_694_720


def test_cost_module_against_a_count_by_hand():
    params = spec.load_cell(CELL)["config"]["model_params"]
    cost = spec.load_cost("smallthinker_moe_share")
    # query t reads min(t + 1, 4096) keys in a window layer
    assert cost.pairs_read(LENGTH, WINDOW) == (BAND, CAUSAL)
    assert BAND == 4096 * 4097 // 2 + 12288 * 4096 and CAUSAL == 16384 * 16385 // 2
    assert 0.437 < BAND / CAUSAL < 0.438
    assert cost.pairs_read(2048, WINDOW) == (2048 * 2049 // 2,) * 2  # dead weight at 2,048
    pairs = 3 * BAND + CAUSAL
    attention = 12 * pairs / LENGTH * 28 * 128
    by_hand = 6 * (133_160_960 + 17_694_720) + attention
    assert cost.train_flops_per_token(params, LENGTH) == pytest.approx(by_hand)
    assert round(by_hand / 1e6) == 1720
    # forward, a token: kernels 272M, projections and routers 169M,
    # routed experts 35M, head 97M: the kernels are about half
    assert round(attention / 3e6) == 272 and 0.47 < attention / by_hand < 0.48
    assert round(2 * 4 * 21_135_360 / 1e6) == 169 and round(2 * 17_694_720 / 1e6) == 35
    assert round(2 * 48_619_520 / 1e6) == 97
    with pytest.raises(ValueError, match="knows layers a and w"):
        cost.train_flops_per_token(dict(params, layer_pattern="awws"), LENGTH)
    with pytest.raises(ValueError, match="expert layer behind each"):
        cost.train_flops_per_token(dict(params, num_dense_layers=1), LENGTH)


def test_flash_win_cost_against_hand_arithmetic():
    assert flash_win_cost.pairs_in_band(LENGTH, WINDOW) == BAND
    assert flash_win_cost.pairs_in_band(1024, WINDOW) == 1024 * 1025 // 2
    for kernel, matmuls in (("fwd", 2), ("bwd_dq", 3), ("bwd_dkv", 4)):
        name = "edl_flash_win_" + kernel
        cost = flash_win_cost.windowed_kernel_cost(name, HEADS, LENGTH, 128, WINDOW)
        assert cost[0] == matmuls * 2 * HEADS * BAND * 128
        tensors, rows = {"fwd": (4, 1), "bwd_dq": (5, 2), "bwd_dkv": (6, 2)}[kernel]
        assert cost[1] == HEADS * LENGTH * (tensors * 128 * 2 + rows * 4)
        assert flops.roofline(*cost, "TPU v5 lite")[1] == "compute"
        # a kernel that computes the whole triangle, as fast as the dense
        # one at ITS roofline, is credited with 43.7% of it
        dense = flops.flash_kernel_cost(name.replace("_win", ""), HEADS, LENGTH, 128)
        assert cost[0] / dense[0] == pytest.approx(BAND / (LENGTH**2 / 2))
        assert 0.4375 < cost[0] / dense[0] < 0.4376
    two = flash_win_cost.windowed_kernel_cost("edl_flash_win_fwd", 2 * HEADS, LENGTH, 128, WINDOW)
    one = flash_win_cost.windowed_kernel_cost("edl_flash_win_fwd", HEADS, LENGTH, 128, WINDOW)
    assert two == (2 * one[0], 2 * one[1])


def test_the_new_readers_arithmetic():
    run = _traced_run()
    assert spec.load_reader("flash_win_ms_per_step").read(run) == pytest.approx(
        1e3 * (0.6 + 0.9 + 1.1) / STEPS
    )
    # the plain kernels by their own names: a prefix that ends in the
    # kernel's name catches no windowed call
    assert spec.load_reader("flash_global_ms_per_step").read(run) == pytest.approx(
        1e3 * (0.4 + 0.6 + 0.7) / STEPS
    )
    assert spec.load_reader("flash_ms_per_step").read(run) == pytest.approx(
        1e3 * (0.6 + 0.9 + 1.1 + 0.4 + 0.6 + 0.7) / STEPS
    )  # its prefix takes both, which is why it does not list this cell
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        name = "edl_flash_win_" + kernel
        seconds, calls = WIN_OPS[name + "_bf16_28_16384_128_"]
        least, bound = flops.roofline(
            *flash_win_cost.windowed_kernel_cost(name, HEADS, LENGTH, 128, WINDOW),
            "TPU v5 lite",
        )
        share = spec.load_reader(name + "_roofline").read(run)
        assert share == pytest.approx(100 * STEPS * calls * least / seconds)
        assert 0 < share < 100
        # the accepted readers of the plain kernels read the plain calls only
        plain = spec.load_reader(name.replace("_win", "") + "_roofline").read(run)
        seconds, calls = PLAIN_OPS[name.replace("_win", "") + "_bf16_28_16384_128_"]
        least, _ = flops.roofline(
            *flops.flash_kernel_cost(name.replace("_win", ""), HEADS, LENGTH, 128),
            "TPU v5 lite",
        )
        assert plain == pytest.approx(100 * STEPS * calls * least / seconds)
    # the window is the program's own fact: half of it, half the band
    narrower = dict(run, events=[dict(e) for e in run["events"]])
    narrower["events"][1]["attention_window"] = 2048
    assert spec.load_reader("edl_flash_win_fwd_roofline").read(narrower) < 0.6 * spec.load_reader(
        "edl_flash_win_fwd_roofline"
    ).read(run)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_reader_is_silent_on_a_program_without_a_window(name):
    """The parent has no such kernel or fact: the reader returns nothing
    and does not raise, traced or not."""
    run = _traced_run(windowed=False)
    assert spec.load_reader(name).read(dict(run, trace=None)) is None
    assert spec.load_reader(name).read(run) is None
    # and of this program's own untraced run
    assert spec.load_reader(name).read(dict(_traced_run(), trace=None)) is None


TOY = dict(
    name="smallthinker-toy",
    model_def="transformer_lm.hybrid_moe_lm.custom_model",
    reference="smallthinker_moe_reference",
    model_params=dict(
        vocab_size=256, layer_pattern="awww", num_dense_layers=0, embed_dim=64,
        num_heads=4, num_kv_heads=2, head_dim=16, expert_dim=32, num_experts=16,
        experts_held=4, first_expert_held=0, num_experts_per_tok=3,
        routing="softmax", router_input="operator_norm", expert_act="relu",
        expert_apply="masked", attention_window=24, rope=False, window_rope=True,
        qk_norm=False, tie_head=False, rope_theta=1.5e6,
        norm_eps=1e-6, dtype="bfloat16",
    ),
)  # fmt: skip


def test_f32_program_equals_the_reference_through_the_comparison_child():
    config = dict(TOY, model_params=dict(TOY["model_params"], dtype="float32"))
    got = compare.compare(config, 64, seed=3)
    assert got["program_leaves"] == 3 + 4 * 9
    assert got["loss_rel_error"] < 1e-6
    assert max(got["grad_rel_l2_error"].values()) < 1e-4


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 77])
def test_the_control_is_refused_at_the_toy_size(seed):
    """The next precision down from the configuration's bf16, in the
    program's place: the comparison has to say no, by a gradient leaf;
    the program itself, same seed, is inside every limit."""
    got = compare.compare(TOY, 64, seed=seed, control="float8_e4m3fn")
    assert got["control"] == "float8_e4m3fn" and not got["agree"], got
    worst = max(got["grad_rel_l2_error"].values())
    assert worst > got["grad_rel_l2_tolerance"]
    sound = compare.compare(TOY, 64, seed=seed)
    assert sound["agree"], sound
    assert worst > 2 * max(sound["grad_rel_l2_error"].values())
