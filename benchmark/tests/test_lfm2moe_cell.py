"""The cell ``lfm2moe-ep8-l2048``: a traced line holds exactly the
cell's metrics, read from what this cell's step holds (the flash
kernels once, the grouped-matmul kernels of four expert layers, the
routing counters on its events); the grouped-matmul readers' arithmetic;
and their silence on a program that has no such kernel or counter (the
parent commit, on which the driver runs them too)."""

import json
import os

import pytest

import flops
import gmm_cost
import spec
from conftest import BENCHMARK

CELL = "lfm2moe-ep8-l2048"
NEW_METRICS = {
    "gmm_ms_per_step", "edl_gmm_roofline", "edl_tgmm_roofline",
    "moe_load_max_over_mean",
}  # fmt: skip
STEPS, LAYERS, HELD = 16, 4, 8
BUFFER, ROWS_HERE = 32768, 4096  # T * k, and an eighth of it
# what one expert layer's step holds: op name -> calls a layer a step
GMM_OPS = {
    "edl_gmm_k2048_fwd_bf16_32768_3072_": 1,
    "edl_gmm_k1536_fwd_bf16_32768_2048_": 1,
    "edl_gmm_k2048_dlhs_bf16_32768_1536_": 1,
    "edl_gmm_k3072_dlhs_bf16_32768_2048_": 1,
    "edl_tgmm_bf16_8_2048_3072_": 1,
    "edl_tgmm_bf16_8_1536_2048_": 1,
}
FLASH_OPS = [
    "edl_flash_%s_bf16_128_2048_64_" % k for k in ("fwd", "bwd_dq", "bwd_dkv")
]


def _traced_run(with_experts=True):
    """What run.py hands the readers after a traced run of the cell."""
    loaded = spec.load_cell(CELL)
    op_s = {"fusion_bf16_8_16_": 1.0}
    op_calls = {"fusion_bf16_8_16_": STEPS}
    for name in FLASH_OPS:
        op_s[name], op_calls[name] = 0.05, STEPS
    window = {
        "kind": "train_window", "id": 3, "seconds": 1.2, "steps": 8, "ts": 103.0,
    }  # fmt: skip
    built = {"kind": "step_built", "ts": 60.0}
    if with_experts:
        for name, calls in GMM_OPS.items():
            op_s[name], op_calls[name] = 0.02, STEPS * LAYERS * calls
        window.update(
            moe_rows_here=8 * LAYERS * ROWS_HERE,
            moe_rows_routed=8 * LAYERS * BUFFER,
            moe_rows_max_expert=8 * 640,
            moe_rows_mean_expert=8 * 512.0,
            expert_bias_abs_max=0.016,
        )
        built.update(expert_layers=LAYERS, experts_held=HELD, experts_routed=64)
    return dict(
        loaded,
        events=[
            {"kind": "resize_end", "world_s": 1, "init_s": 2, "place_s": 3, "compile_s": 0, "ts": 50.0},
            built,
            dict(window, id=1, seconds=20.0, ts=100.0),
            {"kind": "task_done", "dispatch_to_report_s": 2.5, "ts": 102.0},
            window,
        ],
        windows=[window],
        window_start=101.5,
        device_kind="TPU v5 lite",
        tokens_per_s_per_chip=6e4,
        setup_s=90.0,
        bench_prep_s=0.5,
        cache_files_added=0,
        trace={
            "steps": STEPS,
            "busy_s": sum(op_s.values()),
            "window_s": 2.4,
            "op_s": op_s,
            "op_calls": op_calls,
            "collective_s": 0.0,
            "collective_exposed_s": 0.0,
        },
    )  # fmt: skip


def test_a_traced_line_holds_exactly_the_cells_metrics():
    loaded = spec.load_cell(CELL)
    run = _traced_run()
    asked = {m["name"] for m in loaded["per_layer"]}
    assert NEW_METRICS <= asked
    assert not {m for m in asked if m.startswith("edl_flash") or m.startswith("flash_ms")}
    values = {name: spec.load_reader(name).read(run) for name in asked}
    assert not [name for name, value in values.items() if value is None]
    # every metric that lists no cells is reported here too
    with open(os.path.join(os.path.dirname(BENCHMARK), "BENCHMARK.json")) as f:
        everywhere = {m["name"] for m in json.load(f)["per_layer"] if "workloads" not in m}
    assert asked == everywhere | NEW_METRICS
    assert 0 < values["mfu"] < 100


def test_the_cell_states_its_cut():
    loaded = spec.load_cell(CELL)
    config, traffic = loaded["config"], loaded["traffic"]
    assert loaded["cell"]["chips"] == 1
    assert traffic["seq_len"] * traffic["minibatch_size"] == 8192
    assert config["tpu_custom_calls"] == {"pallas": 3 + 6 * LAYERS}
    with open(os.path.join(os.path.dirname(BENCHMARK), "BENCHMARK.json")) as f:
        (entry,) = [c for c in json.load(f)["configs"] if c["name"] == config["name"]]
    assert entry["reduced"] == config["reduced"]
    # every published width is as published; what is cut is named
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "conv_L_cache",
                "num_experts_per_tok"):  # fmt: skip
        assert key not in config["reduced"]
    params = config["model_params"]
    assert (params["embed_dim"], params["mlp_dim"], params["expert_dim"]) == (
        config["hidden_size"], config["intermediate_size"], config["moe_intermediate_size"],
    )  # fmt: skip
    assert (params["num_heads"], params["num_kv_heads"]) == (
        config["num_attention_heads"], config["num_key_value_heads"],
    )  # fmt: skip
    assert params["experts_held"] == config["num_experts"] == 8
    assert params["num_experts"] == config["published"]["num_experts"] == 64
    assert len(params["layer_pattern"]) == config["num_hidden_layers"] == len(config["layer_types"])
    assert [{"c": "conv", "a": "full_attention"}[k] for k in params["layer_pattern"]] == config["layer_types"]
    assert params["vocab_size"] == config["vocab_size"] == config["published"]["vocab_size"] // 8


def test_gmm_readers_arithmetic():
    run = _traced_run()
    assert spec.load_reader("gmm_ms_per_step").read(run) == pytest.approx(
        1e3 * 6 * 0.02 / STEPS
    )
    assert spec.load_reader("moe_load_max_over_mean").read(run) == pytest.approx(1.25)
    # least time from the rows counted as routed here, not the buffer
    calls = STEPS * LAYERS
    least = sum(
        calls * flops.roofline(*gmm_cost.grouped_product_cost(ROWS_HERE, k, n, HELD), "TPU v5 lite")[0]
        for k, n in ((2048, 3072), (1536, 2048), (2048, 1536), (3072, 2048))
    )
    assert spec.load_reader("edl_gmm_roofline").read(run) == pytest.approx(
        100 * least / (4 * 0.02)
    )
    least = sum(
        calls * flops.roofline(*gmm_cost.grouped_product_cost(ROWS_HERE, k, n, HELD), "TPU v5 lite")[0]
        for k, n in ((2048, 3072), (1536, 2048))
    )
    share = spec.load_reader("edl_tgmm_roofline").read(run)
    assert share == pytest.approx(100 * least / (2 * 0.02)) and 0 < share < 100
    # at these shapes the products are compute-bound: each held
    # expert's matrix is read once for 512 rows
    cost = gmm_cost.grouped_product_cost(ROWS_HERE, 2048, 3072, HELD)
    assert cost == (2 * 4096 * 2048 * 3072, 2 * (4096 * 2048 + 8 * 2048 * 3072 + 4096 * 3072))
    assert flops.roofline(*cost, "TPU v5 lite")[1] == "compute"


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_reader_is_silent_on_a_program_without_experts(name):
    """The parent has no such kernel and no such counter: the reader
    returns nothing and does not raise, traced or not."""
    run = _traced_run(with_experts=False)
    assert spec.load_reader(name).read(run) is None
    assert spec.load_reader(name).read(dict(run, trace=None)) is None
