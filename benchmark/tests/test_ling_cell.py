"""The cell ``ling3flash-ep64-l4096``: the configuration file's parameter
count by hand and against ``jax.eval_shape`` of the model's init, the
cost module and ``flash_mla_cost`` against hand arithmetic, a traced
line holding the cell's metrics (read from a fixture of what this
cell's step holds: the three flash kernels at unequal head sizes in one
layer, the grouped products of six expert layers), the new readers'
silence on a program without these layers (the parent commit, on which
the driver runs them too), and the float8 control refused at a toy
size."""

import json
import os
import sys

import pytest

import compare
import flash_mla_cost
import flops
import spec
from conftest import BENCHMARK

sys.path.insert(0, os.path.join(BENCHMARK, "layer_metrics"))
sys.path.insert(0, os.path.join(BENCHMARK, "tools"))

CELL = "ling3flash-ep64-l4096"
CONFIG = "ling-3.0-flash-vl-ep64"
NEW_METRICS = [
    "kda_ms_per_step", "flash_mla_ms_per_step", "edl_flash_mla_fwd_roofline",
    "edl_flash_mla_bwd_dq_roofline", "edl_flash_mla_bwd_dkv_roofline",
    "gmm_ling_ms_per_step", "moe_ling_load_max_over_mean",
]  # fmt: skip
# every one but the loops' reader, which opens the run's own trace file
FROM_THE_REDUCED_TRACE = [m for m in NEW_METRICS if m != "kda_ms_per_step"]
STEPS, BATCH, HEADS, LENGTH = 16, 2, 32, 4096
D_QK, D_V = 192, 128
PAIRS = LENGTH * (LENGTH + 1) // 2
PARAMETERS = 821_951_424
# seconds in the slice and calls a step. The MLA layer's kernels: the
# forward twice (the layer is recomputed), an op's name carrying its
# first result's shape (o at 128; dq and dk at 192)
MLA_OPS = {
    "edl_flash_mla_fwd_bf16_64_4096_128_": (0.10, 2),
    "edl_flash_mla_bwd_dq_bf16_64_4096_192_": (0.09, 1),
    "edl_flash_mla_bwd_dkv_bf16_64_4096_192_": (0.12, 1),
}
# six expert layers: forward and recomputed forward of both products,
# dlhs of both, tgmm of both; a buffer of 8,192 x 8 rows
GMM_OPS = {
    "edl_gmm_k2560_fwd_bf16_65536_1536_": (0.06, 12),
    "edl_gmm_k768_fwd_bf16_65536_2560_": (0.05, 12),
    "edl_gmm_k1536_dlhs_bf16_65536_2560_": (0.04, 6),
    "edl_gmm_k2560_dlhs_bf16_65536_768_": (0.03, 6),
    "edl_tgmm_bf16_8_2560_1536_": (0.07, 6),
    "edl_tgmm_bf16_8_768_2560_": (0.05, 6),
}
ROWS_A_LAYER = 8 * 128  # 8 held experts x 8,192 * 8 / 512 rows


def _bench():
    with open(os.path.join(os.path.dirname(BENCHMARK), "BENCHMARK.json")) as f:
        return json.load(f)


def _traced_run(ling=True):
    """What run.py hands the readers after a traced run of the cell;
    ``ling`` False: of a program that has no such layer (the parent)."""
    loaded = spec.load_cell(CELL)
    op_s = {"fusion_bf16_8_16_": 1.0}
    op_calls = {"fusion_bf16_8_16_": STEPS}
    windows = [
        {"kind": "train_window", "id": i, "seconds": s, "steps": 4, "ts": 100.0 + i}
        for i, s in ((1, 20.0), (2, 1.6), (3, 1.6), (4, 1.6), (5, 1.6))
    ]
    built = {"kind": "step_built", "ts": 60.0}
    if ling:
        for name, (seconds, calls) in {**MLA_OPS, **GMM_OPS}.items():
            op_s[name], op_calls[name] = seconds, STEPS * calls
        for w in windows:
            w.update(
                moe_rows_here=4 * 6 * ROWS_A_LAYER,
                moe_rows_routed=4 * 6 * 65536,
                moe_rows_max_expert=4 * 200,
                moe_rows_mean_expert=4 * 128.0,
                expert_bias_abs_max=0.4,
            )
        built.update(
            expert_layers=6, experts_held=8, experts_routed=512,
            kda_layers=6, kda_heads=32, kda_head_dim=128, kda_chunk=64,
            mla_layers=1, mla_qk_dim=D_QK, mla_v_dim=D_V,
            shared_expert_dim=768, expert_groups=8, expert_groups_per_tok=4,
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


def test_the_cells_list_of_metrics():
    loaded = spec.load_cell(CELL)
    asked = {m["name"] for m in loaded["per_layer"]}
    bench = _bench()
    everywhere = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert asked == everywhere | set(NEW_METRICS)
    assert {m["name"] for m in loaded["end_to_end"]} == {"tokens_per_s_per_chip", "setup_s"}
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s_per_chip"
            reader = spec.load_reader(m["name"])
            assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.BETTER, reader.MOVES) == (
                m["layer"], m["unit"], m["source"], m["better"], m["moves"],
            )  # fmt: skip
        else:
            # no accepted list gained this cell
            assert CELL not in m.get("workloads", [])
    # the grouped products' rooflines take a call's rows from the
    # windows' mean, which a bursty routing's traced steps do not have
    # (PERF.md, Open questions): no share of theirs lists this cell
    assert not [n for n in asked if "gmm" in n and n.endswith("_roofline")]
    assert CONFIG in [c["name"] for c in bench["configs"]]
    assert CELL in [w["name"] for w in bench["workloads"]]


def test_the_cell_states_its_cut():
    loaded = spec.load_cell(CELL)
    config, traffic, cell = loaded["config"], loaded["traffic"], loaded["cell"]
    assert cell["chips"] == 1 and cell["traffic"] == "l4096-tok8k-ids4k"
    assert {k: traffic[k] for k in (
        "seq_len", "minibatch_size", "minibatches_per_task", "tasks_per_epoch",
        "token_ids", "unigram", "expect_attention",
    )} == {
        "seq_len": 4096, "minibatch_size": 2, "minibatches_per_task": 16,
        "tasks_per_epoch": 2, "token_ids": 4096, "unigram": "zipf-1",
        "expect_attention": "pallas",
    }  # fmt: skip
    (entry,) = [c for c in _bench()["configs"] if c["name"] == config["name"]]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "vocab_size", "expert_swiglu_limit_list",
        "share_expert_swiglu_limit_list",
    ]  # fmt: skip
    assert entry["source"] == config["source"]
    params = config["model_params"]
    # every published width is as published, and is what the model is given
    assert (params["embed_dim"], params["mlp_dim"], params["expert_dim"], params["shared_expert_dim"]) == (
        config["hidden_size"], config["intermediate_size"],
        config["moe_intermediate_size"], config["moe_shared_expert_intermediate_size"],
    ) == (2560, 6144, 768, 768)  # fmt: skip
    assert (params["kda_heads"], params["kda_head_dim"], params["kda_conv_kernel"]) == (
        config["num_attention_heads"], config["head_dim"], config["short_conv_kernel_size"],
    ) == (32, 128, 4)  # fmt: skip
    assert params["kda_gate_lower_bound"] == config["kda_lower_bound"] == -5
    assert config["kda_safe_gate"] is True and config["no_kda_lora"] is True
    assert (
        params["mla_kv_rank"], params["mla_nope_dim"], params["mla_rope_dim"],
        params["mla_v_dim"], params["num_heads"],
    ) == (
        config["kv_lora_rank"], config["qk_nope_head_dim"], config["qk_rope_head_dim"],
        config["v_head_dim"], config["num_attention_heads"],
    ) == (512, 128, 64, 128, 32)  # fmt: skip
    assert config["q_lora_rank"] is None and config["rotary_dim"] == 64
    assert (params["num_experts_per_tok"], params["num_expert_groups"], params["expert_groups_per_tok"]) == (
        config["num_experts_per_tok"], config["n_group"], config["topk_group"],
    ) == (8, 8, 4)  # fmt: skip
    assert params["routed_scaling_factor"] == config["routed_scaling_factor"] == 2.5
    assert config["score_function"] == "sigmoid" and params["routing"] == "sigmoid_bias"
    assert config["moe_router_enable_expert_bias"] is True and config["norm_topk_prob"] is True
    assert (params["rope_theta"], params["norm_eps"]) == (config["rope_theta"], config["rms_norm_eps"])
    assert params["tie_head"] is False
    # one dense KDA layer, then one whole group of six with the MLA layer last
    assert params["layer_pattern"] == "k" + "kkkkkl" and params["num_dense_layers"] == 1
    assert config["layer_group_size"] == 6 == len(params["layer_pattern"]) - 1
    assert len(params["layer_pattern"]) == config["num_hidden_layers"] == 7
    assert config["first_k_dense_replace"] == 1
    # what is cut is named, with the published number beside it
    published = config["published"]
    assert (published["num_hidden_layers"], published["first_k_dense_replace"]) == (42, 2)
    assert params["experts_held"] == config["num_experts"] == 8
    assert params["num_experts"] == published["num_experts"] == 512
    assert params["first_expert_held"] == 0
    assert params["vocab_size"] == config["vocab_size"] == published["vocab_size"] // 8 == 19648
    assert traffic["token_ids"] <= params["vocab_size"]
    # the clamps are 0 in every layer held, and the lists are cut to them
    assert config["expert_swiglu_limit_list"] == config["share_expert_swiglu_limit_list"] == [0] * 7
    assert "64-way" in config["deployment"] and "8 of those chips" in config["deployment"]
    # what no config key states, each under ``assumed``
    assert {
        "layer_group", "kda_gate", "kda_decay_projection", "output_gate",
        "kda_heads", "qk_norm", "mla", "rotary", "router", "expert_bias",
        "expert_apply", "swiglu_limits", "loss", "optimizer", "dtype",
        "remat_layers", "language_model_only",
    } <= set(config["assumed"])  # fmt: skip
    assert params["expert_apply"] == "grouped" and params["expert_bias_rate"] == 0.1
    assert params["remat_layers"] is True and params["dtype"] == "bfloat16"
    # six expert layers: two grouped products forward, again recomputed,
    # two dlhs, two tgmm; the MLA layer's three kernels and its forward
    # once more
    assert config["tpu_custom_calls"] == {"pallas": 6 * 8 + 4}
    assert config["cost"] == "ling_linear_moe_share"
    assert config["reference"] == "ling_linear_moe_reference"
    assert config["held_here"]["parameters"] == PARAMETERS


def test_parameters_held_against_the_hand_count_and_the_models_init():
    params = spec.load_cell(CELL)["config"]["model_params"]
    d, inner, heads, v = 2560, 32 * 128, 32, 19648
    assert d * inner == 10_485_760 and d * heads == 81_920
    kda = 5 * d * inner + 2 * d * heads + 3 * inner * 4 + heads + inner + 128
    mla = d * heads * 192 + d * 576 + 512 + 512 * heads * 256 + inner * d
    expert = 3 * d * 768
    expert_ff = d * 512 + expert + 8 * expert
    dense_ff = 3 * d * 6144
    assert (kda, mla, expert_ff, dense_ff) == (52_646_048, 31_883_776, 54_394_880, 47_185_920)
    dense_layer = kda + dense_ff + 2 * d
    kda_layer, mla_layer = kda + expert_ff + 2 * d, mla + expert_ff + 2 * d
    assert (dense_layer, 5 * kda_layer, mla_layer) == (99_837_088, 535_230_240, 86_283_776)
    assert 2 * v * d + d == 100_600_320
    assert dense_layer + 5 * kda_layer + mla_layer + 2 * v * d + d == PARAMETERS
    cost = spec.load_cost("ling_linear_moe_share")
    assert cost.parameters_held(params) == PARAMETERS
    # and what the program's own init makes, leaf by leaf
    import jax
    import jax.numpy as jnp

    model, _ = compare._load_program_model(spec.load_cell(CELL)["config"])
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), {"tokens": jnp.zeros((1, 64), jnp.int32)})
    )
    counted = sum(leaf.size for leaf in jax.tree_util.tree_leaves(shapes["params"]))
    assert counted == PARAMETERS
    assert all(leaf.dtype == jnp.float32 for leaf in jax.tree_util.tree_leaves(shapes["params"]))
    # the state beside them: a bias and a counter an expert and layer
    assert sorted(shapes["moe_state"]["layer_6_moe"]) == ["assignments", "expert_bias"]
    assert shapes["moe_state"]["layer_6_moe"]["expert_bias"].shape == (512,)


def test_cost_module_against_a_count_by_hand():
    params = spec.load_cell(CELL)["config"]["model_params"]
    cost = spec.load_cost("ling_linear_moe_share")
    d, inner = 2560, 4096
    kda = 5 * d * inner + 2 * d * 32
    mla = d * 32 * 192 + d * 576 + 512 * 32 * 256 + inner * d
    expert = 3 * d * 768
    matmul = 6 * kda + mla + 3 * d * 6144 + 6 * (d * 512 + expert) + 19648 * d
    assert cost.matmul_params(params) == matmul == 488_177_664
    # 8 a token, 8 of 512 of them here, in each of 6 expert layers
    assert cost.expert_params_per_token(params) == 6 * 8 * 8 / 512 * expert == 4_423_680
    # the recurrence as a recurrence: 7 d_k d_v a head and position
    assert cost.recurrence_forward_flops_per_token(params) == 7 * 128 * 128 * 32 == 3_670_016
    attention = 6 * (LENGTH + 1) / 2 * 32 * (192 + 128)
    by_hand = 6 * (matmul + 4_423_680) + attention + 3 * 6 * 3_670_016
    assert cost.train_flops_per_token(params, LENGTH) == pytest.approx(by_hand)
    assert round(by_hand / 1e6) == 3148
    # of it: the routed experts here 27M, the shared experts 212M, the
    # recurrence 66M, the MLA kernels 126M, the head 302M
    assert round(6 * 4_423_680 / 1e6) == 27 and round(6 * 6 * expert / 1e6) == 212
    assert round(attention / 1e6) == 126 and round(6 * 19648 * d / 1e6) == 302
    with pytest.raises(ValueError, match="knows layers k and l"):
        cost.train_flops_per_token(dict(params, layer_pattern="kkal"), LENGTH)


def test_flash_mla_cost_against_hand_arithmetic():
    bh = BATCH * HEADS
    assert flash_mla_cost.causal_pairs(LENGTH) == PAIRS == 8_390_656
    by_hand = {
        # q k^T at 192, p v at 128
        "fwd": (D_QK + D_V, 2 * D_QK + 2 * D_V, 1),
        # q k^T and dS k at 192, dO v^T at 128
        "bwd_dq": (2 * D_QK + D_V, 3 * D_QK + 2 * D_V, 2),
        # q k^T and dS^T q at 192, dO v^T and p^T dO at 128
        "bwd_dkv": (2 * D_QK + 2 * D_V, 3 * D_QK + 3 * D_V, 2),
    }
    for kernel, (width, columns, rows) in by_hand.items():
        name = "edl_flash_mla_" + kernel
        cost = flash_mla_cost.unequal_kernel_cost(name, bh, LENGTH, D_QK, D_V)
        assert cost[0] == 2 * bh * PAIRS * width
        assert cost[1] == bh * LENGTH * (columns * 2 + rows * 4)
        assert flops.roofline(*cost, "TPU v5 lite")[1] == "compute"
        # at equal sizes it is the dense kernels' count over the exact
        # causal pairs (flops.py counts L^2 / 2)
        equal = flash_mla_cost.unequal_kernel_cost(name, bh, LENGTH, 128, 128)
        dense = flops.flash_kernel_cost(name.replace("_mla", ""), bh, LENGTH, 128)
        assert equal[0] == pytest.approx(dense[0] * (LENGTH + 1) / LENGTH)
        assert equal[1] == dense[1]


def test_the_new_readers_arithmetic():
    run = _traced_run()
    assert spec.load_reader("flash_mla_ms_per_step").read(run) == pytest.approx(
        1e3 * (0.10 + 0.09 + 0.12) / STEPS
    )
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        name = "edl_flash_mla_" + kernel
        ((_, (seconds, calls)),) = [kv for kv in MLA_OPS.items() if kv[0].startswith(name + "_")]
        least, _ = flops.roofline(
            *flash_mla_cost.unequal_kernel_cost(name, BATCH * HEADS, LENGTH, D_QK, D_V),
            "TPU v5 lite",
        )
        share = spec.load_reader(name + "_roofline").read(run)
        assert share == pytest.approx(100 * STEPS * calls * least / seconds)
        assert 0 < share < 100
    # both head sizes are the program's own facts, not the op's name
    wider = dict(run, events=[dict(e) for e in run["events"]])
    wider["events"][1]["mla_v_dim"] = 256
    assert spec.load_reader("edl_flash_mla_fwd_roofline").read(wider) > spec.load_reader(
        "edl_flash_mla_fwd_roofline"
    ).read(run)
    # the grouped products: the accepted readers' arithmetic, at the
    # rows the program counted (1,024 a layer of the 65,536 in a buffer)
    assert spec.load_reader("gmm_ling_ms_per_step").read(run) == pytest.approx(
        1e3 * sum(s for s, _ in GMM_OPS.values()) / STEPS
    ) == spec.load_reader("gmm_ms_per_step").read(run)
    assert spec.load_reader("moe_ling_load_max_over_mean").read(run) == pytest.approx(200 / 128)


def test_a_traced_line_holds_each_of_the_cells_metrics():
    run = _traced_run()
    values = {}
    for m in spec.load_cell(CELL)["per_layer"]:
        try:
            values[m["name"]] = spec.load_reader(m["name"]).read(run)
        except Exception:  # a reader of the real trace file: not this test's
            assert m["name"] not in FROM_THE_REDUCED_TRACE
    assert not [name for name in FROM_THE_REDUCED_TRACE if values[name] is None]
    assert 0 < values["mfu"] < 100


def test_the_loops_reader_knows_the_state_by_its_shape():
    import _kda

    built = {"kda_heads": 32, "kda_head_dim": 128}
    loop = _kda.state_loop(built, 2)
    forward = "%while.12 = (s32[], f32[2,32,128,128]{3,2,1,0}, bf16[64,2,32,64,128]{4,3,2,1,0}) while(%tuple.3)"
    assert loop.match(forward)
    # granite's scan carries (batch, 64 heads, 64, 128); a dispatch loop
    # a buffer of rows
    assert not loop.match("%while.3 = (s32[], f32[2,64,64,128]{3,2,1,0}) while(%t)")
    assert not loop.match("%while.7 = (s32[], bf16[65536,2560]{1,0}) while(%t)")
    assert not loop.match("%fusion.1 = f32[2,32,128,128]{3,2,1,0} fusion(%p)")
    # a program that says it scans, traced, with no trace file to open: an error
    run = _traced_run()
    with pytest.raises(RuntimeError, match="lowered another way"):
        _kda.state_loops_s(run, xplane=None)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_reader_is_silent_on_a_program_without_these_layers(name):
    """The parent has no such kernel or fact: the reader returns nothing
    and does not raise, traced or not."""
    run = _traced_run(ling=False)
    assert spec.load_reader(name).read(dict(run, trace=None)) is None
    assert spec.load_reader(name).read(run) is None
    # and of this program's own untraced run
    if name != "moe_ling_load_max_over_mean":  # a counter: read untraced too
        assert spec.load_reader(name).read(dict(_traced_run(), trace=None)) is None


TOY = dict(
    name="ling-toy",
    model_def="transformer_lm.hybrid_moe_lm.custom_model",
    reference="ling_linear_moe_reference",
    model_params=dict(
        vocab_size=256, layer_pattern="kkkl", num_dense_layers=1, embed_dim=64,
        num_heads=4, mlp_dim=96, expert_dim=32, num_experts=32, experts_held=4,
        first_expert_held=0, num_experts_per_tok=4, num_expert_groups=4,
        expert_groups_per_tok=2, shared_expert_dim=24, routing="sigmoid_bias",
        expert_apply="grouped", routed_scaling_factor=2.5, kda_heads=4,
        kda_head_dim=16, kda_conv_kernel=4, kda_gate_lower_bound=-5.0,
        kda_chunk=64, mla_kv_rank=24, mla_nope_dim=16, mla_rope_dim=8,
        mla_v_dim=16, tie_head=False, rope_theta=6e6, norm_eps=1e-6,
        remat_layers=True, dtype="bfloat16",
    ),
)  # fmt: skip


def test_f32_program_equals_the_reference_through_the_comparison_child():
    config = dict(TOY, model_params=dict(TOY["model_params"], dtype="float32"))
    got = compare.compare(config, 128, seed=3)
    assert got["program_leaves"] == 3 + 3 * 15 + 7 + 3 + 3 * 5
    assert got["loss_rel_error"] < 1e-6
    assert max(got["grad_rel_l2_error"].values()) < 1e-3


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 77])
def test_the_control_is_refused_at_the_toy_size(seed):
    """The next precision down from the configuration's bf16, in the
    program's place: the comparison has to say no, by a gradient leaf;
    the program itself, same seed, is inside every limit."""
    got = compare.compare(TOY, 128, seed=seed, control="float8_e4m3fn")
    assert got["control"] == "float8_e4m3fn" and not got["agree"], got
    worst = max(got["grad_rel_l2_error"].values())
    assert worst > got["grad_rel_l2_tolerance"]
    # the program itself, same seed: its loss inside the limit and its
    # worst leaf well under the control's. (Not under the cell's own
    # limit: a toy of 64 channels and 32 experts flips far more of its
    # routing on a rounding than 2,560 channels and 512 experts do, and
    # its worst leaf reads 0.4-0.9 where the cell's reads 0.60-0.65.)
    sound = compare.compare(TOY, 128, seed=seed)
    assert sound["loss_rel_error"] <= sound["loss_rel_tolerance"], sound
    assert worst > 1.5 * max(sound["grad_rel_l2_error"].values())


def test_pinned_selections_take_the_routers_error_away():
    """What the limit's readings are made of (PERF.md section 2): with
    the program's selections pinned to the reference's, a router's and
    a routed expert's gradient agree several times better; the toy
    flips some percent of its assignments in every expert layer."""
    import comparison_looks

    got = comparison_looks.pinned(TOY, 128, seed=3)
    assert len(got["flipped_share_by_layer"]) == 3
    assert all(0 < share < 0.2 for share in got["flipped_share_by_layer"])
    for group in ("routers", "routed_experts"):
        loose = got["as_compared"]["by_group"][group][1]
        tight = got["selection_pinned"]["by_group"][group][1]
        assert tight < loose / 2, (group, loose, tight)


def test_rows_left_out_of_the_loss_are_refused_at_the_toy_size():
    import comparison_looks

    got = comparison_looks.rows_left_out(TOY, 128, seed=3)
    assert got["look"] == "rows_left_out" and not got["agree"]
    # the second sequence's embedding rows get no gradient at all
    assert got["by_group"]["embed"][1] > 0.85 and got["leaves_over_the_limit"] > got["leaves"] // 2
