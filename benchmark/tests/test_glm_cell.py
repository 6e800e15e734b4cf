"""The cell ``glm47flash-ep8-l8192``: the configuration file's parameter
count by hand and against ``jax.eval_shape`` of the model's init, the
cost module against a count by hand at L = 8,192, a traced line holding
exactly the cell's metrics (read from a fixture of what this cell's
step holds: the three flash kernels at head size 256 in six layers, the
grouped products of five expert layers, a map of the step's ops under
``edl/mtp``), the new readers' silence on a program without a
prediction module (the parent commit, on which the driver runs them
too), the kernel calls of the built step against the configuration's
number, and the float8 control refused at a toy size."""

import json
import os
import sys

import pytest

import compare
import flops
import spec
from conftest import BENCHMARK

sys.path.insert(0, os.path.join(BENCHMARK, "layer_metrics"))

CELL = "glm47flash-ep8-l8192"
CONFIG = "glm-4.7-flash-ep8"
NEW_METRICS = [
    "flash_glm_ms_per_step", "edl_flash_d256_fwd_roofline",
    "edl_flash_d256_bwd_dq_roofline", "edl_flash_d256_bwd_dkv_roofline",
    "mtp_ms_per_step", "gmm_glm_ms_per_step", "moe_glm_load_max_over_mean",
]  # fmt: skip
# every one but the module's reader, which opens the run's own trace file
FROM_THE_REDUCED_TRACE = [m for m in NEW_METRICS if m != "mtp_ms_per_step"]
STEPS, BATCH, HEADS, LENGTH, WIDTH = 16, 1, 20, 8192, 256
PARAMETERS = 706_518_528
# seconds in the slice and calls a step: six latent attentions (the
# module's among them), the forward twice (a layer is recomputed)
FLASH_OPS = {
    "edl_flash_fwd_bf16_20_8192_256_": (3.2, 12),
    "edl_flash_bwd_dq_bf16_20_8192_256_": (2.1, 6),
    "edl_flash_bwd_dkv_bf16_20_8192_256_": (2.6, 6),
}
# five expert layers: forward and recomputed forward of both products,
# dlhs of both, tgmm of both; a buffer of 8,192 x 4 rows
GMM_OPS = {
    "edl_gmm_k2048_fwd_bf16_32768_3072_": (0.06, 10),
    "edl_gmm_k1536_fwd_bf16_32768_2048_": (0.05, 10),
    "edl_gmm_k3072_dlhs_bf16_32768_2048_": (0.04, 5),
    "edl_gmm_k2048_dlhs_bf16_32768_1536_": (0.03, 5),
    "edl_tgmm_bf16_8_2048_3072_": (0.07, 5),
    "edl_tgmm_bf16_8_1536_2048_": (0.05, 5),
}
ROWS_A_LAYER = 8 * 512  # 8 held experts x 8,192 * 4 / 64 rows


def _bench():
    with open(os.path.join(os.path.dirname(BENCHMARK), "BENCHMARK.json")) as f:
        return json.load(f)


def _traced_run(glm=True):
    """What run.py hands the readers after a traced run of the cell;
    ``glm`` False: of a program that has no such module (the parent)."""
    loaded = spec.load_cell(CELL)
    op_s = {"fusion_bf16_8_16_": 1.0}
    op_calls = {"fusion_bf16_8_16_": STEPS}
    windows = [
        {"kind": "train_window", "id": i, "seconds": s, "steps": 4, "ts": 100.0 + i}
        for i, s in ((1, 20.0), (2, 2.4), (3, 2.4), (4, 2.4), (5, 2.4))
    ]
    built = {"kind": "step_built", "ts": 60.0}
    if glm:
        for name, (seconds, calls) in {**FLASH_OPS, **GMM_OPS}.items():
            op_s[name], op_calls[name] = seconds, STEPS * calls
        for w in windows:
            w.update(
                moe_rows_here=4 * 5 * ROWS_A_LAYER,
                moe_rows_routed=4 * 5 * 32768,
                moe_rows_max_expert=4 * 640,
                moe_rows_mean_expert=4 * 512.0,
                expert_bias_abs_max=0.4,
                lm_loss=6.5,
                mtp_loss=2.0,
            )
        built.update(
            expert_layers=5, experts_held=8, experts_routed=64,
            mla_layers=6, mla_qk_dim=WIDTH, mla_v_dim=WIDTH, mla_q_rank=768,
            mtp_layers=1, mtp_loss_weight=0.3, shared_expert_dim=1536,
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
        tokens_per_s_per_chip=1.3e4,
        setup_s=150.0,
        bench_prep_s=0.5,
        cache_files_added=0,
        trace={
            "steps": STEPS,
            "busy_s": sum(op_s.values()),
            "window_s": 9.6,
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
    # no roofline of the grouped products lists this cell (PERF.md,
    # Open questions: the traced steps' rows are not the windows' mean)
    assert not [n for n in asked if "gmm" in n and n.endswith("_roofline")]
    # by name, no entry's place: a later PR appends behind these
    assert CONFIG in [c["name"] for c in bench["configs"]]
    assert CELL in [w["name"] for w in bench["workloads"]]


def test_the_cell_states_its_cut():
    loaded = spec.load_cell(CELL)
    config, traffic, cell = loaded["config"], loaded["traffic"], loaded["cell"]
    assert cell["chips"] == 1 and cell["traffic"] == "l8192-tok8k-ids4k"
    assert (traffic["seq_len"], traffic["minibatch_size"], traffic["token_ids"]) == (8192, 1, 4096)
    assert traffic["expect_attention"] == "pallas"
    (entry,) = [c for c in _bench()["configs"] if c["name"] == config["name"]]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
    ]  # fmt: skip
    assert entry["source"] == config["source"]
    assert config["source"] == "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"
    params = config["model_params"]
    # every published width is as published, and is what the model is given
    assert (params["embed_dim"], params["mlp_dim"], params["expert_dim"], params["shared_expert_dim"]) == (
        config["hidden_size"], config["intermediate_size"],
        config["moe_intermediate_size"],
        config["n_shared_experts"] * config["moe_intermediate_size"],
    ) == (2048, 10240, 1536, 1536)  # fmt: skip
    assert (
        params["num_heads"], params["mla_q_rank"], params["mla_kv_rank"],
        params["mla_nope_dim"], params["mla_rope_dim"], params["mla_v_dim"],
    ) == (
        config["num_attention_heads"], config["q_lora_rank"], config["kv_lora_rank"],
        config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"],
    ) == (20, 768, 512, 192, 64, 256)  # fmt: skip
    # q and k are as wide as v: the call is the plain kernels'
    assert params["mla_nope_dim"] + params["mla_rope_dim"] == params["mla_v_dim"]
    assert config["num_key_value_heads"] == 20 and config["attention_bias"] is False
    assert (params["num_experts_per_tok"], config["n_group"], config["topk_group"]) == (
        config["num_experts_per_tok"], 1, 1,
    ) == (4, 1, 1)  # fmt: skip
    assert "num_expert_groups" not in params and "expert_groups_per_tok" not in params
    assert params["routed_scaling_factor"] == config["routed_scaling_factor"] == 1.8
    assert config["topk_method"] == "noaux_tc" and params["routing"] == "sigmoid_bias"
    assert config["norm_topk_prob"] is True and config["hidden_act"] == "silu"
    assert (params["rope_theta"], params["norm_eps"]) == (config["rope_theta"], config["rms_norm_eps"]) == (1e6, 1e-5)
    assert config["partial_rotary_factor"] == 1 and config["rope_scaling"] is None
    assert params["tie_head"] is config["tie_word_embeddings"] is False
    assert params["mtp_layers"] == config["num_nextn_predict_layers"] == 1
    assert params["mtp_loss_weight"] == 0.3
    # the dense layer, four expert layers, and the module behind them
    assert params["layer_pattern"] == "lllll" and params["num_dense_layers"] == 1
    assert len(params["layer_pattern"]) == config["num_hidden_layers"] == 5
    assert config["first_k_dense_replace"] == 1
    # what is cut is named, with the published number beside it
    published = config["published"]
    assert published["num_hidden_layers"] == 47
    assert params["experts_held"] == config["n_routed_experts"] == 8
    assert params["num_experts"] == published["n_routed_experts"] == 64
    assert params["first_expert_held"] == 0
    assert params["vocab_size"] == config["vocab_size"] == published["vocab_size"] // 8 == 19360
    assert traffic["token_ids"] <= params["vocab_size"]
    assert config["context_length"] == config["max_position_embeddings"] == 202752
    assert "8-way" in config["deployment"] and "42 layers" in config["deployment"]
    # what no config key states, each under ``assumed``
    assert {
        "mla", "rotary", "router", "expert_bias", "expert_apply", "shared_expert",
        "mtp", "mtp_input", "mtp_loss_weight", "mtp_positions", "optimizer",
        "dtype", "remat_layers", "language_model_only",
    } <= set(config["assumed"])  # fmt: skip
    assert params["expert_apply"] == "grouped" and params["expert_bias_rate"] == 0.1
    assert params["remat_layers"] is True and params["dtype"] == "bfloat16"
    # five expert layers: two grouped products forward, again
    # recomputed, two dlhs, two tgmm; six latent attentions' three
    # kernels and their forward once more
    assert config["tpu_custom_calls"] == {"pallas": 5 * 8 + 6 * 4}
    assert config["cost"] == "glm_mla_mtp_share"
    assert config["reference"] == "glm_mla_mtp_reference"
    assert config["held_here"]["parameters"] == PARAMETERS


def test_every_number_of_the_catalog_row_is_in_the_file():
    """The row's ``config`` as the model-configs guide's catalog has it:
    every key under the same name with the same value, but the three
    that ``reduced`` lists."""
    row = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
        "v_head_dim": 256, "vocab_size": 154880,
    }  # fmt: skip
    config = spec.load_cell(CELL)["config"]
    differs = sorted(key for key, value in row.items() if config.get(key, "absent") != value)
    assert differs == sorted(config["reduced"])
    assert {key: config["published"][key] for key in differs} == {key: row[key] for key in differs}


def test_parameters_held_against_the_hand_count_and_the_models_init():
    params = spec.load_cell(CELL)["config"]["model_params"]
    d, heads, v = 2048, 20, 19360
    mixer = d * 768 + 768 + 768 * heads * 256 + d * 576 + 512 + 512 * heads * 448 + heads * 256 * d
    assert (d * 768, 768 * heads * 256, d * 576, 512 * heads * 448, heads * 256 * d) == (
        1_572_864, 3_932_160, 1_179_648, 4_587_520, 10_485_760,
    )  # fmt: skip
    expert = 3 * d * 1536
    expert_ff = d * 64 + expert + 8 * expert
    dense_ff = 3 * d * 10240
    assert (mixer, expert, expert_ff, dense_ff) == (21_759_232, 9_437_184, 85_065_728, 62_914_560)
    dense_layer, expert_layer = mixer + dense_ff + 2 * d, mixer + expert_ff + 2 * d
    assert (dense_layer, expert_layer, 4 * expert_layer) == (84_677_888, 106_829_056, 427_316_224)
    vocabulary = 2 * v * d + d
    module = 2 * d + 2 * d * d + expert_layer + d
    assert (vocabulary, module) == (79_300_608, 115_223_808)
    assert dense_layer + 4 * expert_layer + vocabulary + module == PARAMETERS
    cost = spec.load_cost("glm_mla_mtp_share")
    assert cost.parameters_held(params) == PARAMETERS
    # 12 B a parameter of state, 4 B more of gradient
    assert round(12 * PARAMETERS / 1e9, 2) == 8.48 and round(4 * PARAMETERS / 1e9, 2) == 2.83
    # and what the program's own init makes, leaf by leaf
    import jax
    import jax.numpy as jnp

    model, _ = compare._load_program_model(spec.load_cell(CELL)["config"])
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), {"tokens": jnp.zeros((1, 64), jnp.int32)})
    )
    leaves = jax.tree_util.tree_leaves(shapes["params"])
    assert sum(leaf.size for leaf in leaves) == PARAMETERS
    assert all(leaf.dtype == jnp.float32 for leaf in leaves)
    in_the_module = sum(
        leaf.size
        for name, part in shapes["params"].items()
        if name.startswith("mtp_0_")
        for leaf in jax.tree_util.tree_leaves(part)
    )
    assert in_the_module == module
    # the state beside them: a bias and a counter an expert and layer,
    # the module's router's under its own path, and the module's loss
    assert sorted(shapes["moe_state"]) == ["layer_%d_moe" % i for i in (1, 2, 3, 4)] + ["mtp_0_moe"]
    assert shapes["moe_state"]["mtp_0_moe"]["expert_bias"].shape == (64,)
    assert shapes["aux_loss"]["mtp_loss"].shape == ()


def test_cost_module_against_a_count_by_hand():
    params = spec.load_cell(CELL)["config"]["model_params"]
    cost = spec.load_cost("glm_mla_mtp_share")
    d, v = 2048, 19360
    mixer = d * 768 + 768 * 20 * 256 + d * 576 + 512 * 20 * 448 + 20 * 256 * d
    expert = 3 * d * 1536
    # six mixers, one dense FF, five routers and shared experts (the
    # module's among them), W_M, and the head's slice TWICE
    matmul = 6 * mixer + 3 * d * 10240 + 5 * (d * 64 + expert) + 2 * d * d + 2 * v * d
    assert mixer == 21_757_952 and cost.mla_params(params) == (mixer, 768 + 512)
    assert cost.matmul_params(params) == matmul == 328_990_720
    # 4 a token, 8 of 64 of them here, in each of 5 expert layers:
    # the experts a token is computed by, not the 8 held
    assert cost.expert_params_per_token(params) == 5 * 4 * 8 / 64 * expert == 23_592_960
    assert matmul + 23_592_960 == 352_583_680
    attention = 6 * (LENGTH + 1) / 2 * 20 * (256 + 256)
    assert cost.attention_flops_per_token(params, LENGTH) == attention
    by_hand = 6 * 352_583_680 + 6 * attention
    assert cost.train_flops_per_token(params, LENGTH) == pytest.approx(by_hand)
    assert round(by_hand / 1e6) == 3626
    # of it: six attentions 1,510M (42%), the head twice 476M, the
    # module (layer, projection, head, attention) 756M
    assert round(6 * attention / 1e6) == 1510 and round(6 * 2 * v * d / 1e6) == 476
    without = dict(params, mtp_layers=0)
    assert round((by_hand - cost.train_flops_per_token(without, LENGTH)) / 1e6) == 756
    assert cost.parameters_held(without) == PARAMETERS - 115_223_808
    # a step's attention over the causal pairs is what the kernels'
    # rooflines count (benchmark/flops.py, which counts L^2 / 2 pairs):
    # forward 2 of its products, backward 4 without the recomputed q k^T
    kernels = sum(
        flops.flash_kernel_cost(k, 20, LENGTH, 256)[0] * share
        for k, share in (("edl_flash_fwd", 1), ("edl_flash_bwd_dq", 2 / 3), ("edl_flash_bwd_dkv", 2 / 4))
    )
    assert attention * LENGTH == pytest.approx(kernels * (LENGTH + 1) / LENGTH)
    with pytest.raises(ValueError, match="knows layers of kind l"):
        cost.train_flops_per_token(dict(params, layer_pattern="lllal"), LENGTH)


def test_the_new_readers_arithmetic():
    run = _traced_run()
    assert spec.load_reader("flash_glm_ms_per_step").read(run) == pytest.approx(
        1e3 * (3.2 + 2.1 + 2.6) / STEPS
    )
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        name = "edl_flash_" + kernel
        ((_, (seconds, calls)),) = [kv for kv in FLASH_OPS.items() if kv[0].startswith(name + "_")]
        least, bound = flops.roofline(
            *flops.flash_kernel_cost(name, BATCH * HEADS, LENGTH, WIDTH), "TPU v5 lite"
        )
        share = spec.load_reader("edl_flash_d256_%s_roofline" % kernel).read(run)
        assert bound == "compute"
        assert share == pytest.approx(100 * STEPS * calls * least / seconds)
        assert 0 < share < 100
        # it is the accepted reader's arithmetic on these ops
        assert share == pytest.approx(spec.load_reader(name + "_roofline").read(run))
    # a call at another head size in the same step is not this metric's
    other = dict(run, trace=dict(run["trace"]))
    other["trace"]["op_s"] = dict(run["trace"]["op_s"], edl_flash_fwd_bf16_64_8192_64_=9.0)
    other["trace"]["op_calls"] = dict(run["trace"]["op_calls"], edl_flash_fwd_bf16_64_8192_64_=16)
    assert spec.load_reader("edl_flash_d256_fwd_roofline").read(other) == pytest.approx(
        spec.load_reader("edl_flash_d256_fwd_roofline").read(run)
    )
    # the grouped products: the accepted readers' arithmetic
    assert spec.load_reader("gmm_glm_ms_per_step").read(run) == pytest.approx(
        1e3 * sum(s for s, _ in GMM_OPS.values()) / STEPS
    ) == spec.load_reader("gmm_ms_per_step").read(run)
    assert spec.load_reader("moe_glm_load_max_over_mean").read(run) == pytest.approx(640 / 512)


def test_a_traced_line_holds_exactly_the_cells_metrics():
    run = _traced_run()
    asked = [m["name"] for m in spec.load_cell(CELL)["per_layer"]]
    values = {}
    for name in asked:
        try:
            values[name] = spec.load_reader(name).read(run)
        except Exception:  # a reader of the real trace file: not this test's
            assert name not in FROM_THE_REDUCED_TRACE
    assert not [name for name in FROM_THE_REDUCED_TRACE if values[name] is None]
    assert 0 < values["mfu"] < 100
    # the plain kernels' accepted readers would find this cell's ops
    # too; their lists did not gain it, so its line does not hold them
    assert "flash_ms_per_step" not in asked and "gmm_ms_per_step" not in asked


def test_the_modules_reader_walks_the_map_the_program_wrote(tmp_path, monkeypatch):
    """``mtp_ms_per_step`` reads the ``scopes`` of the map a traced
    worker writes beside its trace (elasticdl_tpu/utils/step_ops.py):
    the walk is ``_split``'s, handed the scope's own map, and sums the
    ops that lie under ``edl/mtp`` whole."""
    import _glm
    import _split

    run = _traced_run()
    map_path = tmp_path / "edl_step_ops.json"
    monkeypatch.setattr(_split, "map_file", lambda run: str(map_path))
    reader = spec.load_reader("mtp_ms_per_step")
    # no map (an untraced worker, a program before PR 37): nothing
    assert reader.read(run) is None
    # a map without scopes (the parent's), or without this one: nothing
    ops_map = {"module": "jit_per_device", "ops": {"fusion.1": "fwd"}}
    map_path.write_text(json.dumps(ops_map))
    assert reader.read(run) is None
    ops_map["scopes"] = {"edl/mla": {"fusion.2": "in"}}
    map_path.write_text(json.dumps(ops_map))
    assert reader.read(run) is None
    ops_map["scopes"]["edl/mtp"] = {"fusion.1": "in", "fusion.3": "in+out"}
    map_path.write_text(json.dumps(ops_map))
    seen = {}

    def walk(xplane, scope_map, last_step, n_steps):
        seen.update(json.load(open(scope_map)), steps=n_steps)
        return {"in": 0.8, _split.MIXED: 0.3, "": 5.0}

    monkeypatch.setattr(_split, "_walk", walk)
    monkeypatch.setattr(_split, "trace_file", lambda run: "trace.xplane.pb")
    assert _glm.scope_s(run, "edl/mtp") == (0.8, 0.3)
    assert reader.read(run) == pytest.approx(1e3 * 0.8 / STEPS)
    assert seen == {
        "module": "jit_per_device", "steps": STEPS,
        "ops": {"fusion.1": "in", "fusion.3": "in+out"},
    }  # fmt: skip


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_reader_is_silent_on_a_program_without_a_module(name):
    """The parent has no such fact or scope: the reader returns nothing
    and does not raise, traced or not."""
    run = _traced_run(glm=False)
    assert spec.load_reader(name).read(dict(run, trace=None)) is None
    assert spec.load_reader(name).read(run) is None
    # and of this program's own untraced run
    if name != "moe_glm_load_max_over_mean":  # a counter: read untraced too
        assert spec.load_reader(name).read(dict(_traced_run(), trace=None)) is None


TOY = dict(
    name="glm-toy",
    model_def="transformer_lm.hybrid_moe_lm.custom_model",
    reference="glm_mla_mtp_reference",
    model_params=dict(
        vocab_size=256, layer_pattern="lll", num_dense_layers=1, embed_dim=64,
        num_heads=4, mlp_dim=96, expert_dim=32, num_experts=32, experts_held=4,
        first_expert_held=0, num_experts_per_tok=4, shared_expert_dim=24,
        routing="sigmoid_bias", expert_apply="grouped", routed_scaling_factor=1.8,
        mla_q_rank=40, mla_kv_rank=24, mla_nope_dim=24, mla_rope_dim=8,
        mla_v_dim=32, mtp_layers=1, mtp_loss_weight=0.3, tie_head=False,
        rope_theta=1e6, norm_eps=1e-5, remat_layers=True, dtype="bfloat16",
    ),
)  # fmt: skip


def test_the_built_step_holds_the_configurations_kernel_calls(monkeypatch):
    """The cell's layout at toy widths and 1,024 positions (where the
    policy hands attention to the kernels), lowered for the TPU from
    here with the state a training step holds: the number of Mosaic
    calls ``step_built`` will report is the configuration's."""
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.dirname(BENCHMARK))
    from elasticdl_tpu.ops import flash_attention, grouped_matmul

    monkeypatch.setattr(flash_attention, "kernel_interpret_mode", lambda: False)
    monkeypatch.setattr(grouped_matmul, "kernel_interpret_mode", lambda: False)
    cell = spec.load_cell(CELL)["config"]
    laid_out = dict(
        TOY["model_params"], embed_dim=128, expert_dim=128, mla_nope_dim=96,
        mla_rope_dim=32, mla_v_dim=128,
        **{k: cell["model_params"][k] for k in ("layer_pattern", "num_dense_layers", "mtp_layers")},
    )  # fmt: skip
    model, loss = compare._load_program_model(dict(TOY, model_params=laid_out))
    tokens = jnp.zeros((1, 1024), jnp.int32)
    state = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), {"tokens": tokens}))
    params = state.pop("params")

    def objective(params, state):
        logits, new = model.apply(
            dict(state, params=params), {"tokens": tokens}, training=True,
            mutable=list(state),
        )  # fmt: skip
        return loss(logits, tokens) + new["aux_loss"]["mtp_loss"]

    text = (
        jax.jit(jax.value_and_grad(objective))
        .trace(params, state)
        .lower(lowering_platforms=("tpu",))
        .as_text()
    )
    assert text.count("@tpu_custom_call") == cell["tpu_custom_calls"]["pallas"] == 64


def test_f32_program_equals_the_reference_through_the_comparison_child():
    config = dict(TOY, model_params=dict(TOY["model_params"], dtype="float32"))
    got = compare.compare(config, 128, seed=3)
    # embedding, head, final norm; 9 leaves a layer's mixer and norms,
    # 3 of the dense FF, 5 of an expert FF; 4 more of the module
    assert got["program_leaves"] == 3 + 3 * 9 + 3 + 2 * 5 + 4 + 9 + 5
    assert len(got["grad_rel_l2_error"]) == got["program_leaves"]
    assert {"mtp.proj", "mtp.router", "L2.wqa", "L2.q_norm", "L0.w1"} <= set(
        got["grad_rel_l2_error"]
    )
    assert got["loss_rel_error"] < 1e-6
    assert max(got["grad_rel_l2_error"].values()) < 1e-3


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 77])
def test_the_control_is_refused_at_the_toy_size(seed):
    """The next precision down from the configuration's bf16, in the
    program's place: the comparison has to say no, by a gradient leaf;
    the program itself, same seed, is inside the loss's limit and its
    worst leaf well under the control's."""
    got = compare.compare(TOY, 128, seed=seed, control="float8_e4m3fn")
    assert got["control"] == "float8_e4m3fn" and not got["agree"], got
    worst = max(got["grad_rel_l2_error"].values())
    assert worst > got["grad_rel_l2_tolerance"]
    sound = compare.compare(TOY, 128, seed=seed)
    assert sound["loss_rel_error"] <= sound["loss_rel_tolerance"], sound
    assert worst > 1.5 * max(sound["grad_rel_l2_error"].values())
