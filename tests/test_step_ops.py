"""The device step under the program's names (utils/step_ops.py):

- ``classify``: a name stack of the compiled step -> forward, backward,
  recomputed forward, optimizer or reduce;
- ``op_classes``: the compiled text of a step built by
  ``make_elastic_train_step`` -> each instruction that runs as an op of
  its own with the classes it holds, a fusion's those of what is fused
  into it;
- ``ElasticDPTrainer.describe_step`` writes that map beside the trace in
  a traced run (``EDL_PROFILE_DIR``) and only there: unset, it compiles
  nothing, writes nothing and says what it said before;
- ``tracetool --step-split`` joins the map with a trace recorded on the
  chip.

Everything runs on the CPU; nothing here is a measurement.
"""

import gzip
import json
import os
import re

import flax.linen as nn
import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import elasticdl_tpu.parallel.distributed as dist_mod
from elasticdl_tpu.parallel import elastic
from elasticdl_tpu.parallel.distributed import WorldSpec
from elasticdl_tpu.tools import tracetool
from elasticdl_tpu.training.step import TrainState
from elasticdl_tpu.utils import step_ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(
    REPO, "benchmark", "tests", "data", "lm125m-l2048.2steps.xplane.pb.gz"
)

# name stacks read off real compiled steps: the toy below, and the steps
# of lm125m-l2048-dp4 and granite4h-vp8-l2048 compiled for a described v5e
STACKS = [
    ("jit(step)/jvp(edl/model)/dot_general", {"fwd"}),
    ("jit(step)/jvp()/tanh", {"fwd"}),
    ("jit(per_device)/shard_map/jvp(TransformerLM)/edl_flash_fwd", {"fwd"}),
    ("jit(per_device)/shard_map/jvp(jit(take_along_axis))/gather", {"fwd"}),
    # the transpose primitive is no transposition of a jvp
    ("jit(per_device)/shard_map/jvp(TransformerLM)/transpose", {"fwd"}),
    ("jit(step)/transpose(jvp())/mul", {"bwd"}),
    (
        "jit(per_device)/shard_map/transpose(jvp(TransformerLM))/edl_flash_bwd_dkv",
        {"bwd"},
    ),
    # under jax.checkpoint the backward pass keeps the inner jvp( in its stack
    (
        "jit(per_device)/transpose(jvp(HybridMoELM))/jvp(HybridMoELM)/checkpoint/mul",
        {"bwd"},
    ),
    ("jit(per_device)/transpose(jvp(HybridMoELM))/jvp(HybridMoELM)/remat2", {"bwd"}),
    (
        "jit(step)/transpose(jvp(edl/model))/checkpoint/rematted_computation/dot_general",
        {"remat"},
    ),
    (
        "jit(per_device)/transpose(jvp(HybridMoELM))/jvp(HybridMoELM)/checkpoint/"
        "rematted_computation/edl_flash_fwd",
        {"remat"},
    ),
    # a scan whose body is checkpointed, inside the backward pass
    (
        "jit(per_device)/transpose(jvp(HybridMoELM))/jvp(HybridMoELM)/checkpoint/"
        "edl/ssd/while/body/checkpoint/rematted_computation/dot_general",
        {"remat"},
    ),
    ("jit(step)/edl/optimizer/sub", {"optimizer"}),
    ("jit(per_device)/shard_map/edl/optimizer/jit(_where)/select_n", {"optimizer"}),
    ("jit(per_device)/shard_map/edl/reduce/psum", {"reduce"}),
    # a scope of ours wins over the pass it sits in
    ("jit(f)/transpose(jvp(m))/edl/reduce/psum", {"reduce"}),
    # no marker: nothing
    ("jit(per_device)/shard_map/mul", set()),
    ("jit(per_device)/shard_map/edl/ssd/while", set()),
    ("checkpoint", set()),
    ("", set()),
    # names the compiler merged, joined by `;`: each gives its class
    (
        "jit(per_device)/jvp(HybridMoELM)/edl/ssd/reshape;jit(per_device)/jvp(HybridMoELM)",
        {"fwd"},
    ),
    ("jit(s)/transpose(jvp(m))/dot_general;jit(s)/edl/optimizer/mul", {"bwd", "optimizer"}),
    ("jit(s)/jvp(m)/exp;jit(s)/mul;jit(s)/transpose(jvp(m))/mul", {"fwd", "bwd"}),
]


@pytest.mark.parametrize("op_name, classes", STACKS)
def test_classify(op_name, classes):
    assert step_ops.classify(op_name) == classes
    assert step_ops.joined(classes) == "+".join(sorted(classes))


@pytest.mark.parametrize(
    "classes, bucket",
    [("", "unnamed"), ("bwd", "bwd"), ("reduce", "reduce"), ("bwd+optimizer", "mixed")],
)
def test_a_set_of_more_than_one_class_is_mixed(classes, bucket):
    assert step_ops.bucket_of(classes) == bucket


# ---------------------------------------------------------------------------
# a compiled step's text
# ---------------------------------------------------------------------------


class Layer(nn.Module):
    @nn.compact
    def __call__(self, x):
        # the second product reads a recomputed value, so its own
        # recomputation is an op that is recomputation alone
        return jnp.tanh(nn.Dense(32)(jnp.tanh(nn.Dense(32)(x))))


class Toy(nn.Module):
    """One checkpointed layer, then three layers under a ``lax.scan``."""

    @nn.compact
    def __call__(self, x, training=False):
        x = nn.remat(Layer)(name="recomputed")(x)
        stack = self.param("stack", nn.initializers.lecun_normal(), (3, 32, 32))

        def body(h, w):
            return jnp.tanh(h @ w), None

        x, _ = jax.lax.scan(body, x, stack)
        return nn.Dense(1, name="head")(x)


def _loss(output, labels):
    return jnp.mean((output[:, 0] - labels) ** 2)


def _lowered():
    module, optimizer = Toy(), optax.adamw(1e-2)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    x = jnp.ones((8, 16), jnp.float32)
    params = module.init(jax.random.PRNGKey(0), x)["params"]
    ts = TrainState.create(params, {}, optimizer)
    step = elastic.make_elastic_train_step(module, _loss, optimizer, mesh)
    with mesh:
        return step.lower(
            ts, x, jnp.ones((8,)), jnp.ones((1,)), jnp.zeros((1,), jnp.int32),
            jax.random.PRNGKey(1),
        )  # fmt: skip


@pytest.fixture(scope="module")
def lowered():
    """The toy's step through ``make_elastic_train_step`` on a mesh of one
    device, lowered."""
    return _lowered()


@pytest.fixture(scope="module")
def compiled_text(lowered):
    return lowered.compile().as_text()


def test_every_class_is_in_the_compiled_step(compiled_text):
    classes = step_ops.op_classes(compiled_text)
    held = set()
    for joined in classes.values():
        held |= set(joined.split("+")) if joined else set()
    assert held == set(step_ops.CLASSES)
    # ops that are one class alone (the CPU's compiler folds this toy's
    # recomputed products into the forward's, so what it recomputes is
    # fused into backward ops: `bwd+remat`)
    assert {"fwd", "bwd", "optimizer", "reduce"} <= set(classes.values())
    assert any("remat" in c.split("+") for c in classes.values())
    ops_map, total = step_ops.step_ops_map(compiled_text)
    assert ops_map["module"] == "jit_per_device"
    assert ops_map["ops"] == {n: c for n, c in classes.items() if c}
    assert 0 < len(ops_map["ops"]) <= total == len(classes)


# a module as the TPU's compiler prints one, cut to what the parser
# reads: names with and without `%`, tuple types, a fusion whose fused
# computation holds a reduction, a loop, a conditional, a call, an
# instruction with braces behind its metadata, and one with none
WRITTEN = """HloModule jit_per_device, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

FileNames
1 "step.py"

%region_add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%a, %b), metadata={op_name="jit(per_device)/edl/optimizer/reduce_sum"}
}

%fused_remat (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %tanh.9 = f32[8]{0} tanh(%p0), metadata={op_name="jit(per_device)/transpose(jvp(M))/jvp(M)/checkpoint/rematted_computation/tanh"}
}

%fused_update (p0.1: f32[8], p1.1: f32[8]) -> f32[] {
  %p0.1 = f32[8]{0} parameter(0)
  %p1.1 = f32[8]{0} parameter(1)
  %mul.3 = f32[8]{0} multiply(%p0.1, %p1.1), metadata={op_name="jit(per_device)/transpose(jvp(M))/dot_general"}
  %zero = f32[] constant(0)
  ROOT %sum.4 = f32[] reduce(%mul.3, %zero), dimensions={0}, to_apply=%region_add
}

%body (carry: (s32[], f32[8])) -> (s32[], f32[8]) {
  %carry = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%carry), index=0
  %x = f32[8]{0:T(8,128)S(1)} get-tuple-element(%carry), index=1
  %exp.5 = f32[8]{0} exponential(%x), metadata={op_name="jit(per_device)/jvp(M)/while/body/exp" stack_frame_id=3}, backend_config={"flag_configs":[],"window_config":{"kernel_window_bounds":[]}}
  ROOT %next = (s32[], f32[8]{0}) tuple(%i, %exp.5)
}

%cond (carry.1: (s32[], f32[8])) -> pred[] {
  %carry.1 = (s32[], f32[8]{0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%carry.1), index=0
  %three = s32[] constant(3)
  ROOT %lt.6 = pred[] compare(%i.1, %three), direction=LT, metadata={op_name="jit(per_device)/jvp(M)/while/cond/lt"}
}

%on_true (t: f32[8]) -> f32[8] {
  %t = f32[8]{0} parameter(0)
  ROOT %neg.7 = f32[8]{0} negate(%t), metadata={op_name="jit(per_device)/edl/reduce/neg"}
}

on_false (f: f32[8]) -> f32[8] {
  f = f32[8]{0} parameter(0)
  ROOT copy.8 = f32[8]{0} copy(f)
}

%called (c: f32[8]) -> f32[8] {
  %c = f32[8]{0} parameter(0)
  ROOT %sqrt.2 = f32[8]{0} sqrt(%c), metadata={op_name="jit(per_device)/edl/optimizer/sqrt"}
}

ENTRY %main.1_spmd (arg: f32[8]) -> f32[8] {
  %arg = f32[8]{0} parameter(0)
  %start = (s32[], f32[8]{0}) tuple(%zero.1, %arg)
  %while.10 = (s32[], f32[8]{0:T(8,128)(2,1)}) while(%start), condition=%cond, body=%body, metadata={op_name="jit(per_device)/jvp(M)/while"}
  %out = f32[8]{0} get-tuple-element(%while.10), index=1
  %fusion.11 = f32[8]{0} fusion(%out), kind=kLoop, calls=%fused_remat, metadata={op_name="jit(per_device)/transpose(jvp(M))/jvp(M)/checkpoint/rematted_computation/tanh"}
  %fusion.12 = f32[] fusion(%fusion.11, %out), kind=kInput, calls=%fused_update, metadata={op_name="jit(per_device)/edl/optimizer/mul"}
  %pick = pred[] constant(true)
  %conditional.13 = f32[8]{0} conditional(%pick, %out, %out), true_computation=%on_true, false_computation=on_false
  %call.14 = f32[8]{0} call(%conditional.13), to_apply=%called
  %bitcast.15 = f32[8]{0} bitcast(%call.14)
  ROOT %copy.16 = f32[8]{0} copy(%bitcast.15)
}
"""


def test_op_classes_of_a_written_module():
    assert step_ops.module_name(WRITTEN) == "jit_per_device"
    assert step_ops.op_classes(WRITTEN) == {
        # the entry's ops; a loop's own op holds its own name's class
        "while.10": "fwd",
        "fusion.11": "remat",
        # a fusion holds what is fused into it, a reduction's region too
        "fusion.12": "bwd+optimizer",
        # a conditional's and a call's computations run as ops of their own
        "conditional.13": "",
        "call.14": "",
        "copy.16": "",
        "exp.5": "fwd",
        "lt.6": "fwd",
        "neg.7": "reduce",
        "copy.8": "",
        "sqrt.2": "optimizer",
    }
    ops_map, total = step_ops.step_ops_map(WRITTEN)
    assert (len(ops_map["ops"]), total) == (7, 11)
    assert "copy.8" not in ops_map["ops"] and ops_map["ops"]["fusion.12"] == "bwd+optimizer"


@pytest.mark.parametrize(
    "op_name, held",
    [
        ("jit(step)/jvp(M)/edl/mtp/M.predicted/dot_general", {"in"}),
        ("jit(step)/transpose(jvp(M))/edl/mtp/embed/scatter-add", {"in"}),
        ("jit(step)/transpose(jvp(edl/mtp))/mul", {"in"}),
        ("jit(step)/jvp(M)/edl/mtp/M.predicted/edl/mla/mtp_0_mla/dot_general", {"in"}),
        ("jit(step)/jvp(M)/M.layer/edl/mla/layer_0_mla/dot_general", {"out"}),
        # a scope whose name only starts alike is another scope
        ("jit(step)/jvp(M)/edl/mtp_other/add", {"out"}),
        ("a/edl/mtp/x;a/edl/optimizer/y", {"in", "out"}),
        ("", set()),
    ],
)
def test_a_name_stack_is_inside_a_scope_or_outside_it(op_name, held):
    assert step_ops.inside("edl/mtp")(op_name) == held


# a fusion wholly under a model's scope, one the compiler fused with
# the optimizer's work, and ops outside it
SCOPED = """HloModule jit_per_device, is_scheduled=true

%fused_module (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %exp.1 = f32[8]{0} exponential(%p0), metadata={op_name="jit(per_device)/jvp(M)/edl/mtp/M.predicted/exp"}
  ROOT %tanh.2 = f32[8]{0} tanh(%exp.1), metadata={op_name="jit(per_device)/jvp(M)/edl/mtp/M.predicted/edl/mla/mtp_0_mla/tanh"}
}

%fused_both (p0.1: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  %neg.3 = f32[8]{0} negate(%p0.1), metadata={op_name="jit(per_device)/transpose(jvp(M))/edl/mtp/M.predicted/neg"}
  ROOT %sqrt.4 = f32[8]{0} sqrt(%neg.3), metadata={op_name="jit(per_device)/edl/optimizer/sqrt"}
}

ENTRY %main (arg: f32[8]) -> f32[8] {
  %arg = f32[8]{0} parameter(0)
  %fusion.5 = f32[8]{0} fusion(%arg), kind=kLoop, calls=%fused_module
  %fusion.6 = f32[8]{0} fusion(%fusion.5), kind=kLoop, calls=%fused_both
  %abs.7 = f32[8]{0} abs(%fusion.6), metadata={op_name="jit(per_device)/jvp(M)/M.layer/edl/mla/layer_0_mla/abs"}
  ROOT %copy.8 = f32[8]{0} copy(%abs.7)
}
"""


def test_the_map_says_which_ops_lie_under_a_models_scope():
    """``scopes`` of the map a traced worker writes: a scope of the
    model's by name, its ops whole (``in``) or fused with work from
    outside it (``in+out``); the two class scopes are not among them;
    an op with no name says nothing."""
    assert step_ops.model_scopes(SCOPED) == ["edl/mla", "edl/mtp"]
    assert step_ops.ops_under(SCOPED, "edl/mtp") == {
        "fusion.5": "in", "fusion.6": "in+out",
    }  # fmt: skip
    # the module's own latent attention is under both; the trunk's under one
    assert step_ops.ops_under(SCOPED, "edl/mla") == {"fusion.5": "in+out", "abs.7": "in"}
    ops_map, total = step_ops.step_ops_map(SCOPED)
    assert total == 4 and ops_map["ops"]["fusion.6"] == "bwd+optimizer"
    assert ops_map["scopes"] == {
        "edl/mla": {"fusion.5": "in+out", "abs.7": "in"},
        "edl/mtp": {"fusion.5": "in", "fusion.6": "in+out"},
    }
    # a step with no scope of a model's: the key is there and empty
    assert step_ops.step_ops_map(WRITTEN)[0]["scopes"] == {}


def _computations(text):
    """{computation: [instruction names]} and the names a ``fusion``
    calls, by a reading of the text that shares nothing with the parser
    under test."""
    inside, fused, current = {}, set(), None
    for line in text.splitlines():
        if line.endswith("{") and " -> " in line and not line.startswith(" "):
            current = line.split()[1 if line.startswith("ENTRY") else 0].lstrip("%")
            inside[current] = []
        elif line.startswith("}"):
            current = None
        elif current and " = " in line:
            inside[current].append(line.split(" = ")[0].split()[-1].lstrip("%"))
            if " fusion(" in line:
                fused.add(re.search(r"calls=%?([\w.\-]+)", line).group(1))
    return inside, fused


def test_loop_bodies_are_in_the_map_and_fused_computations_are_not(compiled_text):
    classes = step_ops.op_classes(compiled_text)
    inside, fused = _computations(compiled_text)
    assert fused, "the compiled toy holds no fusion"
    for computation in fused:
        assert not set(inside[computation]) & set(classes), computation
    # the scan's layers run in `while` bodies: forward, and backward
    bodies = set(re.findall(r"\bbody=%?([\w.\-]+)", compiled_text))
    assert len(bodies) >= 2
    in_bodies = {n for b in bodies for n in inside[b] if classes.get(n)}
    assert {classes[n] for n in in_bodies} >= {"fwd", "bwd"}
    # a fusion holds the union of what is fused into it
    mixed = [c for c in classes.values() if "+" in c]
    assert all(set(c.split("+")) <= set(step_ops.CLASSES) for c in mixed)
    # parameters, tuples and the like never run: they are not counted
    assert not [n for n in classes if n.startswith(("param", "get-tuple-element", "tuple"))]


def test_two_compiles_of_one_lowering_bear_the_same_names(lowered, compiled_text):
    """The map is made from one executable and the device runs another
    (the jitted step's own, or one loaded from the cache): the
    instruction names have to be the compiler's for that lowering, not
    an accident of one compile."""
    again = lowered.compile().as_text()
    assert step_ops.op_classes(again) == step_ops.op_classes(compiled_text)


def test_the_scopes_change_no_cache_key(lowered, monkeypatch):
    """A scope is metadata, and jax leaves metadata out of the key under
    which a compiled program is kept: the step with the two scopes and
    the step without them are one entry of the compile cache, so a
    machine that holds the parent's program holds this one."""
    import contextlib

    from jax._src import cache_key, compiler, xla_bridge

    def key(lowering):
        return cache_key.get(
            lowering.compiler_ir(),
            np.asarray(jax.devices()[:1]),
            compiler.get_compile_options(num_replicas=1, num_partitions=1),
            xla_bridge.get_backend(),
        )

    assert "edl/optimizer" in lowered.as_text(debug_info=True)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = _lowered()
    assert "edl/optimizer" not in bare.as_text(debug_info=True)
    assert key(bare) == key(lowered)


# ---------------------------------------------------------------------------
# the trainer writes the map in a traced run, and only there
# ---------------------------------------------------------------------------

TODAYS_FACTS = {
    "pallas_calls", "pallas_interpreted", "pallas_kernels",
    "tpu_custom_calls", "triangular_solves", "mosaic_kernels", "donated_inputs",
}  # fmt: skip
ROWS = 8


def _batch():
    x = np.ones((ROWS, 16), np.float32)
    return x, np.ones((ROWS,), np.float32)


@pytest.fixture
def trainer(monkeypatch):
    monkeypatch.setattr(dist_mod, "ensure_world", lambda s, **k: None)
    monkeypatch.setattr(
        elastic, "build_world_mesh",
        lambda mesh_axes_fn=None: Mesh(np.asarray(jax.devices()[:1]), ("data",)),
    )  # fmt: skip
    trainer = elastic.ElasticDPTrainer(Toy(), _loss, optax.adamw(1e-2))
    trainer.default_minibatch_size = ROWS
    trainer.establish(
        WorldSpec(coordinator="", num_processes=1, process_id=0, epoch=0),
        example_batch=_batch(),
    )
    yield trainer
    trainer.close()


@pytest.fixture
def compiles(monkeypatch):
    """Every ``Lowered.compile`` call made while the test runs."""
    calls = []
    compile_ = jax.stages.Lowered.compile

    def counted(self, *args, **kwargs):
        calls.append(self)
        return compile_(self, *args, **kwargs)

    monkeypatch.setattr(jax.stages.Lowered, "compile", counted)
    return calls


def test_an_untraced_run_compiles_and_writes_nothing(trainer, compiles, monkeypatch, tmp_path):
    monkeypatch.delenv("EDL_PROFILE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    facts = trainer.describe_step()
    assert set(facts) == TODAYS_FACTS
    assert not compiles
    assert not os.listdir(tmp_path)


def test_a_traced_run_writes_the_map_beside_the_trace(trainer, compiles, monkeypatch, tmp_path):
    trace_dir = tmp_path / "profile"
    monkeypatch.setenv("EDL_PROFILE_DIR", str(trace_dir))
    facts = trainer.describe_step()
    assert set(facts) == TODAYS_FACTS | set(step_ops.STEP_BUILT_FIELDS)
    assert len(compiles) == 1
    assert os.listdir(trace_dir) == [step_ops.FILE_NAME]
    with open(trace_dir / step_ops.FILE_NAME) as f:
        ops_map = json.load(f)
    assert ops_map["module"] == "jit_per_device"
    assert 0 < facts["step_ops_named"] == len(ops_map["ops"]) <= facts["step_ops_total"]
    held = set("+".join(ops_map["ops"].values()).split("+"))
    assert {"fwd", "bwd", "remat", "optimizer"} <= held <= set(step_ops.CLASSES)
    # the compiler's account of the step's memory: the state goes in,
    # and on a mesh one process owns it is aliased to what comes out
    state_bytes = sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree_util.tree_leaves(trainer._ts)
    )
    assert facts["step_argument_bytes"] >= state_bytes
    assert 0 < facts["step_alias_bytes"] <= facts["step_argument_bytes"]
    assert facts["step_temp_bytes"] > 0
    # a re-established step replaces the file
    (trace_dir / step_ops.FILE_NAME).write_text("stale")
    trainer.describe_step()
    with open(trace_dir / step_ops.FILE_NAME) as f:
        assert json.load(f) == ops_map
    # and the step it described trains
    loss = trainer.train_step(*_batch(), ROWS, sync=True)
    assert np.isfinite(np.asarray(loss)).all()


# ---------------------------------------------------------------------------
# the same join for a person: tracetool --step-split
# ---------------------------------------------------------------------------


def test_self_times_take_nested_events_out_of_their_parent():
    events = [
        ("while.1", 0, 100),
        ("fusion.2", 10, 30),
        ("fusion.3", 30, 60),
        ("fusion.4", 100, 110),
        ("call.5", 120, 160),
        ("while.6", 125, 155),
        ("fusion.7", 130, 150),
    ]
    assert step_ops.self_times(events) == [50, 20, 30, 10, 10, 10, 20]
    split = step_ops.split_by_class(
        [("%" + n + " = f32[4]{0} op()", s, e) for n, s, e in events],
        {"while.1": "fwd", "fusion.2": "fwd", "fusion.3": "bwd+fwd", "fusion.7": "optimizer"},
    )
    assert split["fwd"] == {("while_f32_4_", "fwd"): [50.0, 1], ("fusion_f32_4_", "fwd"): [20.0, 1]}
    assert split["mixed"] == {("fusion_f32_4_", "bwd+fwd"): [30.0, 1]}
    assert split["optimizer"] == {("fusion_f32_4_", "optimizer"): [20.0, 1]}
    assert sum(ns for ops in split["unnamed"].values() for ns in ops[:1]) == 30.0


@pytest.fixture
def profile_dir(tmp_path):
    """A profile directory as a traced worker leaves it, around the
    trace recorded on the chip (steps 40 and 41 of lm125m-l2048)."""
    directory = tmp_path / "plugins" / "profile" / "2026_01_01"
    directory.mkdir(parents=True)
    with gzip.open(RECORDED) as f:
        (directory / "host.xplane.pb").write_bytes(f.read())
    return tmp_path


def test_step_split_of_a_recorded_trace(profile_dir, capsys):
    from jax.profiler import ProfileData

    (xplane,) = (profile_dir / "plugins" / "profile" / "2026_01_01").iterdir()
    (plane,) = [p for p in ProfileData.from_file(str(xplane)).planes if p.name == "/device:TPU:0"]
    (line,) = [ln for ln in plane.lines if ln.name == "XLA Ops"]
    ops, kernel_ns = {}, {"fwd": 0.0, "bwd": 0.0}
    for e in line.events:
        name = step_ops.instruction_name(e.name)
        if name.startswith("edl_flash_"):
            ops[name] = "fwd" if name.startswith("edl_flash_fwd") else "bwd"
            kernel_ns[ops[name]] += e.duration_ns
        elif name.startswith("multiply_reduce_fusion"):
            ops[name] = "bwd+fwd"
        elif name.startswith("convolution_add_fusion"):
            ops[name] = "bwd+optimizer"
    (profile_dir / step_ops.FILE_NAME).write_text(
        json.dumps({"module": "jit_per_device", "ops": ops})
    )
    told = tracetool.step_split(str(profile_dir))
    assert (told["module"], told["devices"], told["steps"]) == ("jit_per_device", 1, 2)
    # 24 calls of the forward kernel, 32.876 ms in the two steps
    assert told["ms_per_step"]["fwd"] == pytest.approx(kernel_ns["fwd"] / 2e6, rel=1e-9)
    assert told["ms_per_step"]["fwd"] == pytest.approx(16.438, abs=0.001)
    assert told["ms_per_step"]["bwd"] == pytest.approx(kernel_ns["bwd"] / 2e6, rel=1e-9)
    assert told["ms_per_step"]["remat"] == told["ms_per_step"]["optimizer"] == 0.0
    assert list(told["mixed_pairs"]) == ["bwd+fwd", "bwd+optimizer"]  # largest first
    assert sum(told["mixed_pairs"].values()) == pytest.approx(told["ms_per_step"]["mixed"])
    # every op of the two steps is in one bucket: 159.86 ms a step busy
    assert sum(told["ms_per_step"].values()) == pytest.approx(159.86, abs=0.01)
    assert [row[0] for row in told["top"]["bwd"]] == [
        "edl_flash_bwd_dkv_bf16_96_2048_64_", "edl_flash_bwd_dq_bf16_96_2048_64_",
    ]  # fmt: skip
    assert told["top"]["bwd"][0][3] == 12  # calls a step
    assert len(told["top"]["unnamed"]) == 10

    assert tracetool.main(["--step-split", str(profile_dir)]) == 0
    printed = capsys.readouterr().out
    assert "ms a step by class" in printed and "bwd+optimizer" in printed
    assert "edl_flash_bwd_dkv_bf16_96_2048_64_" in printed
    assert tracetool.main(["--step-split", str(profile_dir), "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["steps"] == 2


@pytest.mark.parametrize("missing", ["trace", "map", "module"])
def test_step_split_says_what_it_did_not_find(profile_dir, missing, capsys):
    if missing != "map":
        (profile_dir / step_ops.FILE_NAME).write_text(
            json.dumps({"module": "jit_other" if missing == "module" else "jit_per_device", "ops": {}})
        )
    if missing == "trace":
        (xplane,) = (profile_dir / "plugins" / "profile" / "2026_01_01").iterdir()
        xplane.unlink()
    assert tracetool.main(["--step-split", str(profile_dir)]) == 2
    said = capsys.readouterr().out
    assert {"trace": "no *.xplane.pb", "map": "no edl_step_ops.json", "module": "no execution of module"}[missing] in said


# ---------------------------------------------------------------------------
# a step with the flash kernels says how many steps their grids take (PR 41)
# ---------------------------------------------------------------------------


class Windowed(nn.Module):
    """A global attention layer and one under a window of 20, in tiles
    of 16: four tiles each way at 64 positions."""

    head_size: int = 16

    @nn.compact
    def __call__(self, x, training=False):
        from elasticdl_tpu.ops.flash_attention import flash_attention

        d = self.head_size
        for window in (None, 20):
            q, k, v = (
                nn.Dense(2 * d)(x).reshape(*x.shape[:2], 2, d) for _ in range(3)
            )
            x = x + nn.Dense(16)(
                flash_attention(q, k, v, True, 16, 16, window=window).reshape(
                    *x.shape[:2], 2 * d
                )
            )
        return nn.Dense(1, name="head")(x.mean(axis=1))


@pytest.mark.parametrize(
    "head_size, lane_sums",
    [
        pytest.param(16, 0, id="ones-in-the-spare-lanes"),
        pytest.param(128, 2, id="row-sums-by-lanes"),
    ],
)
def test_step_built_carries_the_flash_grids_steps(
    monkeypatch, head_size, lane_sums
):
    """`describe_step` sums the steps of every flash call in the traced
    step, and those with no tile to compute, and counts the forwards
    that keep their row sums by lanes (a head size that fills its lanes:
    both layers' at 128, none at 16); the worker's `step_built` carries
    all three. A step without the kernels says nothing of them
    (`TODAYS_FACTS` above)."""
    from elasticdl_tpu.utils import profiling
    from elasticdl_tpu.worker.elastic_allreduce_worker import (
        ElasticAllReduceWorker,
    )

    monkeypatch.delenv("EDL_PROFILE_DIR", raising=False)
    monkeypatch.setattr(dist_mod, "ensure_world", lambda s, **k: None)
    monkeypatch.setattr(
        elastic, "build_world_mesh",
        lambda mesh_axes_fn=None: Mesh(np.asarray(jax.devices()[:1]), ("data",)),
    )  # fmt: skip
    trainer = elastic.ElasticDPTrainer(
        Windowed(head_size), _loss, optax.sgd(1e-2)
    )
    trainer.default_minibatch_size = 2
    batch = np.ones((2, 64, 16), np.float32), np.ones((2,), np.float32)
    try:
        trainer.establish(
            WorldSpec(coordinator="", num_processes=1, process_id=0, epoch=0),
            example_batch=batch,
        )
        facts = trainer.describe_step()
        # 2 sequences x 2 heads, three kernels a layer; ten tiles under
        # the diagonal, nine of them under the window
        assert facts["flash_grid_steps"] == 4 * 3 * (10 + 9)
        assert facts["flash_grid_steps_empty"] == 0
        assert facts["flash_fwd_lane_sums"] == lane_sums
        assert set(facts) == TODAYS_FACTS | {
            "flash_grid_steps", "flash_grid_steps_empty", "flash_fwd_lane_sums"
        }  # fmt: skip
        worker = ElasticAllReduceWorker.__new__(ElasticAllReduceWorker)
        worker._step_reported, worker._worker_id = False, 0
        worker.trainer, worker._model = trainer, object()
        emitted = {}
        monkeypatch.setattr(
            profiling.events, "emit",
            lambda kind, **fields: emitted.update(kind=kind, **fields),
        )  # fmt: skip
        worker._report_step_built()
        assert emitted["kind"] == "step_built"
        assert emitted["flash_grid_steps"] == 228
        assert emitted["flash_grid_steps_empty"] == 0
        assert emitted["flash_fwd_lane_sums"] == lane_sums
        assert emitted["attention"] == "pallas-interpret"
    finally:
        trainer.close()
