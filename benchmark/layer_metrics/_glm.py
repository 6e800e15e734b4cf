"""What the readers of glm-4.7-flash-ep8's cell share: whether the
built step is one with a multi-token-prediction module, the flash
kernels' ops at the head size its latent attention has, and the device
time of the ops under a named scope of the model's. Every function
returns None where the program has no such fact, kernel or scope (the
parent commit, on which the driver runs the readers too)."""

import json
import os

import _common
import _split
import events as ev

KERNELS = ("edl_flash_fwd", "edl_flash_bwd_dq", "edl_flash_bwd_dkv")
MTP_SCOPE = "edl/mtp"


def built_with_a_prediction_module(run):
    """The ``step_built`` event of a step that holds a prediction
    module (``mtp_layers`` among its facts), else None."""
    built = ev.of_kind(run["events"], "step_built")
    if not built or not built[0].get("mtp_layers"):
        return None
    return built[0]


def equal_head_size(run):
    """The one head size of a latent attention whose q, k and v are
    equally wide (``mla_qk_dim == mla_v_dim`` on ``step_built``): its
    calls are the plain ``edl_flash_*`` kernels. None of every other
    step."""
    built = built_with_a_prediction_module(run)
    if built is None or built.get("mla_qk_dim") != built.get("mla_v_dim"):
        return None
    return built.get("mla_qk_dim")


def at_its_head_size(run):
    """``run`` with its trace narrowed to the ops whose name ends in
    that head size (an op's name ends in its result's shape,
    ``..._<seq>_<head size>_``): what ``_common.flash_ops`` and
    ``_common.flash_roofline`` then read is this latent attention's
    calls and no other's. None of every other step and of an untraced
    run."""
    d, trace = equal_head_size(run), run["trace"]
    if d is None or not trace:
        return None
    kept = {n: s for n, s in trace["op_s"].items() if n.endswith("_%d_" % d)}
    return dict(run, trace=dict(trace, op_s=kept))


def flash_ops(run, kernel):
    narrowed = at_its_head_size(run)
    return _common.flash_ops(narrowed, kernel) if narrowed else []


def flash_roofline(run, kernel):
    """``_common.flash_roofline`` (least time by shapes,
    benchmark/flops.py ``flash_kernel_cost``, over measured time, in
    percent) over the calls of ``kernel`` at the latent attention's
    head size, which is the program's own fact."""
    narrowed = at_its_head_size(run)
    return _common.flash_roofline(narrowed, kernel) if narrowed else None


def scope_s(run, scope):
    """Device seconds, per device, of the slice's ops of the train-step
    module that lie under the model's named scope ``scope``: ``(whole,
    shared)``, the ops every part of which is under it and the ops the
    compiler fused with work from outside it (never divided). The
    program's traced worker says which instruction is which in the map
    it writes beside its trace (``scopes`` of ``edl_step_ops.json``,
    elasticdl_tpu/utils/step_ops.py ``ops_under``); the walk is
    ``_split``'s own, handed that scope's map in the classes' place.
    None of an untraced run and of a program whose map names no such
    scope."""
    trace = run["trace"]
    map_path = _split.map_file(run)
    if not trace or not os.path.exists(map_path):
        return None
    with open(map_path, encoding="utf-8") as f:
        ops_map = json.load(f)
    under = ops_map.get("scopes", {}).get(scope)
    if not under:
        return None
    scope_map = "%s.%s.json" % (map_path[: -len(".json")], scope.replace("/", "_"))
    with open(scope_map, "w", encoding="utf-8") as f:
        json.dump({"module": ops_map["module"], "ops": under}, f)
    walked = _split._walk(
        _split.trace_file(run),
        scope_map,
        ev.steps_before(run["events"], run["windows"][-1]),
        trace["steps"],
    )
    return walked.get("in", 0.0), walked.get(_split.MIXED, 0.0)
