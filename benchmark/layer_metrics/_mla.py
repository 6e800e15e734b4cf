"""Shared arithmetic of the readers of attention at unequal head sizes
(a latent attention in its expanded form): the ``edl_flash_mla_*``
kernels' ops in the reduced trace and their least time over the causal
pairs at the two widths. Every function returns None where the program
has no such kernel or fact (the parent commit, on which the driver runs
the readers too)."""

import re

import events as ev
import flash_mla_cost
import flops
from _common import flash_ops as ops  # any kernel's ops by name prefix

KERNELS = tuple(flash_mla_cost.MATMULS)
# edl_flash_mla_fwd_<dtype>_<batch*heads>_<seq>_<one head size>_ : the
# op's name carries its FIRST result's shape (o at d_v, dq and dk at
# d_qk); both sizes are the program's own facts (``step_built``)
_OP = re.compile(r"^_([a-z]+\d*)_(\d+)_(\d+)_(\d+)_$")


def built_with_a_latent_attention(run):
    """The ``step_built`` event of a step that holds ``l`` layers
    (``mla_qk_dim`` among its facts), else None."""
    built = ev.of_kind(run["events"], "step_built")
    if not built or "mla_qk_dim" not in built[0]:
        return None
    return built[0]


def roofline(run, kernel):
    """Least time over the causal pairs (benchmark/flash_mla_cost.py)
    over measured time, in percent, over every call of ``kernel`` in
    the slice."""
    built = built_with_a_latent_attention(run)
    if built is None:
        return None
    least = measured = 0.0
    for name, seconds, calls in ops(run, kernel):
        found = _OP.match(name[len(kernel) :])
        if not found:
            continue
        dtype, bh, length, _ = found.groups()
        cost = flash_mla_cost.unequal_kernel_cost(
            kernel, int(bh), int(length), built["mla_qk_dim"],
            built["mla_v_dim"], itemsize=4 if dtype == "f32" else 2,
        )  # fmt: skip
        least += calls * flops.roofline(*cost, run["device_kind"])[0]
        measured += seconds
    return 100.0 * least / measured if measured else None
