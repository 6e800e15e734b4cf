"""Device time of the three UNWINDOWED flash kernels per step, of a
step that holds window layers too (its global layers' kernels):
``flash_ms_per_step`` lists its cells by name and its prefix would
catch the windowed calls with the plain ones, so this sums the three
plain kernels by their own names, under a name that lists the cell of
smallthinker-21b-a3b-ep8. None where ``step_built`` names no
``attention_window`` (every other cell, and the parent commit)."""
import _common
import _win

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"

KERNELS = ("edl_flash_fwd", "edl_flash_bwd_dq", "edl_flash_bwd_dkv")


def read(run):
    if _win.built_with_a_window(run) is None:
        return None
    ops = [op for kernel in KERNELS for op in _common.flash_ops(run, kernel)]
    if not ops:
        return None
    return 1e3 * sum(s for _, s, _ in ops) / run["trace"]["steps"]
