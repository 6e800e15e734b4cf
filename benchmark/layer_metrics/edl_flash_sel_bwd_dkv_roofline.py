"""Roofline share of edl_flash_sel_bwd_dkv: least time over the pairs the
selection KEEPS (benchmark/flash_sel_cost.py; compute-bound at these
shapes) over measured. The kernel computes every causal pair and masks,
so at L = 8,192 and top-k 2,048 it reads at most 44% of what
edl_flash_bwd_dkv reads of its own roofline."""
import _sel

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
BETTER = "higher"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return _sel.roofline(run, "edl_flash_sel_bwd_dkv")
