"""Roofline share of edl_flash_mla_bwd_dq: least time over the causal
pairs at the two head sizes (benchmark/flash_mla_cost.py: a product
with q or k at 192, one with v or dO at 128; compute-bound at these
shapes) over measured. What the kernel pads 192 to is not counted, so
100% is out of reach by that much."""
import _mla

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
BETTER = "higher"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return _mla.roofline(run, "edl_flash_mla_bwd_dq")
