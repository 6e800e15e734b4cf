"""Roofline share of edl_flash_win_bwd_dq: least time over the pairs
INSIDE the band (benchmark/flash_win_cost.py; compute-bound at these
shapes) over measured. A kernel that computed the whole causal
triangle and masked would read at most 43.7% at L = 16,384 under a
window of 4,096."""
import _win

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
BETTER = "higher"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return _win.roofline(run, "edl_flash_win_bwd_dq")
