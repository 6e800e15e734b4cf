"""Roofline share of edl_flash_bwd_dkv: least time by shapes
(benchmark/flops.py; compute-bound at these shapes) over measured."""
import _common

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
BETTER = "higher"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return _common.flash_roofline(run, "edl_flash_bwd_dkv")
