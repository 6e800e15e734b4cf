"""Roofline share of edl_flash_bwd_dkv at head size 256 (a latent
attention whose q, k and v are equally wide; the size is the program's
own fact): least time by shapes (benchmark/flops.py; compute-bound at
these shapes) over measured."""
import _glm

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
BETTER = "higher"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return _glm.flash_roofline(run, "edl_flash_bwd_dkv")
