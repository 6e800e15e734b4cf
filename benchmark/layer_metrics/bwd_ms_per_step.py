"""Device time per step of the compiled step's ops that are the backward
pass: every named part of the op is under ``transpose(`` and not
recomputed. 0.0 on a program that wrote no map of its step's ops
(_split.py)."""
import _split

LAYER = "step"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return _split.ms_per_step(run, "bwd")
