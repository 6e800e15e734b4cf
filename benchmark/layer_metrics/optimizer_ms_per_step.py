"""Device time per step of the compiled step's ops that are the
optimizer: every named part of the op is under the scope
``edl/optimizer`` (the update, its application and the selects that
build the new state). 0.0 on a program that wrote no map of its step's
ops (_split.py)."""
import _split

LAYER = "step"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return _split.ms_per_step(run, "optimizer")
