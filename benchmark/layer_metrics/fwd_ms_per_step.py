"""Device time per step of the compiled step's ops that are the forward
pass: every named part of the op is under ``jvp(`` and under nothing
else (an op on a value whose gradient is stopped is there too).
0.0 on a program that wrote no map of its step's ops (_split.py)."""
import _split

LAYER = "step"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return _split.ms_per_step(run, "fwd")
