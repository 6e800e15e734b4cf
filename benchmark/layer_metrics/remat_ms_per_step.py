"""Device time per step of the compiled step's ops that are a recomputed
forward: every named part of the op is under ``rematted_computation``,
jax.checkpoint's second run of a forward inside the backward pass.
0.0 where nothing is recomputed, and on a program that wrote no map of
its step's ops (_split.py)."""
import _split

LAYER = "step"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return _split.ms_per_step(run, "remat")
