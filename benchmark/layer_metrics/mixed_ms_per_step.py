"""Device time per step of the compiled step's ops that hold more than
one class: the compiler fused parts of two or more of forward,
backward, recomputed forward, optimizer and reduce into one op. Never
divided between them: which pairs are fused is the finding (``tracetool
--step-split`` lists them by pair). 0.0 on a program that wrote no map
of its step's ops (_split.py)."""
import _split

LAYER = "step"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return _split.ms_per_step(run, "mixed")
