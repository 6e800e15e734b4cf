"""Device time of the three flash kernels per step, of a step that
holds state-space layers: ``flash_ms_per_step`` lists its cells by
name, and this is its arithmetic under a name that lists the cell of
granite-4.0-h-micro-vp8. None where ``step_built`` names no
``mamba_layers`` (every other cell, and the parent commit)."""
import events as ev
import flash_ms_per_step

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    built = ev.of_kind(run["events"], "step_built")
    if not built or not built[0].get("mamba_layers"):
        return None
    return flash_ms_per_step.read(run)
