"""Device memory in use when establish returns (``resize_end``'s
``state_device_bytes``: the fullest local device) over the train
state's own bytes, f32 parameters and two f32 AdamW moments of the
parameters the configuration's ``cost`` module counts: 1 and a little
when establish left one copy of the state behind, 2 when it left two."""
import _common
import spec

LAYER = "trainer"
UNIT = "ratio"
SOURCE = "program_counter"
BETTER = "lower"
MOVES = "setup_s"

STATE_BYTES_PER_PARAMETER = 12


def read(run):
    e = _common.resize_end(run)
    count = getattr(spec.load_cost(run["config"]["cost"]), "parameters", None)
    if e is None or count is None or e.get("state_device_bytes") is None:
        return None
    return e["state_device_bytes"] / (
        STATE_BYTES_PER_PARAMETER * count(run["config"]["model_params"])
    )
