"""World formation + host-side init + placement + step acquisition of
the first establish."""
import _common

LAYER = "trainer"
UNIT = "s"
SOURCE = "program_span"
BETTER = "lower"
MOVES = "setup_s"


def read(run):
    e = _common.resize_end(run)
    if e is None:
        return None
    return sum(e[k] for k in ("world_s", "init_s", "place_s", "compile_s"))
