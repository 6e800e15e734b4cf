"""Host-side model init inside the first establish."""
import _common

LAYER = "trainer"
UNIT = "s"
SOURCE = "program_span"
BETTER = "lower"
MOVES = "setup_s"


def read(run):
    e = _common.resize_end(run)
    return None if e is None else e["init_s"]
