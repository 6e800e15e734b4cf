"""The job's event file, and the measured window cut from it.

The events are the program's own (``--telemetry_events_path``): the
worker emits them, the master's process appends them as JSON lines.
``src_ts`` is the worker's clock at emission, ``ts`` the master's at
arrival (up to ``--telemetry_report_secs`` later), so every time taken
here is the worker's.
"""

import json

# windows left out before the measured ones: the first holds the step's
# trace, lowering and compile (or cache load), the second is one clean
# window after it
WARMUP_WINDOWS = 2


def read_events(path):
    """Every complete JSON line of ``path``; a last line still being
    written is left for the next read."""
    out = []
    try:
        with open(path, encoding="utf-8") as f:
            for line in f:
                if not line.endswith("\n"):
                    break
                line = line.strip()
                if line:
                    out.append(json.loads(line))
    except FileNotFoundError:
        pass
    return out


def of_kind(events, kind):
    return [e for e in events if e.get("kind") == kind]


def emitted_at(event):
    """The emitting process's clock; events the master emitted itself
    carry no ``src_ts``."""
    return event.get("src_ts", event["ts"])


def measured_windows(events, seconds):
    """The whole ``train_window`` events after the warm-up windows, up
    to and including the one with which their ``seconds`` reach
    ``seconds``; None while the events do not hold that much yet. Never
    a partial window: a window either ended (its event exists) or is
    not counted."""
    windows = of_kind(events, "train_window")[WARMUP_WINDOWS:]
    total = 0.0
    for i, w in enumerate(windows):
        total += w["seconds"]
        if total >= seconds:
            return windows[: i + 1]
    return None


def warmup_end(events):
    """Worker clock at the end of the last warm-up window, or None."""
    windows = of_kind(events, "train_window")
    if len(windows) < WARMUP_WINDOWS:
        return None
    return emitted_at(windows[WARMUP_WINDOWS - 1])


def steps_before(events, window):
    """Optimizer steps the worker had finished when ``window`` ended
    (``window`` included), counted from the job's first step."""
    steps = 0
    for w in of_kind(events, "train_window"):
        steps += w["steps"]
        if w["id"] == window["id"]:
            return steps
    raise ValueError("window %r is not in the events" % window.get("id"))
