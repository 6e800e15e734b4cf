"""Window selection on a canned events file."""

import json

import events as ev


def _write(path, window_seconds, partial_tail=True):
    """An events file as the master writes it: a resize_end, a
    step_built, then one train_window per entry."""
    t = 1000.0
    lines = [
        {"kind": "resize_end", "id": 1, "ts": t, "init_s": 20.0},
        {"kind": "step_built", "id": 2, "ts": t, "platform": "tpu"},
    ]
    for i, s in enumerate(window_seconds):
        t += s
        lines.append(
            {"kind": "train_window", "id": 3 + i, "ts": t + 4.0, "src_ts": t,
             "steps": 8, "seconds": s, "nonfinite": 0}
        )  # fmt: skip
    with open(path, "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
        if partial_tail:
            # a line the master is still writing
            f.write('{"kind": "train_window", "id": 99, "seco')


def test_partial_line_is_not_read(tmp_path):
    path = tmp_path / "events.jsonl"
    _write(path, [30.0, 1.4, 1.4])
    events = ev.read_events(str(path))
    assert len(ev.of_kind(events, "train_window")) == 3


def test_missing_file_is_no_events(tmp_path):
    assert ev.read_events(str(tmp_path / "nothing")) == []


def test_warmup_windows_are_left_out_and_windows_are_whole(tmp_path):
    path = tmp_path / "events.jsonl"
    _write(path, [30.0, 1.5] + [1.4] * 10)
    events = ev.read_events(str(path))
    got = ev.measured_windows(events, 4.0)
    # 1.4 + 1.4 = 2.8 < 4.0 <= 4.2: three whole windows, never 2.86
    assert [w["seconds"] for w in got] == [1.4, 1.4, 1.4]
    assert got[0]["id"] == 5  # the third window overall
    assert ev.warmup_end(events) == 1000.0 + 30.0 + 1.5
    assert ev.steps_before(events, got[-1]) == 5 * 8


def test_a_window_shorter_than_asked_is_none(tmp_path):
    path = tmp_path / "events.jsonl"
    _write(path, [30.0, 1.5] + [1.4] * 10)
    events = ev.read_events(str(path))
    assert ev.measured_windows(events, 14.0) is not None
    assert ev.measured_windows(events, 14.1) is None


def test_worker_clock_is_preferred(tmp_path):
    assert ev.emitted_at({"ts": 5.0, "src_ts": 1.0}) == 1.0
    assert ev.emitted_at({"ts": 5.0}) == 5.0


def test_a_stall_is_named_and_two_speeds_are_not():
    """The rate keeps every measured window (run.py; the rehearsal pins
    it); ``stall_share`` says how much of them a stall was."""
    import spec

    reader = spec.load_reader("stall_share")
    seconds = [1.32, 1.31, 7.6, 1.33, 1.44, 1.32]
    # 1.44 is a slow window, not a stall; 7.6 is 5.7 medians long
    got = reader.read({"windows": [{"seconds": s} for s in seconds]})
    assert abs(got - 100 * 7.6 / sum(seconds)) < 1e-9
    # two speeds in one run are not stalls of each other
    two = [{"seconds": s} for s in [1.15] * 10 + [1.23] * 15]
    assert reader.read({"windows": two}) == 0.0
