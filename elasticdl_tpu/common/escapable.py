"""Daemon-thread escapable calls for wedge-prone device interactions.

A dead peer (a gloo socket whose other side is gone, a lost host) can
block device calls forever inside C++ where no Python timeout reaches.
This leaf module (no framework imports) provides the machinery the
elastic trainer (parallel/elastic.py) runs its device calls through.
"""


class EscapeTimeout(Exception):
    """:func:`escapable_call` abandoned its device thread (hard timeout
    elapsed or the abort probe signalled)."""


def escapable_call(
    fn,
    timeout=None,
    should_abort=None,
    abort_after=2.0,
    abort_interval=1.0,
    poll=0.05,
):
    """Run a device-touching callable on a sacrificial daemon thread so
    the calling thread can escape a wedged accelerator backend.

    ``fn`` runs on a DAEMON thread (daemon, not an executor:
    concurrent.futures joins its workers at interpreter exit, so one
    abandoned wedged thread would hang the process forever at
    shutdown); the caller polls its result queue and gives up by
    raising :class:`EscapeTimeout` when ``timeout`` seconds elapse or
    ``should_abort()`` returns True (probed every ``abort_interval`` s
    after an initial ``abort_after`` s grace; probe exceptions read as
    "don't abort"). The abandoned thread stays parked in the dead call
    — the process must treat the backend as wedged from then on
    (ElasticDPTrainer sets ``_wedged``).

    Returns ``fn()``'s value; re-raises ``fn``'s exception."""
    import queue as _queue
    import threading as _threading
    import time as _time

    out = _queue.Queue(maxsize=1)

    def runner():
        try:
            out.put((True, fn()))
        except BaseException as e:  # noqa: BLE001 - re-raised below
            out.put((False, e))

    t = _threading.Thread(target=runner, name="edl-device", daemon=True)
    t.start()
    t0 = _time.monotonic()
    last_check = t0
    while True:
        try:
            ok, value = out.get(timeout=poll)
        except _queue.Empty:
            pass
        else:
            if ok:
                return value
            raise value
        now = _time.monotonic()
        if timeout is not None and now - t0 >= timeout:
            raise EscapeTimeout(
                "device call still blocked after %.1fs" % timeout
            )
        if (
            should_abort is not None
            and now - t0 >= abort_after
            and now - last_check >= abort_interval
        ):
            last_check = now
            try:
                moved_on = should_abort()
            except Exception:
                moved_on = False
            if moved_on:
                raise EscapeTimeout("abort probe signalled")
