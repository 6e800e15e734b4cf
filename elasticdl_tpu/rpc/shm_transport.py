"""Shared-memory transport for co-located PS pods (docs/wire.md).

On a loopback fleet (PS pods scheduled on the worker's host — the
co-located placement k8s topology hints produce for exactly this
reason) the gRPC payload path still pays serialization into a `bytes`
request, the C-core's own copies, and the receive-side reassembly.
This module moves the PAYLOAD into a client-owned ring of
``multiprocessing.shared_memory`` slots negotiated at connect time via
a ``transport_hello`` RPC; the gRPC message then carries only
``{segment name, slot, generation, length}``, ~100 bytes regardless of
tensor sizes. The scatter-gather packer (rpc/core.plan_message /
pack_message_into) writes frames STRAIGHT into the slot — one memcpy
from the source arrays into shared memory per direction, zero
intermediate `bytes` — and the receiver decodes read-only views in
place (common/tensor deserialization contract).

Protocol:

- ``transport_hello``: the client creates a ring (one per channel) and
  sends ``{name, n_slots, slot_size, host}``; the server attaches only
  when the host fingerprint (hostname + kernel boot id) matches its
  own and the attach succeeds — anything else answers
  ``accepted=False`` and the channel permanently falls back to the
  bytes path. The ring is REQUEST AND RESPONSE transport: the server
  overwrites the request slot with its reply (the slot stays
  client-owned for the whole round trip).
- Each slot carries a 16-byte header ``(u64 generation, u64 length)``.
  The client stamps a fresh generation per call; the server validates
  it before dispatch and stamps ``generation | RESP_BIT`` on the
  reply, so a retried control RPC can never decode a response as a
  request (it reads a mismatch and answers ``_shm_error`` WITHOUT
  dispatching — the retry then goes inline, which is safe exactly
  because nothing was dispatched).
- Fallbacks are per-call and lossless: payload too big for a slot or
  slot pool exhausted -> inline bytes path; ``_shm_error`` (server
  restarted, ring unknown) -> channel disables itself and resends
  inline; transport error mid-call (deadline on a dead pod) ->
  the slot is QUARANTINED, never reused, because the server might
  still write into it after the client moved on.
- Lifetime: the creator unlinks on ``close()`` and at interpreter
  exit (atexit); the server's registry unlinks every attached ring on
  ``close()``, which is what reclaims segments of clients that were
  SIGKILLed mid-call (POSIX keeps /dev/shm names until someone
  unlinks; the memory itself dies with the last mapping).

Slot replies decode with a :class:`~elasticdl_tpu.common.tensor.
WireArena` whose ``release()`` recycles the slot — consumers
(worker/ps_client.py) materialize anything they retain, then release.
"""

import atexit
import socket
import struct
import threading

from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.common.tensor import WireArena

_NAME_PREFIX = "edlw-"
_SLOT_HDR = 16  # u64 generation | u64 payload length
_RESP_BIT = 1 << 62  # stamped into the generation of a reply header
_MAX_SLOTS = 64
_MAX_SLOT_BYTES = 256 << 20
_MAX_RING_BYTES = 1 << 30


def host_fingerprint():
    """Identity of this kernel + hostname: equal fingerprints mean the
    peers can plausibly see the same /dev/shm namespace (a mismatching
    container mount namespace still fails at attach, which the hello
    treats the same way: bytes-path fallback)."""
    try:
        with open("/proc/sys/kernel/random/boot_id") as f:
            boot = f.read().strip()
    except OSError:
        boot = ""
    return "%s|%s" % (socket.gethostname(), boot)


class ShmRing:
    """A fixed-geometry ring of payload slots in one shared segment.

    Created (and owned) by the CLIENT; the server attaches by name.
    All slot bookkeeping beyond the 16-byte in-segment headers lives on
    the client side, so the segment itself needs no cross-process
    synchronization — a slot is exclusively the client's except during
    the window between sending the control RPC and receiving its
    reply, when it is exclusively the server's."""

    def __init__(self, n_slots, slot_size, name=None):
        from multiprocessing import shared_memory

        self.n_slots = int(n_slots)
        self.slot_size = int(slot_size)
        self._stride = _SLOT_HDR + self.slot_size
        size = self._stride * self.n_slots
        self.created = name is None
        if self.created:
            import uuid

            for _attempt in range(8):
                candidate = _NAME_PREFIX + uuid.uuid4().hex[:16]
                try:
                    self._shm = shared_memory.SharedMemory(
                        name=candidate, create=True, size=size
                    )
                    break
                except FileExistsError:
                    continue
            else:
                raise OSError("could not allocate a unique shm ring name")
        else:
            if not name.startswith(_NAME_PREFIX):
                raise ValueError("not an elasticdl wire segment: %r" % name)
            self._shm = shared_memory.SharedMemory(name=name)
            if self._shm.size < size:
                self._shm.close()
                raise ValueError("segment smaller than advertised ring")
            # CPython < 3.13 registers ATTACHED segments with the
            # resource tracker too, which would unlink the creator's
            # live segment when this (server) process exits — detach
            # the tracker, the creator owns the name (_dispose
            # re-balances the ledger before any unlink)
            self._tracker_call("unregister")
        self.name = self._shm.name
        self._destroyed = False

    def _tracker_call(self, op):
        from multiprocessing import resource_tracker

        try:
            getattr(resource_tracker, op)(self._shm._name, "shared_memory")
            return True
        except (AttributeError, KeyError, ValueError, OSError) as err:
            logger.debug("shm resource-tracker %s skipped: %s", op, err)
            return False

    def payload_view(self, slot):
        """Writable memoryview of one slot's payload area."""
        base = slot * self._stride + _SLOT_HDR
        return self._shm.buf[base : base + self.slot_size]

    def write_header(self, slot, generation, length):
        struct.pack_into(
            "<QQ", self._shm.buf, slot * self._stride, generation, length
        )

    def read_header(self, slot):
        return struct.unpack_from("<QQ", self._shm.buf, slot * self._stride)

    def _dispose(self, unlink):
        if self._destroyed:
            return
        self._destroyed = True
        if unlink:
            # balance the tracker ledger BEFORE unlink: the attach-time
            # detach (and same-process create+attach topologies —
            # the tests — where the set-backed ledger
            # collapses the two registrations into one) can leave this
            # name untracked, and unlink()'s built-in unregister would
            # then crash the tracker's exit sweep. register is a
            # set-add: always safe, leaves exactly one entry for
            # unlink to consume.
            self._tracker_call("register")
            try:
                self._shm.unlink()
            except FileNotFoundError:
                # the peer unlinked first; drop our (now dangling)
                # tracker entry so exit-time cleanup stays silent
                self._tracker_call("unregister")
        try:
            self._shm.close()
        except BufferError:
            # numpy views into the segment are still alive somewhere;
            # the mapping dies with the process, and the name is
            # already gone above — nothing can leak
            logger.debug(
                "shm ring %s close deferred: exported views still live",
                self.name,
            )

    def destroy(self):
        """Close this mapping; unlink the name if we created it.

        Unlink only removes the /dev/shm NAME — the memory lives until
        the last mapping drops, so a consumer still holding
        un-materialized views keeps valid pages and the OS reclaims at
        process exit."""
        self._dispose(unlink=self.created)

    def reclaim(self):
        """Server-side reclamation of a (possibly dead) client's ring:
        unlink the name regardless of who created it, then close —
        the path that frees segments of SIGKILLed clients."""
        self._dispose(unlink=True)


class ShmChannel:
    """Client-side channel: an rpc.core ``Client`` plus the negotiated
    shared-memory payload path, with per-call bytes-path fallback.

    Thread-safe for the PSClient fan-out pool: slot accounting rides
    one lock; the RPCs themselves always run outside it. Retry safety
    matches the PR-2 invariants — the control RPC for ``method`` is
    retriable exactly when ``method`` is idempotent, and every
    ``_shm_error`` reply is answered by the server BEFORE dispatch, so
    the inline resend it triggers can never double-apply."""

    def __init__(self, client, n_slots=4, slot_mb=8):
        self._client = client
        self._n_slots = max(1, int(n_slots))
        self._slot_size = max(1, int(slot_mb)) << 20
        self._mu = threading.Lock()
        self._state = "new"  # new | negotiating | on | off
        self._ring = None
        self._free = list(range(self._n_slots))
        self._gen = 0
        # calls currently between _acquire and _leave: a concurrent
        # _disable (peer _shm_error, close()) must not destroy the
        # ring out from under them — it parks it in _retired instead
        self._users = 0
        self._retired = None
        self.stats = {"shm": 0, "inline": 0, "quarantined": 0}

    # -- negotiation ----------------------------------------------------

    def _ensure(self):
        """Current state, driving the one-shot hello on first use.

        Exactly one thread claims the negotiation; the RPC runs outside
        the lock (edlint R5), and racers use the inline path until the
        state settles."""
        with self._mu:
            if self._state != "new":
                return self._state
            self._state = "negotiating"
        state, ring = "off", None
        try:
            ring = ShmRing(self._n_slots, self._slot_size)
            atexit.register(ring.destroy)  # crash-safe unlink floor
            resp = self._client.call(
                "transport_hello",
                name=ring.name,
                n_slots=self._n_slots,
                slot_size=self._slot_size,
                host=host_fingerprint(),
            )
            if resp.get("accepted"):
                state = "on"
            else:
                logger.info(
                    "shm transport declined (%s); using the bytes path",
                    resp.get("reason", "unspecified"),
                )
        except Exception as err:  # noqa: BLE001 — any failure => bytes path
            logger.info(
                "shm transport negotiation failed (%s); using the "
                "bytes path",
                err,
            )
        if state != "on" and ring is not None:
            ring.destroy()
            ring = None
        with self._mu:
            self._ring = ring
            self._state = state
        return state

    # -- slot accounting ------------------------------------------------

    def _acquire(self):
        """(ring, slot, generation) or None when the pool is empty or
        the channel is not (yet) on. A successful claim counts the
        caller as a ring user until its matching :meth:`_leave`."""
        with self._mu:
            if self._state != "on" or not self._free:
                return None
            slot = self._free.pop()
            self._gen += 1
            self._users += 1
            return self._ring, slot, self._gen

    def _leave(self):
        """The caller is done touching ring memory (its reply views,
        if any, keep their own mapping alive); the last user out
        destroys a ring a concurrent _disable retired."""
        with self._mu:
            self._users -= 1
            ring = None
            if self._users == 0 and self._retired is not None:
                ring, self._retired = self._retired, None
        if ring is not None:
            ring.destroy()

    def _release(self, slot):
        with self._mu:
            if self._state == "on" and slot not in self._free:
                self._free.append(slot)

    def _quarantine(self, slot):
        """Never reuse ``slot``: after a transport error mid-call the
        server may still write its late reply into it, and a fresh
        request there could be torn under that write. Slots are cheap;
        a channel that loses all of them degrades to the bytes path."""
        with self._mu:
            self.stats["quarantined"] += 1

    def _disable(self):
        """Stop offering shm on this channel. The ring is destroyed
        only once no call is between _acquire and _leave — a fan-out
        sibling mid-call must degrade to the bytes path, not crash on
        a closed mapping."""
        with self._mu:
            self._state = "off"
            ring, self._ring = self._ring, None
            if ring is not None and self._users:
                self._retired, ring = ring, None
        if ring is not None:
            ring.destroy()

    # -- the call path --------------------------------------------------

    def _inline(self, method, fields, plan=None):
        """The bytes path, with the PR-2 retry guard computed in ONE
        place; an already-built plan rides through so fallbacks never
        plan a message twice."""
        with self._mu:
            self.stats["inline"] += 1
        return self._client.call(
            method,
            _retriable=(method != "push_gradient"),
            _plan=plan,
            **fields
        )

    def call(self, method, /, **fields):
        # positional-only: a wire field may itself be named "method"
        # (get_model's GetModelMethod selector) and must land in
        # ``fields``, not collide with the RPC name
        from elasticdl_tpu.rpc.core import (
            pack_message_into,
            plan_message,
            unpack_message,
        )
        from elasticdl_tpu.utils import profiling

        # span context rides the SLOT payload (the control message only
        # carries the slot spec), so inject before planning; the inline
        # fallbacks reuse these fields and Client.call skips its own
        # injection when the key is already present
        sctx = profiling.wire_span_context()
        if sctx is not None and "_sctx" not in fields:
            fields["_sctx"] = sctx

        if self._ensure() != "on":
            return self._inline(method, fields)
        plan = plan_message(fields)
        claim = self._acquire() if plan.total <= self._slot_size else None
        if claim is None:
            # payload bigger than a slot, or every slot in flight /
            # quarantined: the bytes path is always correct
            return self._inline(method, fields, plan)
        ring, slot, gen = claim
        try:
            payload = ring.payload_view(slot)
            pack_message_into(plan, payload)
            ring.write_header(slot, gen, plan.total)
            try:
                ctrl = self._client.call(
                    method,
                    _retriable=(method != "push_gradient"),
                    _shm_req={
                        "name": ring.name,
                        "slot": slot,
                        "gen": gen,
                        "len": plan.total,
                    },
                )
            except BaseException:
                self._quarantine(slot)
                raise
            if "_shm_error" in ctrl:
                # answered BEFORE dispatch (ring unknown / stale
                # generation — e.g. a restarted PS lost its
                # attachments): resend inline, and stop offering shm
                # on this channel
                logger.warning(
                    "shm transport rejected by server (%s); falling "
                    "back to the bytes path",
                    ctrl["_shm_error"],
                )
                self._release(slot)
                self._disable()
                return self._inline(method, fields, plan)
            spec = ctrl.get("_shm_resp")
            if spec is None:
                # reply didn't fit a slot: it arrived inline, slot done
                self._release(slot)
                with self._mu:
                    self.stats["shm"] += 1
                return ctrl
            hgen, hlen = ring.read_header(slot)
            if spec.get("gen") != gen or hgen != (gen | _RESP_BIT) or (
                hlen != spec.get("len")
            ):
                self._quarantine(slot)
                self._disable()
                raise RuntimeError(
                    "shm reply generation mismatch on %s slot %d "
                    "(protocol desync; channel disabled)"
                    % (ring.name, slot)
                )
            view = payload[: spec["len"]].toreadonly()
            arena = WireArena(view, on_release=lambda: self._release(slot))
            with self._mu:
                self.stats["shm"] += 1
            return unpack_message(view, arena=arena)
        finally:
            # reply views (if any) hold their own mapping; this only
            # ends the window where ring HEADERS/slots may be touched,
            # letting a concurrent _disable's deferred destroy proceed
            self._leave()

    def close(self):
        self._disable()

    @property
    def state(self):
        with self._mu:
            return self._state


# ---------------------------------------------------------------------------
# server side
# ---------------------------------------------------------------------------


class ShmEndpointRegistry:
    """Server-side table of client rings attached via transport_hello.

    ``close()`` reclaims EVERY attached ring (unlink + close) — the
    path that frees segments of clients SIGKILLed mid-call, since a
    dead creator's atexit never ran."""

    def __init__(self, writable_request_views=False):
        self._mu = threading.Lock()
        self._rings = {}
        self._fingerprint = host_fingerprint()
        # device-resident PS shards opt in (docs/ps_device.md): request
        # payloads decode as WRITABLE slot views so gradients can
        # dlpack-import to device with zero copies (numpy cannot export
        # a read-only buffer). Safe under the existing slot contract —
        # the handler consumes the request fully before the reply
        # overwrites the slot (the device apply blocks on its outputs)
        # — but it forfeits the codec's mutation guard, so it is never
        # the default.
        self._writable_request_views = bool(writable_request_views)

    def hello(self, req):
        name = req.get("name", "")
        n_slots = int(req.get("n_slots", 0))
        slot_size = int(req.get("slot_size", 0))
        if req.get("host") != self._fingerprint:
            return {"accepted": False, "reason": "cross-host"}
        if not isinstance(name, str) or not name.lstrip("/").startswith(
            _NAME_PREFIX
        ):
            return {"accepted": False, "reason": "bad segment name"}
        if not (
            0 < n_slots <= _MAX_SLOTS
            and 0 < slot_size <= _MAX_SLOT_BYTES
            and n_slots * slot_size <= _MAX_RING_BYTES
        ):
            return {"accepted": False, "reason": "ring geometry out of bounds"}
        try:
            ring = ShmRing(n_slots, slot_size, name=name)
        except (OSError, ValueError) as err:
            return {"accepted": False, "reason": "attach failed: %s" % err}
        with self._mu:
            old = self._rings.pop(name, None)
            self._rings[name] = ring
        if old is not None:
            old.reclaim()  # same client re-negotiated: the old attach goes
        return {"accepted": True}

    def _resolve(self, name):
        with self._mu:
            return self._rings.get(name)

    def wrap(self, fn):
        """Route ``_shm_req`` control messages through the slot; plain
        requests pass straight to ``fn``. Every ``_shm_error`` return
        happens BEFORE ``fn`` runs (the client's inline resend safety).
        """
        from elasticdl_tpu.rpc.core import (
            pack_message_into,
            plan_message,
            unpack_message,
        )

        def handler(req):
            spec = req.get("_shm_req") if isinstance(req, dict) else None
            if spec is None:
                return fn(req)
            ring = self._resolve(spec.get("name", ""))
            if ring is None:
                return {"_shm_error": "unknown ring"}
            slot, gen = int(spec.get("slot", -1)), int(spec.get("gen", -1))
            length = int(spec.get("len", -1))
            if not 0 <= slot < ring.n_slots:
                return {"_shm_error": "slot out of range"}
            hgen, hlen = ring.read_header(slot)
            if hgen != gen or hlen != length or not (
                0 <= length <= ring.slot_size
            ):
                return {"_shm_error": "stale generation"}
            payload = ring.payload_view(slot)
            if self._writable_request_views:
                request = unpack_message(
                    payload[:length], writable=True
                )
            else:
                request = unpack_message(payload[:length].toreadonly())
            reply = fn(request) or {}
            # the handler is done with the request (the audited PS
            # servicer materializes anything it retains), so the slot
            # can carry the reply back in place
            del request
            plan = plan_message(reply)
            if plan.total > ring.slot_size:
                return reply  # inline fallback for oversized replies
            pack_message_into(plan, payload)
            ring.write_header(slot, gen | _RESP_BIT, plan.total)
            return {
                "_shm_resp": {"slot": slot, "gen": gen, "len": plan.total}
            }

        return handler

    def close(self):
        with self._mu:
            rings, self._rings = list(self._rings.values()), {}
        for ring in rings:
            ring.reclaim()


def install_shm_endpoint(
    methods, hello_extra=None, writable_request_views=False
):
    """Wrap a ``{name: fn}`` RPC table with the shared-memory endpoint.

    Returns ``(methods, registry)`` where ``methods`` additionally
    serves ``transport_hello``; call ``registry.close()`` at server
    stop to reclaim attached (including orphaned) rings.

    ``hello_extra``: extra fields merged into every hello reply —
    the PS serves its ``shard_epoch`` boot id here so a reconnecting
    co-located client learns the incarnation at negotiation time,
    before its first data-plane round (docs/ps_recovery.md).

    ``writable_request_views``: device-resident PS shards only — see
    :class:`ShmEndpointRegistry`."""
    registry = ShmEndpointRegistry(
        writable_request_views=writable_request_views
    )
    wrapped = {name: registry.wrap(fn) for name, fn in methods.items()}
    if hello_extra:
        extra = dict(hello_extra)

        def hello(req):
            resp = dict(registry.hello(req) or {})
            resp.update(extra)
            return resp

        wrapped["transport_hello"] = hello
    else:
        wrapped["transport_hello"] = registry.hello
    return wrapped, registry
