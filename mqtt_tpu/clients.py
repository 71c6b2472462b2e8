"""Per-client connection state machine: buffered async reads, a single
writer task draining a bounded outbound queue, packet-id allocation,
keepalive deadlines, topic aliases, and session state.

Behavioral parity with reference ``clients.go``. The reference's
goroutine-per-connection becomes one asyncio reader task plus one writer
task per client; the bounded ``outbound`` channel becomes an
``asyncio.Queue`` whose ``put_nowait``-full path reproduces the reference's
drop-on-slow-consumer semantics (server.go:1099-1110).
"""

from __future__ import annotations

import asyncio
import collections
import functools
import logging
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Awaitable, Callable, Optional

from . import packets as pkts
from .inflight import Inflight
from .packets import (
    ERR_PACKET_TOO_LARGE,
    ERR_QUOTA_EXCEEDED,
    Code,
    FixedHeader,
    Packet,
    Properties,
    UserProperty,
)
from .topics import OutboundTopicAliases, Subscriptions, TopicAliases
from .utils import LockedMap
from .utils.loopwitness import DEFAULT_LOOP_PLANE as _LOOP_PLANE
from .utils.mempool import get_buffer, put_buffer

DEFAULT_KEEPALIVE = 10  # default connection keepalive seconds (clients.go:25)
DEFAULT_CLIENT_PROTOCOL_VERSION = 4  # (clients.go:26)
MINIMUM_KEEPALIVE = 5  # below this a warning is logged (clients.go:27)
# a socket's cork (Client._cork) is written out early once it holds this
# many bytes: large payloads are never joined into one buffer
CORK_MAX_BYTES = 64 * 1024
# the first bytes of the frames an ingest run takes (server.ingest_run):
# a PUBLISH of QoS0 or QoS1 without RETAIN, and QoS1 with DUP, which
# changes nothing inbound. The rest of the 0x3_ bytes are RETAIN, QoS2
# and the two the fixed header refuses (QoS3, DUP at QoS0).
RUN_FIRST_BYTES = frozenset((0x30, 0x32, 0x3A))
# the frame an ack run takes (server.ack_run): a PUBACK that is its
# packet id and nothing else, v3.1.1's only form and v5's short one
# (reason 0, no properties)
ACK_FIRST_BYTE = 0x40
ACK_REMAINING = 2


class ConnectionClosedError(Exception):
    """The client connection is not open (reference ErrConnectionClosed)."""


class OutboundQueue:
    """A thread-safe bounded outbound queue with asyncio.Queue's
    data-plane surface (``put_nowait``/``QueueFull``, awaitable
    ``get``, ``full``/``qsize``/``empty``).

    asyncio.Queue is loop-affine: ``put_nowait`` wakes waiters with a
    plain ``call_soon``, which is illegal from any other thread. Under
    the event-loop shard fabric (mqtt_tpu.shards) a publisher's fan-out
    runs on ITS shard's loop and enqueues onto subscribers owned by
    OTHER shards — so the queue itself goes thread-safe: a lock-guarded
    deque plus a single-consumer wakeup future that cross-thread
    producers resolve via ``call_soon_threadsafe`` on the consumer's
    loop. Single-loop brokers pay one uncontended lock acquire per
    enqueue/dequeue and keep identical semantics.
    """

    __slots__ = ("maxsize", "_items", "_lock", "_waiter", "_witness_loop")

    def __init__(self, maxsize: int = 0) -> None:
        self.maxsize = maxsize
        self._items: collections.deque = collections.deque()
        self._lock = threading.Lock()
        # the single consumer's parked (loop, future), or None; the
        # write loop is the only get() caller, so one slot suffices
        self._waiter: Optional[tuple] = None
        # owning-loop identity stamped by the first witnessed get()
        # (mqtt_tpu.utils.loopwitness); None while unobserved/disarmed
        self._witness_loop: Optional[asyncio.AbstractEventLoop] = None

    def qsize(self) -> int:
        return len(self._items)

    def empty(self) -> bool:
        return not self._items

    def full(self) -> bool:
        return 0 < self.maxsize <= len(self._items)

    @staticmethod
    def _wake(fut: "asyncio.Future") -> None:
        if not fut.done():
            fut.set_result(None)

    def put_nowait(self, item: Any) -> None:
        """Enqueue from ANY thread; raises ``asyncio.QueueFull`` past
        the bound (the drop-on-slow-consumer contract is unchanged)."""
        plane = _LOOP_PLANE
        if plane.active:
            w = plane.witness
            if w is not None:
                w.note_crossing(
                    "outbound_queue", "put_local", "put_cross",
                    self._witness_loop,
                )
        wake = None
        with self._lock:
            if 0 < self.maxsize <= len(self._items):
                raise asyncio.QueueFull()
            self._items.append(item)
            if self._waiter is not None:
                wake, self._waiter = self._waiter, None
        if wake is not None:
            loop, fut = wake
            try:
                running = asyncio.get_running_loop()
            except RuntimeError:
                running = None
            if loop is running:
                self._wake(fut)
            else:
                try:
                    loop.call_soon_threadsafe(self._wake, fut)
                except RuntimeError:
                    pass  # consumer loop closed; the writer task is gone

    async def get(self) -> Any:
        """Dequeue (single consumer: the client's write loop)."""
        plane = _LOOP_PLANE
        if plane.active:
            w = plane.witness
            if w is not None:
                if self._witness_loop is None:
                    self._witness_loop = asyncio.get_running_loop()
                w.check_owner(
                    "outbound_queue", "get_owner", self._witness_loop
                )
        while True:
            with self._lock:
                if self._items:
                    return self._items.popleft()
                loop = asyncio.get_running_loop()
                fut: asyncio.Future = loop.create_future()
                self._waiter = (loop, fut)
            try:
                await fut
            except asyncio.CancelledError:
                with self._lock:
                    if self._waiter is not None and self._waiter[1] is fut:
                        self._waiter = None
                raise


class ScanGate:
    """Coalesce frame scans from read loops that wake in the same
    event-loop tick into ONE native multi-buffer call (ISSUE 13's
    read-side decode batching — mqtt_native.mqtt_frame_scan_multi).

    Read loops register their buffer and await a future; a
    ``call_soon`` flush runs after every currently-ready callback (i.e.
    after every read loop that woke this tick has registered), scans
    all buffers in one GIL-released pass, and resolves the futures.
    Single-scanner ticks pay one loop-callback hop and nothing else;
    without the native library the flush falls back to per-buffer
    scans. Opt-in via ``Options.scan_coalesce``."""

    def __init__(self) -> None:
        self._pending: list = []
        self._scheduled = False
        self.batches = 0  # flush calls issued (observability)
        self.scans = 0  # buffers scanned through the gate

    def scan(
        self, buf: bytearray, max_frames: int, max_packet_size: int
    ) -> "asyncio.Future":
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self._pending.append((buf, fut))
        self._max_frames = max_frames
        self._max_packet_size = max_packet_size
        if not self._scheduled:
            self._scheduled = True
            loop.call_soon(self._flush)
        return fut

    def _flush(self) -> None:
        from .native import frame_scan, frame_scan_multi

        self._scheduled = False
        pending, self._pending = self._pending, []
        if not pending:
            return
        self.batches += 1
        self.scans += len(pending)
        results = None
        try:
            results = frame_scan_multi(
                [buf for buf, _ in pending],
                max_frames=self._max_frames,
                max_packet_size=self._max_packet_size,
            )
        except Exception as e:
            for _buf, fut in pending:
                if not fut.done():
                    fut.set_exception(e)
            return
        if results is None:
            # no native library: per-buffer scans, same contract
            for buf, fut in pending:
                if fut.done():
                    continue
                try:
                    fut.set_result(
                        frame_scan(
                            buf, max_frames=self._max_frames,
                            max_packet_size=self._max_packet_size,
                        )
                    )
                except Exception as e:
                    fut.set_exception(e)
            return
        for (_buf, fut), res in zip(pending, results):
            if not fut.done():
                fut.set_result(res)


# the most one read of the stream feeder brings (asyncio's stream
# reader's own limit), and what a gated or throttled connection may
# buffer before its transport stops reading: the stream reader pauses at
# twice its limit
READ_SIZE = 65536
READ_HIGH_WATER = 2 * READ_SIZE
# where ``_DirectFeed._pump`` takes up its turn
_SCAN, _SETTLE, _READ = range(3)


class _DirectFeed(asyncio.BufferedProtocol):
    """The direct feeder of ``Client.read``: the broker's own protocol on
    a connection's transport, once the CONNECT handshake is through.

    The transport receives into one buffer a thread (``get_buffer``: no
    allocation a read, where a plain protocol's transport allocates
    256 KiB for every ``recv`` and the allocator maps, shrinks and unmaps
    it: three system calls beside the one that reads).
    ``buffer_updated`` appends what came to the connection's buffer and
    runs the scan, the frame loop and what follows them
    (``Client._take_frames``, ``_settle_scan``: the stream feeder's own)
    inside the transport's callback. The three waits of the read side
    are a handle each at most, and none is made for a read that needs
    none:

    - **the gate**: while ``cl._staged`` is non-zero no frame of the
      connection is handled; bytes that come meanwhile are buffered
      (past ``READ_HIGH_WATER`` the transport stops reading). The
      completion that takes ``_staged`` to zero calls
      ``cl._staged_waiter``, which schedules the rest of the turn with
      ``call_soon``: a turn of the loop of its own, never inside a
      completion slice.
    - **the keepalive**: one ``call_later`` handle a connection, armed
      for ``cl._deadline`` and re-armed when it fires and the deadline
      has moved ([MQTT-3.1.2-24]).
    - **the THROTTLE lever**: a positive ``read_delay`` stops the
      transport reading until a ``call_later`` resumes it.

    ``run`` resolves on a clean end and fails with the exception the
    stream feeder would have raised; after that no byte of the
    connection is scanned. ``pause_writing``, ``resume_writing``,
    ``eof_received`` and ``connection_lost`` are passed on to the
    ``StreamReaderProtocol`` the transport had, which the connection's
    ``StreamWriter`` still hangs on (``drain``, ``wait_closed``)."""

    # the receive buffer of the thread's connections (an event loop runs
    # in one thread): the transport fills it and ``buffer_updated`` has
    # copied out of it before it returns
    _received = threading.local()

    def __init__(self, cl: "Client", transport: asyncio.Transport, packet_handler) -> None:
        from .native import MAX_FRAMES_PER_SCAN, frame_scan, varint_decode

        view = getattr(self._received, "view", None)
        if view is None:
            view = self._received.view = memoryview(bytearray(READ_SIZE))
        self._view = view
        self._cl = cl
        self._transport = transport
        self._stream = transport.get_protocol()
        self._handler = packet_handler
        self._frame_scan = frame_scan
        self._varint_decode = varint_decode
        self._max_frames = MAX_FRAMES_PER_SCAN
        self._caps = cl.ops.options.capabilities
        self._loop = asyncio.get_running_loop()
        self._done: asyncio.Future = self._loop.create_future()
        self._rbuf = bytearray()
        # the scan whose frames are handled and whose publishes are still
        # in the stage: (frames, full, consumed, err), for _settle_scan
        self._held: tuple = ()
        # stopped at the gate or by the THROTTLE lever: bytes are only
        # buffered, and ``_fresh`` says some were
        self._blocked = False
        self._fresh = False
        self._paused = False  # this feeder stopped the transport reading
        # bytes still to come of the partial packet at the buffer's head
        # (0 = unknown): they are not worth a scan
        self._need = 0
        # why no more bytes will come: the peer's EOF, the transport lost
        self._end: Optional[BaseException] = None
        self._on_gate = self._gate_opened  # one bound method a connection
        self._turn: Optional[asyncio.Handle] = None  # the gate's or the lever's
        self._timer: Optional[asyncio.TimerHandle] = None  # the keepalive's

    async def run(self) -> None:
        """Take the transport over from the stream reader, with what it
        still buffers (bytes that followed CONNECT in one segment), its
        EOF or exception and a transport it had paused; then serve the
        connection to its end."""
        reader = self._cl.net.reader
        self._transport.set_protocol(self)
        buffered = reader._buffer
        if buffered:
            self._rbuf += buffered
            del buffered[:]
            self._fresh = True
        reader._maybe_resume_transport()
        exc = reader.exception()
        if exc is not None:
            self._end = exc
        elif reader.at_eof():
            self._end = ConnectionClosedError()
        try:
            self._pump(_SCAN)
            await self._done
        finally:
            for handle in (self._turn, self._timer):
                if handle is not None:
                    handle.cancel()
            if not self._done.cancel() and not self._done.cancelled():
                self._done.exception()  # seen, if the await never was
            if self._cl._staged_waiter is self._on_gate:
                self._cl._staged_waiter = None

    # -- the transport's callbacks -----------------------------------------

    def get_buffer(self, sizehint: int) -> memoryview:
        return self._view

    def buffer_updated(self, n: int) -> None:
        if self._done.done():
            return
        rbuf = self._rbuf
        rbuf += self._view[:n]
        if self._blocked:
            self._fresh = True
            if len(rbuf) > READ_HIGH_WATER:
                self._pause()
            return
        if n < self._need:
            self._need -= n
            return
        ops = self._cl.ops
        ops.socket_reads += 1
        ops.direct_reads += 1
        self._pump(_SCAN)

    def eof_received(self) -> Optional[bool]:
        self._ended(None)
        return self._stream.eof_received()

    def connection_lost(self, exc: Optional[Exception]) -> None:
        self._ended(exc)
        self._stream.connection_lost(exc)

    def pause_writing(self) -> None:
        self._stream.pause_writing()

    def resume_writing(self) -> None:
        self._stream.resume_writing()

    # -- the read side's turn ----------------------------------------------

    def _pump(self, stage: int) -> None:
        """One turn of the read side from ``stage`` on: scan and handle
        what is buffered (``_SCAN``), do what follows a scan
        (``_SETTLE``), see to the next read (``_READ``), around again
        while complete packets may be buffered; until the next packet
        needs bytes that have not come, the gate or the lever stops the
        turn, or the connection ends."""
        cl = self._cl
        rbuf = self._rbuf
        held = self._held
        try:
            while True:
                if stage == _SCAN:
                    if cl.closed:
                        self._finish(None)
                        return
                    frames, consumed, err = self._frame_scan(
                        rbuf, max_frames=self._max_frames,
                        max_packet_size=self._caps.maximum_packet_size,
                    )
                    cl._take_frames(rbuf, frames, self._handler)
                    n = len(frames)
                    held = (n, n == self._max_frames, consumed, err)
                    if cl._staged:
                        # publishes of this scan are still in the stage:
                        # the completion of the last of them takes the
                        # turn up again (_gate_opened)
                        self._held = held
                        self._blocked = True
                        cl._staged_waiter = self._on_gate
                        return
                if stage != _READ:
                    if cl._settle_scan(rbuf, *held):
                        stage = _SCAN
                        continue
                    delay = cl._read_delay()
                    if delay > 0:
                        self._blocked = True
                        self._pause()
                        self._turn = self._loop.call_later(delay, self._unthrottled)
                        return
                deadline = cl._deadline
                if deadline is not None and self._timer is None:
                    # ticking and not armed: the first read, or the
                    # timer fired while the gate or the lever held
                    left = deadline - time.monotonic()
                    if left <= 0:
                        raise asyncio.TimeoutError()
                    self._timer = self._loop.call_later(left, self._deadline_due)
                if self._fresh:
                    # bytes that came while the gate or the lever held
                    # (or behind CONNECT): one wake-up on data, as the
                    # stream feeder's read of what its reader buffered
                    self._fresh = False
                    cl.ops.socket_reads += 1
                    cl.ops.direct_reads += 1
                    stage = _SCAN
                    continue
                if self._end is not None:
                    raise self._end
                if self._paused:
                    self._paused = False
                    self._transport.resume_reading()
                self._need = cl._missing_bytes(rbuf, self._varint_decode)
                return
        except Exception as e:
            self._finish(e)

    def _gate_opened(self) -> None:
        """``cl._staged`` is back at zero (server._complete_staged, inside
        a completion slice): the rest of the turn runs in a turn of the
        loop of its own."""
        self._cl._staged_waiter = None
        self._turn = self._loop.call_soon(self._resume)

    def _resume(self) -> None:
        self._turn = None
        if self._done.done():
            return
        cl = self._cl
        if cl._staged:
            # a publish of this connection was staged from outside its
            # read since (an injected packet): its completion calls again
            cl._staged_waiter = self._on_gate
            return
        self._blocked = False
        self._pump(_SETTLE)

    def _unthrottled(self) -> None:
        self._turn = None
        if self._done.done():
            return
        self._blocked = False
        self._pump(_READ)

    def _deadline_due(self) -> None:
        """The keepalive's timer fired: the connection is over if its
        deadline has not moved, else the timer is armed for where it
        moved to. While the gate or the lever holds, the turn that
        follows looks at the deadline itself."""
        self._timer = None
        deadline = self._cl._deadline
        if self._done.done() or self._blocked or deadline is None:
            return
        left = deadline - time.monotonic()
        if left > 0:
            self._timer = self._loop.call_later(left, self._deadline_due)
        else:
            self._finish(asyncio.TimeoutError())

    def _pause(self) -> None:
        if not self._paused:
            self._paused = True
            self._transport.pause_reading()

    def _ended(self, exc: Optional[BaseException]) -> None:
        """No more bytes will come. What is buffered is still served
        (the gate or the lever may hold it); the turn that next needs
        bytes ends the connection, as does this call where one waits."""
        if self._end is None:
            self._end = exc if exc is not None else ConnectionClosedError()
        if not self._blocked:
            self._finish(self._end)

    def _finish(self, exc: Optional[BaseException]) -> None:
        if self._done.done():
            return
        if exc is None:
            self._done.set_result(None)
        else:
            self._done.set_exception(exc)


@dataclass
class Will:
    """Last will and testament details (clients.go:132-140)."""

    payload: bytes = b""
    user: list[UserProperty] = field(default_factory=list)
    topic_name: str = ""
    flag: int = 0  # 0/1; cleared once the will is sent
    will_delay_interval: int = 0
    qos: int = 0
    retain: bool = False


class ClientConnection:
    """Transport state for one client (clients.go:113-120)."""

    def __init__(
        self,
        reader: Optional[asyncio.StreamReader] = None,
        writer: Optional[asyncio.StreamWriter] = None,
    ) -> None:
        self.reader = reader
        self.writer = writer
        self.remote = ""
        self.listener = ""
        self.inline = False
        # the asyncio loop OWNING this transport (set at attach): under
        # the shard fabric every transport write/close must happen on
        # it; None (inline clients, unattached tests) means loop-local
        self.loop: Optional[asyncio.AbstractEventLoop] = None
        if writer is not None:
            peer = writer.get_extra_info("peername")
            if peer:
                self.remote = f"{peer[0]}:{peer[1]}" if isinstance(peer, tuple) else str(peer)


class SliceSocket:
    """What a completion slice keeps of ONE socket whose cork it opened
    (``server._cork_repeated``: a socket of this loop that the slice
    hits more than once), from the slice's start to its end: what
    fan-out would otherwise read again at every delivery.

    The socket was ready when the record was made (open, no TLS, an
    empty transport buffer, an empty outbound queue) and its session
    asks for no rewrite of a shared frame (no outbound aliases, no size
    cap): a delivery is then ONE append of its variant's frame to
    ``cork``, in submit order. That holds only while the record's own
    appends (and other packets that join the same cork) are all that
    touched the socket, so whatever else puts a frame on the client's
    way out or takes the socket away clears ``Client._slice``: an early
    cork write (``_uncork``), the outbound queue's two ways in
    (``server._enqueue_frame``, ``publish_to_client``), ``stop``. The
    next delivery then reads the socket afresh, as before.

    ``n``, ``nbytes``, ``payload``: the deliveries the record took, their
    frames' bytes and their payloads' (the tenant's count). The io
    counts a delivery moves (``info``, the connection's, telemetry's
    outbound pair, the tenant's) are added once, from these, when the
    slice ends (``settle``): the slice does not yield, and nothing
    reads them from another thread. The counts a profiler's snapshot
    does read off the loop (deliveries, by route; the cork's frames)
    move once a publish and variant, with the publish's ``fanout_n``
    (``server._flush_variant``)."""

    __slots__ = ("cl", "cork", "version", "n", "nbytes", "payload")

    def __init__(self, cl: "Client") -> None:
        self.cl = cl
        self.cork = cl._cork
        self.version = cl.properties.protocol_version
        self.n = 0
        self.nbytes = 0
        self.payload = 0

    def take(self, frame: bytes, payload: int) -> None:
        """One delivery: ``frame`` behind what the cork holds
        (``payload``: its payload's bytes). Past the cork's byte bound
        the cork is written early, at the same frame as ``Client._write``
        would, and the record ends."""
        cork = self.cork
        cork += frame
        self.n += 1
        self.nbytes += len(frame)
        self.payload += payload
        if len(cork) >= CORK_MAX_BYTES:
            self.cl._cut_cork()

    def settle(self) -> None:
        """Add the io counts of ``n`` shared-frame deliveries into a
        cork, as ``Client.write_frame`` and the tenant's note add them
        a frame at a time."""
        n = self.n
        if not n:
            return
        cl = self.cl
        cl._count_sent(self.nbytes, n)
        cl.ops.info.messages_sent += n
        tenant = cl.tenant
        if tenant is not None:
            tenant.messages_out += n
            tenant.bytes_out += self.payload


class ClientProperties:
    """Properties defining client behaviour (clients.go:123-129)."""

    def __init__(self) -> None:
        self.props = Properties()
        self.will = Will()
        self.username = b""
        self.protocol_version = DEFAULT_CLIENT_PROTOCOL_VERSION
        self.clean = False


class ClientState:
    """Operational state of one client (clients.go:143-158)."""

    def __init__(self, topic_alias_maximum: int, max_writes_pending: int) -> None:
        self.topic_aliases = TopicAliases(topic_alias_maximum)
        self.inflight = Inflight()
        self.subscriptions = Subscriptions()  # filter -> Subscription (client mirror)
        self.disconnected = 0  # unix ts of disconnect, for expiry
        # Packet on the per-subscriber path, raw bytes on the shared
        # QoS0 frame fast path (clients._write_loop dispatches on type);
        # thread-safe so cross-shard fan-out can enqueue directly
        # (mqtt_tpu.shards)
        self.outbound: OutboundQueue = OutboundQueue(
            maxsize=max_writes_pending
        )
        self.keepalive = DEFAULT_KEEPALIVE
        self.server_keepalive = False
        self.packet_id = 0  # current highest allocated packet id
        self.stop_cause: Optional[Exception] = None
        self.is_taken_over = False
        self.open = True
        # monotonic ts the outbound queue was first found full (None =
        # not full); the overload governor's slow-consumer eviction
        # sweep compares it against the grace window (mqtt_tpu.overload)
        self.outbound_full_since: Optional[float] = None
        # monotonic ts the client's backlog (transport write buffer past
        # its limit, or a still-full outbound queue) was first observed
        # by the overload sweep; cleared the moment it drains
        self.backlog_over_since: Optional[float] = None
        # transport buffer size at the last overload sweep: a consumer
        # whose buffer SHRANK since then is draining (slow, not stalled)
        # and must not accumulate eviction grace
        self.sweep_buffered = 0
        # outbound queue-wait sampling (mqtt_tpu.telemetry): every
        # successful enqueue bumps out_seq (server._stamp_outbound);
        # sampled enqueues park (seq, t) here and the write loop matches
        # out_deq against the head to observe the wait. Bounded: evicted
        # stamps are just lost samples.
        self.out_seq = 0
        self.out_deq = 0
        self.out_stamps: collections.deque = collections.deque(maxlen=64)
        # write-path accounting (mqtt_tpu.profiling / ROADMAP item 3):
        # bytes and socket-write calls this client's outbound legs have
        # issued — the per-client face of the aggregate
        # mqtt_tpu_outbound_{bytes,writes}_total counters
        self.out_bytes = 0
        self.out_writes = 0

    @property
    def outbound_qty(self) -> int:
        """Queued outbound publishes — delegated to the thread-safe
        queue's own count. A bare ``+=`` mirror would lose updates when
        shard threads enqueue concurrently (mqtt_tpu.shards), and this
        count gates the direct-socket flush eligibility
        (server._flush_variant), where an undercount could reorder
        frames past still-queued ones."""
        return self.outbound.qsize()


class Client:
    """A client known by the broker (clients.go:103-110)."""

    def __init__(self, reader, writer, ops) -> None:
        self.ops = ops
        self.id = ""
        self.properties = ClientProperties()
        self.state = ClientState(
            ops.options.capabilities.topic_alias_maximum,
            ops.options.capabilities.maximum_client_writes_pending,
        )
        self.net = ClientConnection(reader, writer)
        self._deadline: Optional[float] = None  # monotonic keepalive deadline
        self._writer_task: Optional[asyncio.Task] = None
        # per-evaluation-window publish counter for the overload
        # governor's THROTTLE read-delay verdict (mqtt_tpu.overload);
        # the read loop counts, read_delay() resets on window roll
        self._pub_epoch = -1
        self._pub_count = 0
        # this connection's publishes in the staging loop
        # (mqtt_tpu.staging): counted up where one is parked from the
        # connection's own loop and down by its batch's completion
        # (server._park_publish / _complete_staged). The read side stops
        # ONCE a scan until the count is back at zero, and raises there
        # the first error a completion recorded. ``_staged_waiter`` is
        # what it left at the gate, or None: the completion that takes
        # the count to zero calls it (the stream feeder's future's
        # wake-up; the direct feeder's ``call_soon`` of its next turn).
        self._staged = 0
        self._staged_waiter: Optional[Callable[[], None]] = None
        self._staged_err: Optional[BaseException] = None
        # the encoded packets held back for this socket, joined in order,
        # or None while nothing is: they leave as ONE transport write when
        # whoever opened the cork closes it (_uncork). Two openers: the
        # connection's own socket read (read: its handlers' acks,
        # mostly) and a completion slice that delivers to this socket
        # more than once (server._complete_staged). Never open across a
        # return to the event loop. A socket send is the dearest thing
        # the loop does (a syscall against a handful of bytecodes).
        self._cork: Optional[bytearray] = None
        # the completion slice's record of this socket while it holds
        # (SliceSocket), else None
        self._slice: Optional[SliceSocket] = None
        # priority-weighted shedding (mqtt_tpu.overload): the class and
        # its shed/publish-quota multiplier, resolved at CONNECT from
        # Options.overload_priority_users / overload_priority_classes
        # (server._assign_priority_class); 1.0 = the flat default. The
        # governor reads the weight on every admit/read_delay verdict,
        # so it lives here as a plain attribute, not a config lookup.
        self.priority_class = ""
        self.priority_weight = 1.0
        # the tenant this client resolved to at CONNECT
        # (mqtt_tpu.tenancy.Tenant) or None for the global namespace;
        # set once by server._resolve_tenant, read on every publish /
        # subscribe to decide namespace scoping
        self.tenant: Optional[Any] = None
        # the owning shard's read-side ScanGate (mqtt_tpu.shards): set
        # at attach when the fabric is on; None falls back to the
        # server-wide gate (Options.scan_coalesce) or per-socket scans
        self.scan_gate: Optional[ScanGate] = None
        # the attach-handler task serving this connection (set by
        # server.attach_client): the cross-shard takeover quiesce
        # awaits it on the owning loop so the old session's disconnect
        # epilogue fully runs before state migrates (mqtt_tpu.shards)
        self._handler_task: Optional[asyncio.Task] = None

    # -- lifecycle ---------------------------------------------------------

    def start_write_loop(self) -> None:
        """Spawn the single writer task draining the outbound queue
        (clients.go:192-205)."""
        self._writer_task = asyncio.get_running_loop().create_task(self._write_loop())

    async def _write_loop(self) -> None:
        st = self.state
        while True:
            pk = await st.outbound.get()
            st.out_deq += 1
            stamps = st.out_stamps
            if stamps:
                # resync past stamps evicted by the deque bound, then
                # observe the matching sampled enqueue's queue wait
                while stamps and stamps[0][0] < st.out_deq:
                    stamps.popleft()
                if stamps and stamps[0][0] == st.out_deq:
                    _, t0 = stamps.popleft()
                    tele = getattr(self.ops, "telemetry", None)
                    if tele is not None:
                        tele.outbound_wait.observe(time.perf_counter() - t0)
            try:
                if type(pk) is bytes:  # pre-encoded qos0 fan-out frame
                    self.write_frame(pk)
                else:
                    self.write_packet(pk)
            except Exception as e:
                self.ops.log.debug("failed publishing packet to %s: %s", self.id, e)

    def write_frame(self, data: bytes) -> None:
        """Write a pre-encoded PUBLISH frame (the server's qos0 fan-out
        fast path — shared bytes, one encode per publish). The fast path
        is disabled whenever on_packet_encode/on_packet_sent hooks are
        attached, so skipping them here never hides a hook call."""
        if self.closed:
            raise ConnectionClosedError()
        if self.net.writer is None:
            return
        self._write(data)
        # io accounting only: the DELIVERY count for a shared frame is
        # stamped by server._enqueue_frame, which still knows the topic
        # (this pre-encoded frame does not) and so can keep $SYS
        # housekeeping out of the amplification math
        self._count_sent(len(data))
        self.ops.info.messages_sent += 1

    def write_puback(self, packet_id: int) -> None:
        """Write a v3.1.1 PUBACK as its four bytes, counted as
        ``write_packet`` counts one: for a caller that has seen that no
        hook takes the ack as a packet (server.ingest_run)."""
        if self.closed:
            raise ConnectionClosedError()
        if self.net.writer is None:
            return
        self._write(bytes((0x40, 2, packet_id >> 8, packet_id & 0xFF)))
        self._count_sent(4)

    def _count_sent(self, n: int, packets: int = 1) -> None:
        """``packets`` packets of ``n`` bytes together went to the
        transport (or its cork): ``info``, the connection's own and
        telemetry's counts."""
        info = self.ops.info
        info.bytes_sent += n
        info.packets_sent += packets
        st = self.state
        st.out_bytes += n
        st.out_writes += packets
        tele = getattr(self.ops, "telemetry", None)
        if tele is not None:
            tele.outbound_bytes.inc(n)
            tele.outbound_writes.inc(packets)

    def _write(self, data: bytes) -> None:
        """One encoded packet to the transport, in order: behind the
        packets already held for this socket while its cork is open (its
        own read's, or a completion slice's). A cork is bounded: past
        ``CORK_MAX_BYTES`` it is written out early and stays open."""
        cork = self._cork
        if cork is None:
            self._send(data)
            return
        cork += data
        self.ops.cork_frames += 1
        if len(cork) >= CORK_MAX_BYTES:
            self._cut_cork()

    def _cut_cork(self) -> None:
        """The cork is past its byte bound: write it out early, and go
        on corking."""
        self.ops.cork_early_writes += 1
        self._uncork()
        self._cork = bytearray()

    def _uncork(self) -> None:
        """Write what this socket's cork holds, as one transport write,
        and stop corking. For the opener (the read in hand, or the
        completion slice) and for the teardown. Counted (``_Ops``): a
        cork written (``cork_writes``), beside the packets that joined
        one (``cork_frames``) and the writes its byte bound forced
        (``cork_early_writes``). The slice's record of the socket ends
        with its cork: the transport may hold bytes now."""
        cork, self._cork = self._cork, None
        self._slice = None
        if cork and self.net.writer is not None:
            self.ops.cork_writes += 1
            self._send(bytes(cork))

    def _send(self, data: bytes) -> None:
        """One transport write: a call that reaches the socket (the
        transport sends at once while its buffer is empty), counted, and
        timed while a profiler session is live (``send_busy_ns``: what a
        send costs the loop in this run, on this host)."""
        ops = self.ops
        prof = getattr(ops, "profiler", None)
        if prof is not None and prof.armed:
            t0 = time.perf_counter_ns()
            self.net.writer.write(data)
            prof.send_busy_ns += time.perf_counter_ns() - t0
        else:
            self.net.writer.write(data)
        ops.socket_sends += 1

    def parse_connect(self, lid: str, pk: Packet) -> None:
        """Absorb CONNECT parameters into client state (clients.go:208-257)."""
        self.net.listener = lid
        self.properties.protocol_version = pk.protocol_version
        self.properties.username = pk.connect.username
        self.properties.clean = pk.connect.clean
        self.properties.props = pk.properties.copy(False)

        caps = self.ops.options.capabilities
        if self.properties.props.receive_maximum > caps.maximum_inflight:  # 3.3.4 Non-normative
            self.properties.props.receive_maximum = caps.maximum_inflight

        if 0 < pk.connect.keepalive <= MINIMUM_KEEPALIVE:
            # keepalive 0 DISABLES the mechanism [MQTT-3.1.2-22] — a
            # deliberate choice (mostly-idle device fleets), not a
            # too-small value worth one warning per ramped connection
            self.ops.log.warning(
                "client keepalive is below minimum recommended value: client=%s keepalive=%d recommended=%d",
                self.id,
                pk.connect.keepalive,
                MINIMUM_KEEPALIVE,
            )

        self.state.keepalive = pk.connect.keepalive  # [MQTT-3.2.2-22]
        self.state.inflight.reset_receive_quota(caps.receive_maximum)  # server per-client max
        self.state.inflight.reset_send_quota(self.properties.props.receive_maximum)  # client max
        self.state.topic_aliases.outbound = OutboundTopicAliases(
            self.properties.props.topic_alias_maximum
        )

        self.id = pk.connect.client_identifier
        if self.id == "":
            self.id = uuid.uuid4().hex[:20]  # [MQTT-3.1.3-6] [MQTT-3.1.3-7]
            self.properties.props.assigned_client_id = self.id

        if pk.connect.will_flag:
            self.properties.will = Will(
                qos=pk.connect.will_qos,
                retain=pk.connect.will_retain,
                payload=pk.connect.will_payload,
                topic_name=pk.connect.will_topic,
                will_delay_interval=pk.connect.will_properties.will_delay_interval,
                user=pk.connect.will_properties.user,
                flag=1,
            )
            if (
                pk.properties.session_expiry_interval_flag
                and pk.properties.session_expiry_interval
                < pk.connect.will_properties.will_delay_interval
            ):
                self.properties.will.will_delay_interval = pk.properties.session_expiry_interval

    def refresh_deadline(self, keepalive: int) -> None:
        """Arm the read deadline at keepalive x 1.5 [MQTT-3.1.2-22]
        (clients.go:260-269); 0 disables it."""
        self._deadline = time.monotonic() + keepalive * 1.5 if keepalive > 0 else None

    def next_packet_id(self) -> int:
        """The next unused packet id; raises ERR_QUOTA_EXCEEDED when all ids
        are inflight (clients.go:274-299)."""
        i = self.state.packet_id
        started = i
        overflowed = False
        maximum = self.ops.options.capabilities.maximum_packet_id
        while True:
            if overflowed and i == started:
                raise ERR_QUOTA_EXCEEDED()
            if i >= maximum:
                overflowed = True
                i = 0
                continue
            i += 1
            if self.state.inflight.get(i & 0xFFFF) is None:
                self.state.packet_id = i
                return i

    def resend_inflight_messages(self, force: bool) -> None:
        """Resend pending inflight messages with DUP [MQTT-3.3.1-1/-3]
        (clients.go:302-327)."""
        if len(self.state.inflight) == 0:
            return
        for tk in self.state.inflight.get_all(False):
            if tk.fixed_header.type == pkts.PUBLISH:
                tk.fixed_header.dup = True
            self.ops.hooks.on_qos_publish(self, tk, tk.created, 0)
            self.write_packet(tk)
            if tk.fixed_header.type in (pkts.PUBACK, pkts.PUBCOMP):
                if self.state.inflight.delete(tk.packet_id):
                    self.ops.hooks.on_qos_complete(self, tk)
                    self.ops.info.inflight -= 1

    def clear_inflights(self) -> None:
        """Drop all inflight messages, e.g. clean-session disconnect
        (clients.go:330-337)."""
        for tk in self.state.inflight.get_all(False):
            if self.state.inflight.delete(tk.packet_id):
                self.ops.hooks.on_qos_dropped(self, tk)
                self.ops.info.inflight -= 1

    def clear_expired_inflights(self, now: int, maximum_expiry: int) -> list[int]:
        """Drop expired inflight messages [MQTT-3.3.2-5] (clients.go:340-359)."""
        deleted = []
        for tk in self.state.inflight.get_all(False):
            expired = tk.protocol_version == 5 and 0 < tk.expiry < now
            enforced = maximum_expiry > 0 and now - tk.created > maximum_expiry
            if expired or enforced:
                if self.state.inflight.delete(tk.packet_id):
                    self.ops.hooks.on_qos_dropped(self, tk)
                    self.ops.info.inflight -= 1
                    deleted.append(tk.packet_id)
        return deleted

    async def read(self, packet_handler: Callable[["Client", Packet], Optional[Awaitable]]) -> None:
        """Take this connection's packets in until it ends (clients.go:363-388);
        raises on connection error, keepalive timeout, or a handler error,
        and returns on a clean DISCONNECT or a stop from inside a handler.

        Packets are framed in bulk: everything a socket read brought is
        scanned at once by the native frame scanner (mqtt_tpu/native) and
        each complete packet is decoded straight from the buffer
        (SURVEY.md §7 hard-part #5). One frame loop (``_take_frames``)
        and one set of rules after it (``_settle_scan``) serve two
        feeders, chosen by what the connection is:

        - **direct** (``_DirectFeed``): the connection's reader is fed by
          its own transport (TCP, TLS, a unix socket, a socket a loop
          shard wrapped) and no scan coalescer stands before it. The
          broker's own protocol takes the transport over and the scan
          runs inside the transport's read callback (``buffer_updated``):
          no future, task step, timer or allocation a socket read. This coroutine awaits one future
          a connection.
        - **stream** (``_read_stream``): everything else: a reader some
          pump feeds (the WebSocket leg), inline and mock clients, and a
          connection behind a ``ScanGate`` (loop shards,
          ``Options.scan_coalesce``: that coalescer awaits across
          connections). One ``reader.read`` coroutine a socket read.

        ``packet_handler(cl, pk)`` is synchronous. A PUBLISH it parked
        with the staging loop (mqtt_tpu.staging) is still counted in
        ``_staged`` when it returns: every publish of a scan reaches the
        staging batch before the read side stops, and it stops ONCE a
        scan, at one gate, until the last of them has fanned out: no
        frame of this connection is handled before that (the
        back-pressure and the per-connection order depend on it). An
        error a completion recorded for this connection is raised here.
        """
        # the shard's own gate wins (per-shard decode batching is
        # default-on inside the fabric); the server-wide gate serves the
        # single-loop opt-in (Options.scan_coalesce)
        scan_gate = self.scan_gate or getattr(self.ops, "scan_gate", None)
        self.refresh_deadline(self.state.keepalive)
        transport = self._fed_transport() if scan_gate is None else None
        if transport is not None:
            await _DirectFeed(self, transport, packet_handler).run()
        else:
            await self._read_stream(packet_handler, scan_gate)

    def _fed_transport(self) -> Optional[asyncio.Transport]:
        """The transport whose ``StreamReaderProtocol`` feeds this
        connection's reader, or None where something else does (a pump,
        a test) or nothing (an inline client)."""
        reader = self.net.reader
        transport = getattr(self.net.writer, "transport", None)
        get_protocol = getattr(transport, "get_protocol", None)
        if get_protocol is None or not isinstance(reader, asyncio.StreamReader):
            return None
        protocol = get_protocol()
        if (
            isinstance(protocol, asyncio.StreamReaderProtocol)
            and protocol._stream_reader is reader
        ):
            return transport
        return None

    async def _read_stream(self, packet_handler, scan_gate: Optional[ScanGate]) -> None:
        """The stream feeder of ``read``: one ``reader.read`` under the
        keepalive's ``wait_for`` a socket read, one future a gated
        scan."""
        from .native import MAX_FRAMES_PER_SCAN, frame_scan, varint_decode

        caps = self.ops.options.capabilities
        rbuf = bytearray()
        loop = asyncio.get_running_loop()
        while True:
            if self.closed:
                return
            if scan_gate is not None:
                # read-side decode batching (ISSUE 13): every read loop
                # that woke this tick lands in ONE native scan call
                frames, consumed, err = await scan_gate.scan(
                    rbuf, MAX_FRAMES_PER_SCAN, caps.maximum_packet_size
                )
            else:
                frames, consumed, err = frame_scan(
                    rbuf, max_frames=MAX_FRAMES_PER_SCAN,
                    max_packet_size=caps.maximum_packet_size,
                )
            self._take_frames(rbuf, frames, packet_handler)
            if self._staged:
                # publishes of this scan are still in the stage: one
                # pipelining client fills device batches instead of
                # paying a round trip each, and waits here for all of
                # them at once
                waiter = loop.create_future()
                self._staged_waiter = functools.partial(OutboundQueue._wake, waiter)
                try:
                    await waiter
                finally:
                    self._staged_waiter = None
            n = len(frames)
            if self._settle_scan(rbuf, n, n == MAX_FRAMES_PER_SCAN, consumed, err):
                continue
            delay = self._read_delay()
            if delay > 0:
                await asyncio.sleep(delay)
            data = await self._read_more(self._missing_bytes(rbuf, varint_decode))
            if not data:
                raise ConnectionClosedError()
            rbuf += data

    def _take_frames(self, rbuf: bytearray, frames: list, packet_handler) -> None:
        """Handle one scan's complete packets, in the frames' order: the
        one frame loop of both feeders.

        A scan's frames are taken in by the run where they can be: every
        stretch of PUBLISH frames of QoS0 or QoS1 without RETAIN
        (``RUN_FIRST_BYTES``) goes to the server in ONE call
        (``ops.ingest_run``, server.ingest_run), which decodes, checks,
        acknowledges and parks as many of them as it can take as they
        stand and says how many it took. The frame that ended the run,
        the whole stretch where the run's gate is shut, and every other
        packet go one at a time through ``packet_handler``. A stretch of
        PUBACK frames that hold a packet id and nothing else goes the
        same way to ``ops.ack_run`` (server.ack_run), which takes all of
        it or, its gate shut, none, and says how long it is either way.

        What the handlers write to this connection during one scan (an
        ack a QoS>0 frame) is corked and leaves as one transport write
        when the scan's frames are done (``_cork``): one socket send a
        read, not one a frame. The read is one of the cork's two openers;
        a delivery that reaches this socket while it is open (a publisher
        that hears its own topic) joins it.

        While a profiler session is live (mqtt_tpu.tracing), the loop
        time from the frames in hand to their handlers returned (decode,
        admission, acks, the publish parked with the stage) is counted as
        ingest, once a scan, over the publishes in it; a scan without a
        publish books it over its PUBACK frames. Either stretch is also
        an annotation on the profiler's own clock (mqtt/loop.ingest,
        mqtt/loop.acks)."""
        ops = self.ops
        fast_eligible = ops.fast_publish_eligible
        fast_publish = ops.fast_publish
        ingest_run = ops.ingest_run
        ack_run = ops.ack_run
        telemetry = getattr(ops, "telemetry", None)
        prof = getattr(ops, "profiler", None)
        armed = prof is not None and prof.armed
        span = None
        if armed:
            # the annotation lies around the counted stretch, its
            # own cost outside it
            kinds = [f.first_byte >> 4 for f in frames]
            name, n_kind = "mqtt/loop.ingest", kinds.count(pkts.PUBLISH)
            if not n_kind:
                name, n_kind = "mqtt/loop.acks", kinds.count(pkts.PUBACK)
            if n_kind:
                span = prof.annotation(name, n=n_kind)
                span.__enter__()
            n_in = self._pub_count
            t_in = time.perf_counter_ns()
        start = 0
        self._cork = bytearray()  # this read's acks leave as one write
        n = len(frames)
        i = 0
        solo = 0  # the frames below this index go one at a time
        try:
            while i < n:
                f = frames[i]
                if (
                    i >= solo
                    and f.first_byte in RUN_FIRST_BYTES
                    and ingest_run is not None
                ):
                    # a run of PUBLISH frames is taken in by one
                    # call (server.ingest_run); the frame that ends
                    # it goes down the path below, as does the whole
                    # stretch when the run's gate is shut
                    taken = ingest_run(self, rbuf, frames, i, start)
                    if taken < 0:
                        solo = i + 1
                        while solo < n and frames[solo].first_byte in RUN_FIRST_BYTES:
                            solo += 1
                        continue
                    if taken:
                        i += taken
                        f = frames[i - 1]
                        start = f.body_offset + f.remaining
                        if self.closed:
                            break
                    if i < n and frames[i].first_byte in RUN_FIRST_BYTES:
                        solo = i + 1  # a PUBLISH the run refused
                    continue
                if (
                    i >= solo
                    and f.first_byte == ACK_FIRST_BYTE
                    and f.remaining == ACK_REMAINING
                    and ack_run is not None
                ):
                    # a stretch of bare PUBACK frames is taken in by
                    # one call (server.ack_run), all of it or none
                    taken = ack_run(self, rbuf, frames, i, start)
                    if taken < 0:
                        solo = i - taken  # the stretch, a frame at a time
                        continue
                    i += taken
                    f = frames[i - 1]
                    start = f.body_offset + f.remaining
                    continue
                i += 1
                fstart = start
                fend = f.body_offset + f.remaining
                ops.info.bytes_received += (f.body_offset - start) + f.remaining
                start = fend
                if (f.first_byte >> 4) == pkts.PUBLISH:
                    # overload-governor accounting: publishes this window
                    # (both the fast-path and decode legs land here)
                    self._pub_count += 1
                # QoS0 v4 PUBLISH passthrough (flags all zero): deliver the
                # frame bytes without materializing a Packet when the
                # server proves nothing can observe the difference. The
                # session gate runs BEFORE any bytes are copied.
                if (
                    f.first_byte == 0x30
                    and fast_publish is not None
                    and fast_eligible(self)
                ):
                    frame = bytes(rbuf[fstart:fend])
                    if fast_publish(self, frame, f.body_offset - fstart):
                        continue
                    body = frame[f.body_offset - fstart :]
                else:
                    body = bytes(rbuf[f.body_offset : fend])
                # telemetry stage clock: 1-in-N publishes get stamped
                # through decode -> admission -> staging -> fanout
                # (mqtt_tpu.telemetry); the clock rides on the packet
                clock = None
                if telemetry is not None and (f.first_byte >> 4) == pkts.PUBLISH:
                    clock = telemetry.publish_clock()
                fh = FixedHeader()
                fh.decode(f.first_byte)
                fh.remaining = f.remaining
                pk = self._decode_body(fh, body)
                if clock is not None:
                    clock.stamp("decode")
                    # dynamic rider, not a Packet field: the clock never
                    # touches the wire or dataclass equality
                    setattr(pk, "_tclock", clock)
                packet_handler(self, pk)
                if self.closed:
                    break
        finally:
            self._uncork()
            if armed:
                t_out = time.perf_counter_ns()
                if span is not None:
                    span.__exit__(None, None, None)
        if armed:
            busy_ns = t_out - t_in
            if self._pub_count != n_in:
                prof.note_ingest(busy_ns, self._pub_count - n_in)
            else:
                acks = sum(
                    (f.first_byte >> 4) == pkts.PUBACK for f in frames[:i]
                )
                if acks:
                    prof.note_acks(busy_ns, acks)

    def _settle_scan(
        self, rbuf: bytearray, n_frames: int, full: bool, consumed: int, err: int
    ) -> bool:
        """What follows a scan's frames once nothing of this connection
        is staged, for both feeders: raise the first error a completion
        recorded, drop the scanned prefix, raise the scan's own error
        (an oversize packet, a malformed length), extend the keepalive.
        True: scan again before reading on (the connection was stopped,
        which the feeder's next turn sees, or the scan was ``full`` and
        more complete packets may be buffered)."""
        if self._staged_err is not None:
            err0, self._staged_err = self._staged_err, None
            raise err0
        if self.closed:
            return True
        del rbuf[:consumed]
        if err == -2:
            raise ERR_PACKET_TOO_LARGE()  # [MQTT-3.2.2-15]
        if err == -1:
            # replay the per-byte path for the precise reason code
            FixedHeader().decode(rbuf[0])  # raises for bad header bytes
            raise pkts.ERR_MALFORMED_VARIABLE_BYTE_INTEGER()
        if full:
            return True
        if n_frames:
            # progress made — extend the keepalive deadline. A trickle
            # of partial-packet bytes deliberately does NOT extend it.
            self.refresh_deadline(self.state.keepalive)
        return False

    def _read_delay(self) -> float:
        """THROTTLE lever: the seconds an over-quota publisher's next
        socket read is put off, so the kernel's TCP window pushes back
        on it (the QoS0 analog of v5 receive-maximum); 0 otherwise."""
        overload = self.ops.overload
        if overload is None or self.net.inline:
            return 0.0
        return overload.read_delay(self)

    @staticmethod
    def _missing_bytes(rbuf: bytearray, varint_decode) -> int:
        """How many more bytes complete the partial packet at the head of
        the buffer, where that is more than one read brings (``READ_SIZE``);
        else 0: lets a huge body arrive whole (the stream feeder's one
        ``readexactly``, the direct feeder's chunks buffered unscanned)
        instead of in nibbles that would wake the scan each time. A
        smaller rest comes with the next read and whatever follows it."""
        if len(rbuf) < 2:
            return 0
        try:
            remaining, vb = varint_decode(bytes(rbuf[1:5]))
        except ValueError:
            return 0
        if vb == 0:
            return 0
        missing = 1 + vb + remaining - len(rbuf)
        return missing if missing > READ_SIZE else 0

    def _decode_body(self, fh: FixedHeader, body: bytes) -> Packet:
        """Decode one framed packet body and run the on_packet_read chain
        (the bulk-path core of read_packet, clients.go:462-520)."""
        self.ops.info.packets_received += 1
        pk = Packet(fixed_header=fh, protocol_version=self.properties.protocol_version)
        decoder = pkts.DECODERS.get(fh.type)
        if decoder is None:
            raise pkts.ERR_NO_VALID_PACKET_AVAILABLE()
        decoder(pk, body)
        if fh.type == pkts.PUBLISH:
            self.ops.info.messages_received += 1
        return self.ops.hooks.on_packet_read(self, pk)

    async def _read_more(self, need: int = 0) -> bytes:
        """One bulk socket read of the stream feeder, honoring the
        keepalive deadline: a ``reader.read`` coroutine under
        ``wait_for`` (a future, two task steps and a timer a read; the
        direct feeder, ``_DirectFeed``, makes none). ``need``>0 waits for
        exactly that many bytes (the rest of a large body,
        ``_missing_bytes``); otherwise reads whatever is available up to
        ``READ_SIZE``."""
        if self.net.reader is None:
            raise ConnectionClosedError()
        if need > 0:
            coro = self.net.reader.readexactly(need)
        else:
            coro = self.net.reader.read(READ_SIZE)
        if self._deadline is None:
            data = await coro
        else:
            timeout = self._deadline - time.monotonic()
            if timeout <= 0:
                coro.close()
                raise asyncio.TimeoutError()
            data = await asyncio.wait_for(coro, timeout)
        if data:
            # one wake-up of the frame scan on data (the stream may
            # have joined two recvs)
            self.ops.socket_reads += 1
        return data

    def stop(self, err: Optional[Exception] = None) -> None:
        """Idempotently end the client: close the transport, cancel the
        writer task, record the stop cause and time (clients.go:391-407).

        Task.cancel and transport.close are loop-affine: when another
        shard's loop owns this connection (cross-shard takeover, the
        main loop's drain) the teardown is marshaled to the owner via
        ``call_soon_threadsafe``; the closed flag flips immediately
        either way, so every data-plane gate sees the stop at once."""
        if not self.state.open:
            return
        self.state.open = False
        self._slice = None  # a slice in hand reads the socket afresh
        if err is not None:
            self.state.stop_cause = err
        loop = self.net.loop
        marshaled = False
        if loop is not None and loop.is_running():
            try:
                running = asyncio.get_running_loop()
            except RuntimeError:
                running = None
            if loop is not running:
                try:
                    loop.call_soon_threadsafe(self._stop_teardown)
                    marshaled = True
                except RuntimeError:
                    marshaled = False  # owner loop died first
        if not marshaled:
            self._stop_teardown()
        # brokerlint: ok=R3 session-expiry bookkeeping is wall-clock (persists across restarts)
        self.state.disconnected = int(time.time())

    def _stop_teardown(self) -> None:
        """The loop-affine half of stop(): cancel the writer task and
        close the transport on the loop that owns them."""
        if self._writer_task is not None:
            self._writer_task.cancel()
        if self.net.writer is not None:
            try:
                try:
                    # what the cork holds (a DISCONNECT written inside
                    # the read in hand, a slice's deliveries) goes out
                    # before the transport closes behind it
                    self._uncork()
                finally:
                    self.net.writer.close()
            except Exception:  # brokerlint: ok=R4 teardown; the transport is already dead and close() has no one to report to
                pass

    @property
    def stop_cause(self) -> Optional[Exception]:
        return self.state.stop_cause

    @property
    def stop_time(self) -> int:
        return self.state.disconnected

    @property
    def closed(self) -> bool:
        return not self.state.open

    @property
    def is_taken_over(self) -> bool:
        return self.state.is_taken_over

    # -- wire io -----------------------------------------------------------

    async def _read_exactly(self, n: int) -> bytes:
        if self.net.reader is None:
            raise ConnectionClosedError()
        if self._deadline is None:
            return await self.net.reader.readexactly(n)
        timeout = self._deadline - time.monotonic()
        if timeout <= 0:
            raise asyncio.TimeoutError()
        return await asyncio.wait_for(self.net.reader.readexactly(n), timeout)

    async def read_fixed_header(self, fh: FixedHeader) -> None:
        """Read and validate the next packet's fixed header, enforcing the
        maximum packet size [MQTT-3.2.2-15] (clients.go:432-459)."""
        b = await self._read_exactly(1)
        fh.decode(b[0])
        remaining = 0
        multiplier = 0
        bu = 1
        while True:
            eb = (await self._read_exactly(1))[0]
            bu += 1
            remaining |= (eb & 127) << multiplier
            if remaining > pkts.MAX_VARINT:
                raise pkts.ERR_MALFORMED_VARIABLE_BYTE_INTEGER()
            if (eb & 128) == 0:
                break
            multiplier += 7
        fh.remaining = remaining
        caps = self.ops.options.capabilities
        if caps.maximum_packet_size > 0 and remaining + 1 > caps.maximum_packet_size:
            raise ERR_PACKET_TOO_LARGE()  # [MQTT-3.2.2-15]
        self.ops.info.bytes_received += bu

    async def read_packet(self, fh: FixedHeader) -> Packet:
        """Read and decode a packet body, then run the on_packet_read
        modifier chain (clients.go:462-520)."""
        self.ops.info.packets_received += 1
        pk = Packet(fixed_header=fh, protocol_version=self.properties.protocol_version)
        body = await self._read_exactly(fh.remaining) if fh.remaining else b""
        self.ops.info.bytes_received += len(body)
        decoder = pkts.DECODERS.get(fh.type)
        if decoder is None:
            raise pkts.ERR_NO_VALID_PACKET_AVAILABLE()
        decoder(pk, body)
        if fh.type == pkts.PUBLISH:
            self.ops.info.messages_received += 1
        return self.ops.hooks.on_packet_read(self, pk)

    def write_packet(self, pk: Packet) -> None:
        """Encode and write a packet to the client transport
        (clients.go:523-642)."""
        if self.closed:
            raise ConnectionClosedError()
        if self.net.writer is None:
            return
        if pk.expiry > 0:
            expiry = pk.expiry - int(time.time())  # brokerlint: ok=R3 message expiry is an absolute wall-clock stamp
            if expiry < 1:
                expiry = 1
            pk.properties.message_expiry_interval = expiry  # [MQTT-3.3.2-6]

        pk.protocol_version = self.properties.protocol_version
        if pk.mods.max_size == 0:  # NB used to embed client packet sizes in tests
            pk.mods.max_size = self.properties.props.maximum_packet_size

        if (
            self.properties.props.request_problem_info_flag
            and self.properties.props.request_problem_info == 0
        ):
            pk.mods.disallow_problem_info = True  # [MQTT-3.1.2-29]

        if (
            pk.fixed_header.type != pkts.CONNACK
            or self.properties.props.request_response_info == 1
            or self.ops.options.capabilities.compatibilities.always_return_response_info
        ):
            pk.mods.allow_response_info = True  # [MQTT-3.1.2-28]

        pk = self.ops.hooks.on_packet_encode(self, pk)

        buf = get_buffer()
        try:
            pkts.ENCODERS[pk.fixed_header.type](pk, buf)
            if pk.mods.max_size > 0 and len(buf) > pk.mods.max_size:
                raise ERR_PACKET_TOO_LARGE()  # [MQTT-3.1.2-24] [MQTT-3.1.2-25]
            data = bytes(buf)
        finally:
            put_buffer(buf)

        self._write(data)
        self._count_sent(len(data))
        if pk.fixed_header.type == pkts.PUBLISH:
            self.ops.info.messages_sent += 1
            tele = getattr(self.ops, "telemetry", None)
            if tele is not None and not pk.topic_name.startswith("$SYS"):
                # a per-subscriber encode: the amplification numerator
                # (ROADMAP item 3's encode-once rewrite drives this to
                # ~1 per inbound publish). $SYS housekeeping fan-out is
                # excluded — it recurs every interval with no inbound
                # publish behind it and would inflate the ratio without
                # bound; retained deliveries and QoS retransmits DO
                # count (they are real write-path encode work).
                tele.publish_encodes.inc()
                tele.fanout_deliveries.inc()
        self.ops.hooks.on_packet_sent(self, pk, data)


class Clients(LockedMap[str, Client]):
    """Clients known by the broker, keyed on client id (clients.go:36-100).

    Lock-plane adopted (mqtt_tpu.utils.locked): every fan-out delivery
    does a ``get`` per subscriber, so this is the hottest single lock in
    the broker."""

    def __init__(self) -> None:
        super().__init__(name="clients")

    def add_client(self, cl: Client) -> None:
        self.add(cl.id, cl)

    def get_by_listener(self, id_: str) -> list[Client]:
        with self._lock:
            return [
                c for c in self.internal.values() if c.net.listener == id_ and not c.closed
            ]
