"""The broker's publish staging loop: micro-batch concurrent PUBLISHes into
device match batches (SURVEY.md §7 stage 4).

The reference matches synchronously inside ``processPublish``
(server.go:984-1021) — free when the walk is an in-process trie, ruinous
when it is a device round trip. The stage turns the device matcher into a
pipelined batch engine:

- ``park(topic, entry)`` parks the publish and returns immediately: no
  task, future or coroutine a publish. The entry (:class:`Parked`) names
  its completion, which the drain loop calls ONCE A BATCH with the
  batch's entries and results, in submit order, a slice of
  ``COMPLETION_SLICE`` publishes at a time (the server fans a slice out
  under one read of the client registry). The publishing connection
  waits once a socket read for its own publishes (clients.read), so
  *that* client blocks while every other client keeps being served.
  ``park_many(items)`` parks a run of publishes (a socket read's
  PUBLISH frames, server.ingest_run) as ``park()`` would one by one,
  at one lock pair and one wake-up. ``submit(topic)`` is the same path
  for callers that want a future: an entry whose completion sets it.
- A collector task gathers everything submitted within the accumulation
  window (or up to the batch cap) and issues ONE ``match_topics_async``
  dispatch. The issue leg (host tokenize + H2D + async device dispatch)
  runs on its OWN dispatch thread (``mqtt-tpu-h2d``), the blocking D2H
  sync + host materialization on another (``mqtt-tpu-resolve``), and the
  kernel itself is asynchronous on the device — a ``pipeline_depth``-deep
  (default 3) overlapped pipeline in which batch N+2 tokenizes while
  N+1 matches and N drains, so the event loop never carries staging
  work and the device never waits for the host between batches. Every
  boundary of a batch is stamped once, on its own record
  (``tracing.BatchProfile``: the batch's span tree); the per-leg handoff
  waits in the telemetry plane
  (``mqtt_tpu_staging_leg_wait_seconds{leg=h2d|d2h}`` — the numbers
  that must sit near zero when the pipeline is actually full) are read
  off the same stamps.
- The window and the batch cap ADAPT to the measured per-batch service
  time against ``latency_budget_s`` (SURVEY §7 hard part 4: "adaptive
  batch window + host fast-path"): under light load the window shrinks
  toward immediate dispatch (p99 ~= one service time); under heavy load
  batches grow until the service-time EWMA approaches the budget, then
  the cap backs off so publish latency stays bounded instead of batches
  compounding.
- Cold compile is set-up, not service: a batch during whose drain a
  first-signature jit call ran anywhere in the process (the compile
  clock, ops/devicestats.CompileLedger) is kept out of the service-time
  EWMA, so neither the cap controller nor the deadline-aware admission
  test ever reads a compile as load. Without this a cold broker answered
  most of its first burst from the host trie (PR 21).
- A drainer task resolves batches IN ORDER off the event loop (the D2H
  sync blocks, so it runs in the executor) and completes the entries in
  submission order — per-publish fan-out order is exactly submission
  order, as in the reference.
- A matcher failure degrades, never drops: the affected entries complete
  with the bit-identical host trie walk, through the same completion.
- Admission is BOUNDED (``max_pending``): under a publish storm the
  device's backlog never grows past its cap — overflow (and submissions
  whose projected pipeline wait already exceeds the deadline) is walked
  on the host at once, and the overload governor (mqtt_tpu.overload)
  watches the same depth as its staging pressure signal.
- A fallback JOINS the order, it does not jump it ([MQTT-4.6.0-5]): a
  publish whose result came from the host walk while earlier publishes
  may still be in the stage rides on as a *held* member — through
  ``_pending``, its batch and the drain queue, with its result attached
  (``Parked.held``) — and completes in its place in submit order. It is
  left out of tokenize, H2D and the device program and out of every
  depth the admission test and the cap controller reckon with. Only a
  publish that can overtake nothing completes inside ``park()``
  (``Parked.alone``; a stage that is stopping or was never started).
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Optional

from .topics import Subscribers
from .tracing import BatchProfile, span
from .utils.loopwitness import DEFAULT_LOOP_PLANE as _LOOP_PLANE

_log = logging.getLogger("mqtt_tpu.staging")

# publishes a completion call takes: the synchronous unit of a batch's
# fan-out (about 12 ms of loop time). Between two slices of a batch the
# drain loop yields to the event loop once, so a 2,048-topic batch holds
# no timer or socket for longer than one slice.
COMPLETION_SLICE = 256


class Parked:
    """One parked publish: what the stage ships with its batch
    (``clock``, ``feats``, ``rjob``) and what its completion needs.

    ``complete(entries, results, t_set_ns)`` is called with a slice of
    one batch's entries that share this callable and their results
    (``Subscribers``, or the exception a failed host walk raised), in
    submit order, on ``loop`` (the loop that parked them, filled in by
    ``park()`` when its caller has not; None: the stage's own). ``t_set_ns`` is the instant the batch's results were
    in hand while a profiler session keeps the batch, else 0. The
    server fills ``cl`` / ``pk`` / ``counted`` / ``alone``
    (server._park_publish); ``MatchStage.submit`` fills ``fut``."""

    __slots__ = (
        "complete", "clock", "feats", "rjob", "submit_ns", "loop",
        "cl", "pk", "counted", "alone", "fut", "held", "held_ns",
    )

    def __init__(
        self, complete, clock=None, feats=None, rjob=None, cl=None, pk=None
    ) -> None:
        self.complete = complete
        self.clock = clock
        self.feats = feats
        self.rjob = rjob
        # perf_counter_ns at park(), stamped only while a profiler
        # session is live: the batch's mqtt/stage.wait (tracing)
        self.submit_ns = 0
        self.loop: Optional[asyncio.AbstractEventLoop] = None
        self.cl = cl
        self.pk = pk
        self.counted = False
        # the parker's word that nothing it parked earlier is still in
        # the stage: a fallback of this publish can overtake nothing and
        # may complete inside park(). False: it joins the order.
        self.alone = False
        self.fut: Optional[asyncio.Future] = None
        # a held member (module docstring): the host walk's result (or
        # the exception it raised) and the instant it was in hand
        self.held = None
        self.held_ns = 0


def _set_futures(entries, results, t_set_ns: int = 0) -> None:
    """``submit()``'s completion: each entry's result lands on its
    future, on the loop that made it."""
    for entry, value in zip(entries, results):
        fut = entry.fut
        if fut.done():
            continue  # the caller cancelled it
        if isinstance(value, BaseException):
            fut.set_exception(value)  # brokerlint: ok=R12 _hand_over groups entries by parking loop and completes each group on it
        else:
            fut.set_result(value)  # brokerlint: ok=R12 as above: this runs on the loop that made the future


class MatchStage:
    """Micro-batching pipeline between ``process_publish`` and a device
    matcher (``DeltaMatcher`` or any object with ``match_topics_async``)."""

    def __init__(
        self,
        matcher,
        host_fallback: Callable[[str], Subscribers],
        window_s: float = 0.002,
        max_batch: int = 4096,
        max_inflight: int = 4,
        latency_budget_s: Optional[float] = 0.25,
        min_batch: int = 64,
        max_pending: int = 8192,
        telemetry=None,
        profiler=None,
        predicates=None,
        pipeline_depth: int = 3,
        recrypt=None,
        compile_clock: Optional[Callable[[], float]] = None,
    ) -> None:
        self.matcher = matcher
        self.host_fallback = host_fallback
        # overlapped-staging depth: how many batches may be in flight
        # across the h2d-tokenize / device-dispatch / d2h-drain legs
        # (0 falls back to max_inflight for embedders pinning the old
        # knob). Depth 3 keeps one batch per leg.
        self.pipeline_depth = pipeline_depth if pipeline_depth > 0 else max_inflight
        # MQTT+ predicate engine (mqtt_tpu.predicates.PredicateEngine) or
        # None. When attached, each batch's payload-feature rows ride to
        # the device BESIDE the tokenized topics — one extra dispatch,
        # zero extra round trips: both results sync in the drain loop's
        # single executor leg, and the resolved pass bits are stamped
        # back onto the per-publish feature carriers before the futures
        # complete, so fan-out receives the already-filtered set.
        self.predicates = predicates
        # tenant re-encryption engine (mqtt_tpu.tenancy.RecryptEngine)
        # or None. When attached, each batch's publisher-decrypt
        # keystream jobs (RecryptJob carriers) dispatch beside the
        # tokenized topics and resolve in the same drain-loop executor
        # leg — the MQT-TZ decrypt rides the staged batch with zero
        # extra device round trips, exactly like predicate rows.
        self.recrypt = recrypt
        # telemetry plane (mqtt_tpu.telemetry.Telemetry) or None: batch
        # service-time + fill-ratio histograms, fallback-class counters,
        # and the per-publish stage clock's staging_wait / device_batch
        # stamps all flow through it
        self.telemetry = telemetry
        # device pipeline profiler (mqtt_tpu.tracing.DeviceProfiler) or
        # None. When attached (and the matcher feeds it), sampled stage
        # clocks resolve device_batch into h2d / device_dispatch / d2h
        # using the boundaries the matcher recorded for this batch.
        self.profiler = profiler
        self.window_s = window_s  # the MAXIMUM accumulation window
        self.max_batch = max_batch
        self.max_inflight = max_inflight
        # p99 target for one staged publish: wait + service must fit it.
        # None disables adaptation (fixed window + cap — benchmarking the
        # throughput-optimal point needs this)
        self.latency_budget_s = latency_budget_s
        self.min_batch = max(1, min_batch)
        # bounded admission: the publishes parked for the device may
        # never grow past this; overflow (and submissions whose projected
        # pipeline wait already blows the deadline) resolves via the host
        # walk instead of queueing for the device — a publish storm
        # costs bounded memory, not an OOM
        self.max_pending = max(1, max_pending)
        self.admission_fallbacks = 0
        self.peak_pending = 0
        # fallbacks that joined the order as held members instead of
        # completing at once (admission and issue_error), and how many of
        # them sit in _pending (under _plock) and how many batches made
        # of held members alone sit in the queue: neither is device work
        self.order_held = 0
        self._held_pending = 0
        self._held_batches = 0
        # how publishes left the stage: through their batch's completion
        # call (the served path), the calls that took (one a slice),
        # and through submit()'s future (0 on the served path)
        self.batch_completed = 0
        self.batch_completions = 0
        self.adapter_completed = 0
        # parked publishes: (topic, Parked). Guarded by _plock: under
        # the event-loop shard fabric (mqtt_tpu.shards) park() runs on
        # every shard's loop while the collector drains on the stage's
        # own loop — the park list is the one cross-thread hand-off
        # point. An entry completes on the loop that parked it (one
        # call_soon_threadsafe a batch and loop when that is not the
        # stage's), so each shard's publisher fans out on its own loop.
        self._pending: list[tuple] = []
        self._plock = threading.Lock()
        # the loop the collector/drainer run on (start()'s loop)
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._wake: Optional[asyncio.Event] = None
        self._queue: Optional[asyncio.Queue] = None
        self._tasks: list[asyncio.Task] = []
        # the resolve leg's dedicated executor: NAMED threads
        # ("mqtt-tpu-resolve-N") so a slice's CPU by thread group
        # (tracing.thread_group) attributes the blocking D2H sync to the
        # staging pipeline instead of an anonymous default-executor slot
        self._executor: Optional[ThreadPoolExecutor] = None
        # the issue leg's dedicated SINGLE dispatch thread
        # ("mqtt-tpu-h2d-0"): tokenize + H2D + async device dispatch run
        # here, in batch order, off the event loop — batch N+2 tokenizes
        # while N+1's kernel runs and N drains on the resolve leg
        self._h2d_executor: Optional[ThreadPoolExecutor] = None
        # batches currently inside the pipeline (enqueued or draining);
        # exported as mqtt_tpu_staging_pipeline_depth
        self.inflight_batches = 0
        self._stopping = False
        self._ewma_s = 0.0  # per-batch service-time EWMA (drainer-updated)
        # seconds the process has spent in first-signature jit calls
        # (module docstring: cold compile is set-up); the default is
        # the process ledger every KernelWatch feeds
        if compile_clock is None:
            from .ops.devicestats import LEDGER

            compile_clock = LEDGER.compile_clock
        self._compile_clock = compile_clock
        # batches whose service-time sample was dropped for that reason
        self.compile_tainted_batches = 0
        self._batch_cap = max_batch if latency_budget_s is None else max(
            self.min_batch, min(max_batch, 1024)
        )

    @property
    def batch_cap(self) -> int:
        """The current adaptive batch-size cap (<= max_batch)."""
        return self._batch_cap

    def _window(self) -> float:
        """The adaptive accumulation sleep: a fraction of the measured
        service time (batching beyond that trades latency for nothing —
        the pipeline is already busy for that long), never exceeding the
        configured maximum window or the latency budget's headroom.

        Headroom is depth-scaled to match what _observe_service budgets:
        a submitted publish waits for every batch already queued, so the
        effective latency is depth x service — once that alone exceeds
        the budget, any window sleep is pure added wait on an already
        over-budget pipeline, and the window collapses to 0."""
        budget = self.latency_budget_s
        if budget is None or self._ewma_s <= 0.0:
            return self.window_s
        depth = self._queue_depth() + 1
        headroom = budget - depth * self._ewma_s
        if headroom <= 0.0:
            return 0.0  # over budget already: dispatch immediately
        return min(self.window_s, 0.5 * self._ewma_s, headroom)

    def _observe_service(self, dt: float, n: int, depth: int) -> None:
        """Feed one batch's resolve wall time into the controller: grow
        the cap while service time is comfortably under budget, shrink it
        proportionally when a batch overruns (service scales ~linearly in
        batch size past the fixed dispatch cost).

        ``depth`` is the number of batches that were queued behind this
        one: a submitted publish waits for every batch ahead of it, so the
        budget must bound depth x service, not one batch's service — the
        controller compares the EFFECTIVE latency (dt * depth) against the
        budget."""
        self._ewma_s = dt if self._ewma_s == 0.0 else (
            0.7 * self._ewma_s + 0.3 * dt
        )
        budget = self.latency_budget_s
        if budget is None or n <= 0:
            return
        effective = dt * max(1, depth)
        if effective > 0.8 * budget:
            target = max(int(n * 0.6 * budget / effective), self.min_batch)
            if target < self._batch_cap:
                self._batch_cap = target
        elif effective < 0.4 * budget and n >= self._batch_cap:
            # only grow when the cap actually bound the batch
            self._batch_cap = min(self.max_batch, self._batch_cap * 2)

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        """Create the collector/drainer tasks on the running loop."""
        loop = asyncio.get_running_loop()
        self._loop = loop
        if self.profiler is not None:
            self.profiler.loop = loop  # where its armed heartbeat runs
        self._wake = asyncio.Event()
        self._executor = ThreadPoolExecutor(
            max_workers=max(2, self.max_inflight),
            thread_name_prefix="mqtt-tpu-resolve",
        )
        # ONE issue thread: the h2d leg must stay in batch order (the
        # drain loop completes futures in submission order)
        self._h2d_executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="mqtt-tpu-h2d"
        )
        # bounded: if resolution falls behind, collection backpressures
        # instead of queueing unbounded device batches
        self._queue = asyncio.Queue(maxsize=self.pipeline_depth)
        self.inflight_batches = 0  # a restarted stage begins empty
        self._tasks = [
            loop.create_task(self._collect_loop(), name="mqtt-tpu-stage-collect"),
            loop.create_task(self._drain_loop(), name="mqtt-tpu-stage-drain"),
        ]

    async def stop(self) -> None:
        """Stop the pipeline; anything still parked completes via the
        host walk so no publish is ever lost."""
        self._stopping = True
        if self._wake is not None:
            self._wake.set()
        for t in self._tasks:
            t.cancel()
        await asyncio.gather(*self._tasks, return_exceptions=True)
        # oldest first: the drain loop completed the batch it held when
        # it was cancelled; then the queued batches, then _pending (at
        # its head the batch the collector held)
        queue = self._queue
        if queue is not None:
            while not queue.empty():
                _resolver, _entries, batch, *_rest = queue.get_nowait()
                self.inflight_batches -= 1
                self._fallback_all(batch, klass="stop")
        self._held_batches = 0
        with self._plock:
            parked, self._pending = self._pending, []
            self._held_pending = 0
        self._fallback_all(parked, klass="stop")
        if self._executor is not None:
            # in-flight resolves may finish on their own time; queued
            # ones are dead (their entries just completed via fallback)
            self._executor.shutdown(wait=False, cancel_futures=True)
            self._executor = None
        if self._h2d_executor is not None:
            self._h2d_executor.shutdown(wait=False, cancel_futures=True)
            self._h2d_executor = None

    # -- submission --------------------------------------------------------

    def submit(
        self, topic: str, clock=None, feats=None, rjob=None
    ) -> "asyncio.Future[Subscribers]":
        """Park one publish and return a future for its Subscribers: the
        adapter over :meth:`park` for tests, embedders and anything else
        that wants to await one publish (an entry whose completion sets
        the future, on the loop that called). The served path parks its
        own entries and makes no future (server._park_publish);
        ``adapter_completed`` counts what came through here."""
        entry = Parked(_set_futures, clock, feats, rjob)
        entry.fut = asyncio.get_running_loop().create_future()
        self.park(topic, entry)
        return entry.fut

    def park(self, topic: str, entry: Parked) -> None:
        """Park one publish with the stage; ``entry.complete`` is called
        with its result when its batch resolves (:class:`Parked`).
        ``entry.clock`` is an optional sampled stage clock
        (mqtt_tpu.telemetry) stamped at batch issue (staging_wait) and
        resolve (device_batch). ``entry.feats`` is the publish's
        optional payload-feature carrier
        (mqtt_tpu.predicates.PublishFeatures): the batch ships it to the
        device rule table and the resolved pass bits come back ON the
        carrier — host-fallback resolutions simply leave it unstamped
        and the fan-out path's host interpreter decides. ``entry.rjob``
        is the publish's optional decrypt carrier
        (mqtt_tpu.tenancy.RecryptJob) for encrypted-namespace publishes:
        its keystream dispatch rides the same batch and the resolved
        rows come back on the carrier the same way.

        Admission is bounded: once ``max_pending`` publishes are parked
        for the device, or the pipeline's projected wait already exceeds
        the deadline (2x the latency budget), the publish is walked on
        the host at once, inside this call — the degraded-but-bounded
        mode — instead of growing the device's backlog. Its result then
        JOINS the order as a held member (module docstring) and its
        completion runs in its place in submit order; only an entry that
        can overtake nothing (``entry.alone``) completes inside this
        call. Held members cost no device work and are bounded by their
        parkers: a connection's read loop does not read on while a
        publish of its last socket read is in the stage (clients.read),
        so it holds at most one read's frames; a ``submit()`` caller
        holds what it has futures for.

        A run of one (:meth:`park_many`)."""
        self.park_many([(topic, entry)])

    def park_many(self, items: list) -> None:
        """Park a run of publishes, ``(topic, entry)`` in submit order,
        all parked from the calling thread's loop: what :meth:`park`
        called once an item does (the same admission verdict an item,
        reckoned with the depth as it stands when the item's turn comes;
        the same held members, ``alone`` completions and counts), at one
        acquisition of the park lock while every item is admitted, one
        wake-up of the collector and one ``submit_ns`` stamp a run. From
        the first item admission refuses, the rest of the run goes one
        item at a time, each behind the one before it (a held member's
        host walk runs outside the lock and must be done before a later
        item may be parked behind it)."""
        if not items:
            return
        loop = items[0][1].loop
        if loop is None:
            try:
                loop = asyncio.get_running_loop()
            except RuntimeError:
                pass  # no loop on this thread: completes on the stage's
        prof = self.profiler
        # a live profiler session: the park instant rides on the
        # entries, for the batch's mqtt/stage.wait (tracing)
        submit_ns = (
            time.perf_counter_ns() if prof is not None and prof.armed else 0
        )
        for _, entry in items:
            if entry.loop is None:
                entry.loop = loop
            entry.submit_ns = submit_ns
        if _LOOP_PLANE.active:
            w = _LOOP_PLANE.witness
            if w is not None:
                w.note_crossing(
                    "match_stage", "submit_local", "submit_cross", self._loop
                )
        wake = self._wake
        if self._stopping or wake is None:
            self._fallback_all(items, klass=None)
            return
        taken = 0
        with self._plock:
            for item in items:
                if not self._admit(item):
                    break
                taken += 1
        queued = items
        if taken < len(items):
            queued = items[:taken]
            queued += [item for item in items[taken:] if self._park_one(item)]
        if not queued:
            return
        # the wake Event is loop-affine: shard-loop submitters marshal
        # the set() onto the stage's loop (mqtt_tpu.shards). A never-
        # started stage (_loop None: unit harnesses that drive the
        # collector by hand) keeps the direct set.
        if self._loop is None or loop is self._loop:
            wake.set()
        else:
            try:
                self._loop.call_soon_threadsafe(wake.set)
            except RuntimeError:
                # stage loop gone mid-shutdown: serve the host walk now,
                # but for what stop() has already taken
                mine = []
                with self._plock:
                    for item in queued:
                        try:
                            self._pending.remove(item)
                        except ValueError:
                            continue
                        if item[1].held is not None:
                            self._held_pending -= 1
                        mine.append(item)
                self._fallback_all(mine, klass=None)

    def _admit(self, item: tuple) -> bool:
        """Under the park lock: park ``item`` for the device unless the
        backlog is at its cap or the projected wait is past the deadline
        (False: the caller serves it by the host walk)."""
        parked = len(self._pending) - self._held_pending
        if parked >= self.max_pending or self._past_deadline():
            return False
        self._pending.append(item)
        if parked >= self.peak_pending:
            self.peak_pending = parked + 1
        return True

    def _park_one(self, item: tuple) -> bool:
        """One item's admission, by itself under the park lock: the slow
        leg of :meth:`park_many`, from the first item it could not admit.
        True: the item is in ``_pending`` (admitted, or held behind what
        is there); False: it has completed inside this call (alone, or
        the stage is stopping)."""
        with self._plock:
            admitted = self._admit(item)
        if admitted:
            return True
        self.admission_fallbacks += 1
        if item[1].alone:
            self._fallback_all([item], klass="admission")
            return False
        self._hold([item], klass="admission")
        with self._plock:
            # stop() takes _pending under this lock, after it set
            # _stopping: a held member is never left behind it
            stopping = self._stopping
            if not stopping:
                self._pending.append(item)
                self._held_pending += 1
        if stopping:
            self._fallback_all([item], klass=None)
            return False
        return True

    def _hold(self, items, klass: str) -> None:
        """Walk ``(topic, entry)`` items on the host now and leave each
        result on its entry (``held``): the fallback of class ``klass``
        that joins the order instead of completing at once."""
        for topic, entry in items:
            try:
                entry.held = self.host_fallback(topic)
            except Exception as e:  # pragma: no cover - host walk is total
                entry.held = e
            entry.held_ns = time.perf_counter_ns()
        self.order_held += len(items)
        if self.telemetry is not None:
            self.telemetry.note_fallback(klass, len(items))

    def _queue_depth(self) -> int:
        """Device batches waiting in the drain queue."""
        if self._queue is None:
            return 0
        return self._queue.qsize() - self._held_batches

    def _past_deadline(self) -> bool:
        """Deadline-aware admission: a new submission waits behind every
        queued batch plus every parked batch-worth of _pending (held
        members, which the device never sees, left out); when that
        projected wait exceeds twice the latency budget, queueing only
        deepens an already-lost backlog — the host walk serves it now.

        The EWMA never contains a cold compile (_drain_loop drops those
        samples). An IDLE pipeline always admits, whatever the EWMA
        says: the service-time estimate only heals through real
        dispatches, so a one-off spike must not starve the stage into a
        permanent host-walk detour."""
        budget = self.latency_budget_s
        if budget is None or self._ewma_s <= 0.0:
            return False
        qdepth = self._queue_depth()
        parked = len(self._pending) - self._held_pending
        if qdepth == 0 and not parked:
            return False  # idle: admit, and let the EWMA re-learn
        depth = 1 + qdepth + parked // max(1, self._batch_cap)
        return depth * self._ewma_s > 2.0 * budget

    @property
    def pending_depth(self) -> int:
        return len(self._pending)

    def alive(self) -> bool:
        """Pipeline liveness for ``GET /healthz`` (ISSUE 14 satellite):
        started, not stopping, and BOTH loop tasks still running — a
        crashed collector/drainer would otherwise strand every parked
        publish until its caller's timeout, which is exactly the state
        a readiness probe must surface."""
        if self._stopping or self._wake is None:
            return False
        return bool(self._tasks) and all(not t.done() for t in self._tasks)

    def pressure(self) -> float:
        """Normalized staging pressure for the overload governor: parked
        admission depth against its cap, plus the batch queue's fill at
        half weight (a full queue is normal pipelining; sustained
        _pending growth is the real overload signal)."""
        p = (len(self._pending) - self._held_pending) / self.max_pending
        q = 0.0
        if self.pipeline_depth > 0:
            q = self._queue_depth() / self.pipeline_depth
        return max(p, 0.5 * q)

    # -- pipeline ----------------------------------------------------------

    async def _collect_loop(self) -> None:
        wake, queue = self._wake, self._queue
        assert wake is not None and queue is not None  # start() created us
        if _LOOP_PLANE.active:
            w = _LOOP_PLANE.witness
            if w is not None:
                # the collector IS the stage loop's drainer of _pending
                w.check_owner("match_stage", "drain_owner", self._loop)
        while True:
            await wake.wait()
            wake.clear()
            if not self._pending:
                continue
            # the accumulation window: give concurrent publishers a beat to
            # land in this batch (latency cost) so the device sees real
            # batches (throughput win); adaptively sized (see _window) and
            # capped by the adaptive batch cap
            cap = self._batch_cap
            if len(self._pending) < cap:
                w = self._window()
                if w > 0:
                    await asyncio.sleep(w)
                cap = self._batch_cap  # the drainer may have adapted it
            with self._plock:
                batch, self._pending = (
                    self._pending[:cap],
                    self._pending[cap:],
                )
                leftovers = bool(self._pending)
                n_held = 0
                if self._held_pending:
                    n_held = sum(1 for _, e in batch if e.held is not None)
                    self._held_pending -= n_held
            if leftovers:
                wake.set()  # leftovers start the next window now
            # a submit() future cancelled mid-window is dead weight: drop
            # it here so the device never matches for it
            batch = [
                item for item in batch
                if item[1].fut is None or not item[1].fut.cancelled()
            ]
            if not batch:
                continue
            # the batch's own record (mqtt_tpu.tracing): every boundary
            # from here to the batch's hand-over is stamped on it, once,
            # so concurrent or out-of-order resolution (the resilience
            # guard pool) can never cross-attribute them. The profiler
            # numbers it and, while a jax.profiler session is live,
            # keeps it; without a profiler it still carries the stamps
            # the leg-wait histograms read.
            profiler = self.profiler
            if profiler is not None:
                profiler.poll()
                rec = profiler.open_batch()
            else:
                rec = BatchProfile()
            rec.formed_ns = time.perf_counter_ns()
            entries = [e for _, e in batch]
            # held members ride along with their results attached: the
            # device sees the others only
            sent = [e for e in entries if e.held is None] if n_held else entries
            topics = (
                [t for t, e in batch if e.held is None] if n_held
                else [t for t, _ in batch]
            )
            predicates = self.predicates
            recrypt = self.recrypt
            feats = (
                [e.feats for e in sent] if predicates is not None else None
            )
            rjobs = [e.rjob for e in sent] if recrypt is not None else None
            rec.topics = len(topics)
            rec.depth = self.inflight_batches
            if rec.kept:
                rec.stage_wait([e.submit_ns for e in sent if e.submit_ns])
            # the sampled stage clocks (1 publish in 64 carries one)
            clocks = [e.clock for e in sent if e.clock is not None]
            for c in clocks:  # end of the accumulation/park wait
                c.stamp("staging_wait")
                c.batch = rec.seq
            # the ISSUE leg runs on the dedicated h2d dispatch thread,
            # in batch order (single worker): host tokenize + H2D + the
            # async device dispatch leave the event loop free, and batch
            # N+2 tokenizes while N+1's kernel runs and N drains — the
            # 3-deep overlap the device profiler's duty cycle gates on
            matcher = self.matcher
            telemetry = self.telemetry

            def issue():
                rec.issue_start_ns = time.perf_counter_ns()
                if telemetry is not None:
                    # h2d-leg handoff wait: batch formed -> issue start
                    telemetry.observe_leg_wait(
                        "h2d", (rec.issue_start_ns - rec.formed_ns) / 1e9
                    )
                if profiler is not None:
                    # the matcher fills the record's tokenize, dispatch,
                    # D2H and resolve spans; the drain loop sub-stamps
                    # sampled clocks from them
                    resolver = matcher.match_topics_async(
                        topics, profile=rec
                    )
                else:
                    resolver = matcher.match_topics_async(topics)
                # MQTT+ predicate evaluation rides the SAME staged
                # batch: one extra async dispatch against the device
                # rule table, resolved in the same drain-loop executor
                # leg as the match result — no additional device round
                # trip. A None resolver (no rules, breaker open, eval
                # error) leaves the carriers unstamped and the fan-out
                # host interpreter decides.
                pred_resolver = None
                if predicates is not None:
                    try:
                        pred_resolver = predicates.eval_batch_async(feats)
                    except Exception:
                        _log.exception(
                            "predicate eval issue failed; host interpreter"
                        )
                # the tenant decrypt leg rides the same batch: one
                # fused keystream dispatch for every encrypted-namespace
                # publish here; a None resolver (no jobs, breaker open,
                # no backend) leaves the carriers unstamped and the
                # fan-out's host keystream serves (mqtt_tpu.tenancy)
                rec_resolver = None
                if recrypt is not None:
                    try:
                        rec_resolver = recrypt.issue_batch(rjobs)
                    except Exception:
                        _log.exception(
                            "recrypt issue failed; host keystream"
                        )
                rec.issue_end_ns = time.perf_counter_ns()
                return resolver, pred_resolver, rec_resolver

            loop = asyncio.get_running_loop()
            resolver = pred_resolver = rec_resolver = None
            try:
                if topics:  # else held members alone: nothing to issue
                    (
                        resolver, pred_resolver, rec_resolver,
                    ) = await loop.run_in_executor(self._h2d_executor, issue)
            except asyncio.CancelledError:
                # stop() cancelled us with this batch in hand (in neither
                # _pending nor the queue): back to the head of _pending,
                # where stop() finds it in its place. An issue that
                # already reached the device is harmless — its result is
                # simply never synced.
                with self._plock:
                    self._pending[:0] = batch
                raise
            except Exception:
                # the batch falls back whole, and waits its turn behind
                # the batches in the queue as held members
                _log.exception("stage issue failed; host fallback for batch")
                self._hold(
                    [item for item in batch if item[1].held is None],
                    klass="issue_error",
                )
                resolver = None
            if resolver is None:
                self._held_batches += 1
            self.inflight_batches += 1
            try:
                await queue.put(
                    (
                        resolver, entries, batch, clocks, rec,
                        pred_resolver, feats, rec_resolver,
                    )
                )
            except asyncio.CancelledError:
                self.inflight_batches -= 1
                with self._plock:
                    self._pending[:0] = batch
                raise

    async def _drain_loop(self) -> None:
        queue = self._queue
        assert queue is not None  # start() created us
        while True:
            (
                resolver, entries, batch, clocks, rec, pred_resolver, feats,
                rec_resolver,
            ) = await queue.get()
            if resolver is None:
                # held members alone (or a batch that fell back whole at
                # issue): no device leg, no service-time sample
                self._held_batches -= 1
                self.inflight_batches -= 1
                results: list = []
            else:
                results = await self._resolve(
                    resolver, batch, rec, pred_resolver, feats, rec_resolver
                )
                if results is None:
                    continue  # fell back, and completed at the head of the order
            # this batch's own device-timing record: both windows are
            # set only when the batch actually dispatched AND synced —
            # the exact-map fast path and host fallbacks leave them
            # None, and then the coarse device_batch stamp applies (no
            # phantom h2d for batches that never touched the device)
            dispatch, d2h = rec.dispatch, rec.d2h
            for ck in clocks:
                self._stamp_round_trip(ck, dispatch, d2h)
            with span(rec, "deliver"):
                t_set = 0
                if rec.kept:
                    # a live profiler session: the instant the batch's
                    # results were in hand is where every member's wait
                    # for the resolve ends and its wait for the loop
                    # begins (DeviceProfiler.note_fanout)
                    t_set = time.perf_counter_ns()
                    rec.set_sum_ns = rec.topics * t_set
                if len(results) != len(entries):
                    # held members: each takes its place in submit order,
                    # and what the order cost it ends here
                    rec.order_hold(
                        [e.held_ns for e in entries if e.held is not None],
                        time.perf_counter_ns(),
                    )
                    live = iter(results)
                    results = [
                        next(live) if e.held is None else e.held
                        for e in entries
                    ]
                await self._complete(entries, results, t_set)

    async def _resolve(
        self, resolver, batch, rec, pred_resolver, feats, rec_resolver
    ):
        """One device batch's blocking leg: sync its results off the
        loop and feed the controller. Returns the device's results, one
        a topic sent; None when the batch fell back instead (and has
        completed: it was at the head of the order)."""
        loop = asyncio.get_running_loop()
        telemetry = self.telemetry
        try:
            # the D2H sync blocks — run it off the loop. Queue depth is
            # sampled at resolve time: batches still queued waited for
            # this one, so the controller budgets depth x service.
            # The predicate rows sync in the SAME executor leg (the
            # pred resolver never raises — failures degrade to None).
            depth = self._queue_depth() + 1
            t0 = loop.time()
            c0 = self._compile_clock()

            def sync():
                rec.sync_start_ns = time.perf_counter_ns()
                if telemetry is not None:
                    # d2h-leg handoff wait: issue returned (batch
                    # queued behind the pipeline) -> sync start
                    telemetry.observe_leg_wait(
                        "d2h", (rec.sync_start_ns - rec.issue_end_ns) / 1e9
                    )
                return (
                    resolver(),
                    pred_resolver() if pred_resolver is not None else None,
                    rec_resolver() if rec_resolver is not None else None,
                )

            results, pred_rows, rec_rows = await loop.run_in_executor(
                self._executor, sync
            )
            if pred_rows is not None and self.predicates is not None:
                self.predicates.attach_rows(feats, pred_rows)
            if rec_rows is not None and self.recrypt is not None:
                self.recrypt.attach(rec_rows)
            dt = loop.time() - t0
            if self._compile_clock() != c0:
                # a first-signature jit call ran during this drain:
                # set-up, not a service-time sample
                self.compile_tainted_batches += 1
            else:
                self._observe_service(dt, rec.topics, depth)
            if telemetry is not None:
                telemetry.observe_batch(dt, rec.topics, self._batch_cap)
        except asyncio.CancelledError:
            # stop() cancelled us with this batch already popped: it is
            # invisible to stop()'s queue drain, so resolve it here
            self.inflight_batches -= 1
            self._fallback_all(batch, klass="stop")
            raise
        except Exception:
            self.inflight_batches -= 1
            _log.exception("stage resolve failed; host fallback for batch")
            self._fallback_all(batch, klass="resolve_error")
            return None
        self.inflight_batches -= 1
        return results

    async def _complete(self, entries, results, t_set_ns: int) -> None:
        """Hand one resolved batch to its completions, in submit order:
        a slice at a time, with one yield to the event loop between
        slices. stop() cancelling the drain loop at such a yield
        completes the rest at once."""
        todo = list(self._slices(self._hand_over(entries, results, t_set_ns)))
        i = 0
        try:
            while i < len(todo):
                if i:
                    await asyncio.sleep(0)
                i += 1
                self._call(*todo[i - 1], t_set_ns)
        except asyncio.CancelledError:
            for item in todo[i:]:
                self._call(*item, t_set_ns)
            raise

    def _hand_over(self, entries, results, t_set_ns: int) -> list:
        """Group a batch's entries by owning loop and completion. The
        groups of other loops (a shard's publishers, mqtt_tpu.shards)
        are sent there, one ``call_soon_threadsafe`` a group: an entry's
        completion must run on the loop that parked it. Returns the
        groups to complete here, ``(complete, entries, results)``."""
        try:
            here: Optional[asyncio.AbstractEventLoop] = (
                asyncio.get_running_loop()
            )
        except RuntimeError:
            here = None
        first = entries[0]
        witness = _LOOP_PLANE.witness if _LOOP_PLANE.active else None
        if len({(e.loop, e.complete) for e in entries}) == 1:
            groups = {(first.loop, first.complete): (entries, results)}
        else:
            groups = {}
            for e, r in zip(entries, results):
                es, rs = groups.setdefault((e.loop, e.complete), ([], []))
                es.append(e)
                rs.append(r)
        local = []
        for (loop, complete), (es, rs) in groups.items():
            if loop is None or loop is here:
                if witness is not None:
                    witness.note("match_stage", "resolve_local")
                local.append((complete, es, rs))
                continue
            if witness is not None:
                witness.note("match_stage", "resolve_marshal")
            try:
                loop.call_soon_threadsafe(
                    self._run_slices, complete, es, rs, t_set_ns
                )
            except RuntimeError:
                pass  # the parking loop closed: nobody is left to serve
        return local

    @staticmethod
    def _slices(groups):
        """``(complete, entries, results)`` groups cut into slices."""
        for complete, es, rs in groups:
            for lo in range(0, len(es), COMPLETION_SLICE):
                hi = lo + COMPLETION_SLICE
                yield complete, es[lo:hi], rs[lo:hi]

    def _run_slices(self, complete, entries, results, t_set_ns: int) -> None:
        """One group's completion, slice after slice without a yield: a
        shard loop's share of a batch, and the fallbacks."""
        for item in self._slices([(complete, entries, results)]):
            self._call(*item, t_set_ns)

    def _call(self, complete, entries, results, t_set_ns: int) -> None:
        if complete is _set_futures:
            self.adapter_completed += len(entries)
        else:
            self.batch_completed += len(entries)
            self.batch_completions += 1
        try:
            complete(entries, results, t_set_ns)
        except Exception:
            # a completion accounts for its own publishes' errors; one
            # that raises anyway must not take the drain loop with it
            _log.exception(
                "staged completion failed for %d publishes", len(entries)
            )

    @staticmethod
    def _stamp_round_trip(ck, dispatch, d2h) -> None:
        """A sampled clock's issue -> resolved stretch (the device round
        trip), from its batch's own windows."""
        if dispatch is not None and d2h is not None:
            # tokenize + device dispatch; then kernel queue + execution;
            # then the blocking result transfer
            ck.stamp_until("h2d", dispatch[1])
            ck.stamp_until("device_dispatch", d2h[0])
            ck.stamp_until("d2h", d2h[1])
        else:
            ck.stamp("device_batch")

    def _fallback_all(self, items, klass: Optional[str] = "stop") -> None:
        """Complete parked ``(topic, entry)`` items via the host walk,
        now, in the order given, through the entries' own completions
        (each on the loop that parked it). The caller sees to it that
        nothing parked before them is still in the stage. A held member
        among them keeps the result it has. A host walk that raises hands its exception
        to the completion as that publish's result. ``klass`` is the
        fallback class counted (None: not a counted fallback)."""
        if not items:
            return
        entries, results = [], []
        walked = 0
        for topic, entry in items:
            entries.append(entry)
            if entry.held is not None:
                results.append(entry.held)
                continue
            walked += 1
            try:
                results.append(self.host_fallback(topic))
            except Exception as e:  # pragma: no cover - host walk is total
                results.append(e)
        if klass is not None and walked and self.telemetry is not None:
            self.telemetry.note_fallback(klass, walked)
        for complete, es, rs in self._hand_over(entries, results, 0):
            self._run_slices(complete, es, rs, 0)


# -- restart re-registration (the durable session plane's bulk path) ---------


def bulk_register(topics, entries, batch: int = 4096) -> tuple[int, int]:
    """Re-register persisted subscriptions through the trie's bulk-insert
    path in fixed-size batches — the restart leg of the durable session
    plane (ISSUE 16). ``entries`` yield ``(client_id, Subscription)``;
    each chunk of ``batch`` pays ONE trie lock acquisition via
    ``TopicsIndex.subscribe_bulk`` instead of a per-subscription
    ``subscribe`` round-trip, which is the difference between a bounded
    and an unbounded restart at a million sessions. Returns
    ``(new_subscriptions, batches)`` so recovery metrics can prove the
    path was actually batched.

    The whole loop, not a chunk, is one bulk load of the trie
    (``TopicsIndex.bulk_load``, re-entrant: a caller that restores a
    stored batch a call holds one open around its calls): an observing
    ``DeltaMatcher`` rebuilds nothing while it runs, answers publishes
    from the host trie meanwhile, and builds its table once when the
    outermost load closes, whether this returns or raises."""
    added = 0
    batches = 0
    chunk: list = []
    with topics.bulk_load():
        for entry in entries:
            chunk.append(entry)
            if len(chunk) >= batch:
                added += topics.subscribe_bulk(chunk)
                batches += 1
                chunk = []
        if chunk:
            added += topics.subscribe_bulk(chunk)
            batches += 1
    return added, batches


def bulk_inflight(clients, messages, batch: int = 4096) -> tuple[int, int]:
    """Restore persisted inflight (QoS1/QoS2 window) messages in
    fixed-size per-client batches via ``Inflight.set_bulk`` — one lock
    acquisition per chunk, mirroring :func:`bulk_register` (ISSUE 17
    satellite: the unacked window survives kill -9 through the same
    batched restart leg as subscriptions and retained). ``messages``
    yield storage ``Message`` records (``.client`` + ``.to_packet()``);
    records for clients with no live session are skipped (their session
    re-inflates them on reconnect via the subscription restore path).
    Returns ``(restored, batches)``."""
    restored = 0
    batches = 0
    per_client: dict = {}
    for msg in messages:
        cl = clients.get(msg.client)
        if cl is None:
            continue
        chunk = per_client.setdefault(msg.client, (cl, []))[1]
        chunk.append(msg.to_packet())
        if len(chunk) >= batch:
            restored += cl.state.inflight.set_bulk(chunk)
            batches += 1
            chunk.clear()
    for cl, chunk in per_client.values():
        if chunk:
            restored += cl.state.inflight.set_bulk(chunk)
            batches += 1
    return restored, batches


def bulk_retain(topics, packets, batch: int = 4096) -> tuple[int, int]:
    """Re-seat persisted retained messages in fixed-size batches via
    ``TopicsIndex.retain_bulk`` (one lock acquisition per chunk).
    Returns ``(retained, batches)``."""
    retained = 0
    batches = 0
    chunk: list = []
    for pk in packets:
        chunk.append(pk)
        if len(chunk) >= batch:
            retained += topics.retain_bulk(chunk)
            batches += 1
            chunk = []
    if chunk:
        retained += topics.retain_bulk(chunk)
        batches += 1
    return retained, batches
