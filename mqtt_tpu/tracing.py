"""End-to-end trace plane: sampled per-publish span trees, Chrome
trace-event export, and the device pipeline profiler.

PR 3's telemetry histograms answer "what is the p99 of each stage" —
they cannot answer "where did THIS slow publish spend its time", which
is the question ROADMAP item 1 (the 40-100x kernel->e2e gap, owned by
host<->device staging) actually needs, and the per-message latency
decomposition the IoT broker benchmarking study treats as the primary
comparison axis (PAPERS.md). This module adds:

- ``Tracer``: a lock-cheap bounded ring of finished spans plus seeded
  trace/span id generation. 1-in-N publishes (``Options.trace_sample``,
  same knob family as ``telemetry_sample``) carry a ``PublishTrace`` —
  a :class:`~mqtt_tpu.telemetry.StageClock` that also owns a trace id —
  and at fan-out the clock's stamps become one span tree: a root
  ``publish`` span with one child per pipeline stage
  (decode -> admission -> staging_wait -> h2d -> device_dispatch ->
  d2h -> fanout), plus per-peer ``forward`` spans at the origin worker
  and a ``remote_fanout`` span at each receiving worker (the trace id
  rides the cluster frames — TD-MQTT-style transparent cross-broker
  tracing). The ring exports as Chrome trace-event JSON
  (Perfetto-loadable) at ``GET /traces`` and in trigger dumps.
- ``BatchProfile`` / ``DeviceProfiler``: one record per device batch,
  carried with the batch, holding the batch's span tree on
  ``perf_counter_ns`` (parked -> formed -> tokenize -> H2D + dispatch ->
  D2H sync -> resolve -> handed over to its completion and fanned out).
  The profiler folds the
  in-flight windows (dispatch returned -> sync done) into a **duty
  cycle** and an **overlap ratio** AS THE HOST SEES THEM — upper bounds
  on device busy time, not device busy time (a chip the device trace
  shows 0.04% busy reads tens of percent here) — and the **staging
  idle-gap** histogram.
- ``TraceSlice`` / ``last_slice()``: while a ``jax.profiler`` session is
  live (``TraceAnnotation.is_enabled()``: ``start_trace``, the profiler
  server, ``Options.trace_jax_profiler_dir``) the profiler keeps the
  batch records, enters the busy spans as ``TraceAnnotation`` blocks (so
  they lie on the device trace's own clock in the ``.xplane.pb``), and
  brackets the session with two snapshots (per-thread CPU, matcher
  topics, collections by generation, loop counters, loop heartbeat).
  Between them the loop's own time is booked whole (``_LoopFrame``: a
  timing proxy around the loop's selector): every iteration, every
  ``select()``, the longest iteration with what was inside it. When the
  session ends the slice freezes; ``last_slice()`` returns the newest.
- ``check_trace_events``: a ~20-line pure-Python validator for the
  exported JSON (the /traces analog of ``telemetry.check_exposition``),
  used by CI's trace-scrape gate and the test suite.

The unsampled hot path pays one modulo; everything else is on by
default behind ``Options.trace`` / the ``trace_*`` config knobs.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import itertools
import json
import os
import random
import threading
import time
import zlib
from typing import Any, Optional

# DEVICE_SUBSTAGES / TRACE_USER_PROPERTY are canonical in telemetry.py
# (this module imports telemetry, never the reverse); re-exported here
# because they are trace-plane concepts callers look for in this module
from .telemetry import (  # noqa: F401  (re-exports)
    DEVICE_SUBSTAGES,
    TRACE_USER_PROPERTY,
    Histogram,
    StageClock,
)


class PublishTrace(StageClock):
    """A stage clock that is also a trace context: carries the trace id
    and the pre-allocated root span id, so spans recorded BEFORE the
    clock finishes (per-peer forwards) can already parent on the root.
    Rides the pipeline exactly like a plain StageClock — every layer
    that stamps a StageClock stamps this unchanged."""

    __slots__ = ("tracer", "trace_id", "span_id")

    def __init__(self, tracer: "Tracer", trace_id: Optional[str] = None) -> None:
        super().__init__()
        self.tracer = tracer
        self.trace_id = trace_id if trace_id else tracer.new_trace_id()
        self.span_id = tracer.new_span_id()


class Tracer:
    """Bounded span ring + id generation + Chrome trace-event export.

    Spans are stored as plain tuples ``(name, cat, trace_id, span_id,
    parent_id, t0_perf, dur_s, args)``; the ring append is the only
    hot-path cost and runs under a lock held for one append (the same
    posture as the flight recorder's ring). Export converts perf_counter
    times to wall-anchored microseconds, so two workers' exports merge
    into one coherent timeline (same machine, same anchor source).
    ``seed`` makes trace/span ids deterministic for tests."""

    def __init__(
        self,
        sample: int = 64,
        ring: int = 4096,
        seed: Optional[int] = None,
        registry: Any = None,
    ) -> None:
        self.sample = max(0, int(sample))
        # lock-plane adoption (mqtt_tpu.utils.locked): span appends from
        # data-plane threads race /traces exports under this lock
        from .utils.locked import InstrumentedLock

        self._lock = InstrumentedLock("trace_ring")
        self.ring: collections.deque = collections.deque(maxlen=max(16, int(ring)))
        self._rng = random.Random(seed)
        # worker id in a mesh (mqtt_tpu.cluster sets it); the export's
        # Chrome-trace pid, so merged multi-worker files keep one track
        # group per worker
        self.pid = 0
        self.spans_total = 0
        self.publishes_total = 0
        # client-driven adoption (v5 trace-id user property) is rate-
        # bounded: a client stamping EVERY publish must not buy itself
        # 100% tracing (bypassing trace_sample) and flood the ring,
        # evicting the organic samples. 0 disables adoption entirely.
        self.adopt_max_per_s = 64
        self._adopt_window = 0.0  # monotonic second the count belongs to
        self._adopt_count = 0
        # wall anchor for export: perf_counter + anchor = unix seconds.
        # brokerlint: ok=R3 a one-shot wall anchor so exported trace timestamps are operator-correlatable; all durations stay monotonic
        self._anchor = time.time() - time.perf_counter()
        if registry is not None:
            registry.counter(
                "mqtt_tpu_trace_spans_total",
                "Spans recorded into the trace ring",
                fn=lambda: self.spans_total,
            )
            registry.counter(
                "mqtt_tpu_trace_publishes_total",
                "Publishes that carried a sampled trace context",
                fn=lambda: self.publishes_total,
            )

    # -- ids ----------------------------------------------------------------

    def new_trace_id(self) -> str:
        with self._lock:
            return f"{self._rng.getrandbits(64):016x}"

    def new_span_id(self) -> str:
        with self._lock:
            return f"{self._rng.getrandbits(48):012x}"

    # -- recording ----------------------------------------------------------

    def publish_trace(self, trace_id: Optional[str] = None) -> PublishTrace:
        """A trace context for one publish (the caller owns the 1-in-N
        sampling verdict — mqtt_tpu.telemetry.Telemetry.publish_clock)."""
        return PublishTrace(self, trace_id)

    def allow_adopt(self) -> bool:
        """The rate verdict for one client-supplied trace-id adoption:
        at most ``adopt_max_per_s`` per wall second, excess publishes
        stay untraced (they still flow normally)."""
        if self.adopt_max_per_s <= 0:
            return False
        now = time.monotonic()
        with self._lock:
            if now - self._adopt_window >= 1.0:
                self._adopt_window = now
                self._adopt_count = 0
            if self._adopt_count >= self.adopt_max_per_s:
                return False
            self._adopt_count += 1
            return True

    def add_span(
        self,
        name: str,
        cat: str,
        trace_id: str,
        span_id: str,
        parent_id: Optional[str],
        t0: float,
        dur: float,
        args: Optional[dict] = None,
    ) -> None:
        """Record one finished span (``t0`` in perf_counter seconds)."""
        with self._lock:
            self.ring.append(
                (name, cat, trace_id, span_id, parent_id, t0, dur, args)
            )
            self.spans_total += 1

    def finish_publish(self, trace: PublishTrace, topic: str = "", qos: int = 0) -> None:
        """Fold one finished publish trace into the ring: the root
        ``publish`` span plus one child span per stamped stage, laid out
        back-to-back from the clock's start (a StageClock records each
        stage's duration since the previous stamp, so the absolute
        boundaries reconstruct exactly)."""
        spans = []
        t = trace.t0
        for stage, dt in trace.stages:
            spans.append(
                (stage, "stage", trace.trace_id, self.new_span_id(),
                 trace.span_id, t, dt, None)
            )
            t += dt
        spans.append(
            ("publish", "publish", trace.trace_id, trace.span_id, None,
             trace.t0, trace.total(),
             # the root span carries the delivery SLI headline (ISSUE
             # 14): a Perfetto view of a breach exemplar shows the same
             # arrival->flush number the delivery-latency histogram
             # recorded, with the stage breakdown nested under it
             {"topic": topic, "qos": qos,
              "delivery_ms": round(trace.total() * 1e3, 3),
              # the device batch that carried this publish (its
              # BatchProfile.seq: the ``batch`` arg of the mqtt/* host
              # annotations in a profiler trace); None = never staged
              **({} if trace.batch is None else {"batch": trace.batch})})
        )
        with self._lock:
            self.ring.extend(spans)
            self.spans_total += len(spans)
            self.publishes_total += 1

    # -- export -------------------------------------------------------------

    def export(self) -> dict:
        """The ring as a Chrome trace-event document (Perfetto loads it
        directly: open ui.perfetto.dev and drop the JSON in). Spans of
        one trace share a ``tid`` derived from the trace id, so
        concurrent traces render as separate nested tracks. After them,
        the newest profiler slice (``slice_events``): the span trees of
        its device batches, which a sampled publish's ``batch`` arg joins
        it to, and the full collections inside it."""
        with self._lock:
            spans = list(self.ring)
        events = []
        for name, cat, trace_id, span_id, parent_id, t0, dur, args in spans:
            a = {"trace_id": trace_id, "span_id": span_id}
            if parent_id is not None:
                a["parent_id"] = parent_id
            if args:
                a.update(args)
            events.append(
                {
                    "name": name,
                    "cat": cat,
                    "ph": "X",
                    "ts": round((t0 + self._anchor) * 1e6, 3),
                    "dur": round(dur * 1e6, 3),
                    "pid": self.pid,
                    # stable per-trace track id; crc so ADOPTED ids (any
                    # client-chosen string) never break the export
                    "tid": zlib.crc32(trace_id.encode()) % 1_000_000,
                    "args": a,
                }
            )
        sl = last_slice()
        if sl is not None:
            events.extend(slice_events(sl, self._anchor, self.pid))
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_json(self) -> str:
        return json.dumps(self.export())


def check_trace_events(doc) -> int:
    """A minimal pure-Python Chrome trace-event checker (the /traces
    analog of ``telemetry.check_exposition``): the document must carry a
    non-empty ``traceEvents`` list of well-formed complete events.
    Unresolved parent ids are allowed — one worker's export of a
    cross-worker trace legitimately references the peer's spans.
    Accepts a JSON string or a parsed dict; returns the event count."""
    if isinstance(doc, (str, bytes)):
        doc = json.loads(doc)
    events = doc.get("traceEvents") if isinstance(doc, dict) else None
    if not isinstance(events, list) or not events:
        raise ValueError("no traceEvents")
    for i, ev in enumerate(events):
        if not isinstance(ev, dict) or not isinstance(ev.get("name"), str) or not ev["name"]:
            raise ValueError(f"event {i}: missing name")
        if ev.get("ph") != "X":
            raise ValueError(f"event {i}: ph must be 'X' (complete)")
        for k in ("ts", "dur"):
            if not isinstance(ev.get(k), (int, float)) or ev[k] < 0:
                raise ValueError(f"event {i}: bad {k}: {ev.get(k)!r}")
        if not isinstance(ev.get("pid"), int) or not isinstance(ev.get("tid"), int):
            raise ValueError(f"event {i}: pid/tid must be ints")
        args = ev.get("args")
        if args is not None and not isinstance(args, dict):
            raise ValueError(f"event {i}: args must be a dict")
    return len(events)


# the busy spans of a batch: record slot -> span name
BUSY_SPANS = {
    "tokenize": "mqtt/tokenize",
    "h2d_dispatch": "mqtt/h2d_dispatch",
    "d2h_sync": "mqtt/d2h.sync",
    "resolve": "mqtt/resolve",
    # the batch's hand-over: its results in hand -> its last publish
    # fanned out (the name dates from one future a publish; readers
    # know it by this name)
    "deliver": "mqtt/deliver.futures",
}


class BatchProfile:
    """One device batch's span tree, created when the batch forms and
    carried WITH the batch (the resolver closure and the staging queue
    both hold it), so a boundary can never be attributed to a different
    batch — the resilience wrapper resolves batches eagerly on guard
    threads, concurrently and potentially out of order, which rules out
    any "most recent resolve" pairing. Every boundary is one
    ``time.perf_counter_ns()`` read where the work happens (``span``);
    the ``dispatch`` / ``d2h`` windows (seconds, ``perf_counter``: the
    same clock) and the staging leg-wait histograms are fed from the
    same stamps. Assignments are atomic under the GIL; a reader sees
    either None or a complete span. The exact-map fast path, host
    fallbacks and the sharded matcher leave the matcher's spans None."""

    __slots__ = (
        "dispatch", "d2h", "d2h_bytes", "compact", "devices",
        "seq", "kept", "topics", "bucket", "depth",
        "submit_first_ns", "wait_n", "wait_sum_ns",
        "formed_ns", "issue_start_ns", "issue_end_ns", "sync_start_ns",
        "tokenize", "h2d_dispatch", "d2h_sync", "resolve", "deliver",
        "set_sum_ns", "hold", "hold_n", "hold_sum_ns",
    )

    def __init__(self) -> None:
        # (start, end) of the tokenize+dispatch issue leg; None until
        # the batch actually dispatched to the device (the exact-map
        # fast path and host fallbacks never set it)
        self.dispatch: Optional[tuple[float, float]] = None
        # (start, end) of the blocking D2H result sync
        self.d2h: Optional[tuple[float, float]] = None
        # the D2H result bytes this batch moved; 0 = the matcher did not
        # stamp this batch
        self.d2h_bytes = 0
        # True when the result came back as compacted (topic, sid) pairs
        self.compact = False
        # device ids this batch's window ran on, stamped by the matcher
        # at dispatch (TpuMatcher: the output buffer's device; sharded:
        # every mesh device). None = unstamped, folds as device 0.
        self.devices: Optional[tuple] = None
        # the identifier the batch's spans share (DeviceProfiler.
        # open_batch numbers them; a record made without a profiler has
        # none), and whether a live profiler session keeps this record
        self.seq: Optional[int] = None
        self.kept = False
        # args of the root span: topics in the batch, the padded bucket
        # they ran in, batches in the pipeline when this one formed
        self.topics = 0
        self.bucket = 0
        self.depth = 0
        # mqtt/stage.wait (kept records only): park() -> batch formed
        # over the ``wait_n`` members whose park() was stamped (those
        # parked while the session was live), as the oldest and the sum
        self.submit_first_ns: Optional[int] = None
        self.wait_n = 0
        self.wait_sum_ns = 0
        # staging's boundaries (perf_counter_ns): batch formed; issue()
        # starts on the h2d thread; issue() returned; sync() starts on a
        # resolver thread
        self.formed_ns: Optional[int] = None
        self.issue_start_ns: Optional[int] = None
        self.issue_end_ns: Optional[int] = None
        self.sync_start_ns: Optional[int] = None
        # the busy spans, (start_ns, end_ns) each: BUSY_SPANS
        self.tokenize: Optional[tuple[int, int]] = None
        self.h2d_dispatch: Optional[tuple[int, int]] = None
        self.d2h_sync: Optional[tuple[int, int]] = None
        self.resolve: Optional[tuple[int, int]] = None
        self.deliver: Optional[tuple[int, int]] = None
        # sum over the members of the instant their result was in hand
        # (kept records only): ``topics`` x the first instant of the
        # batch's hand-over to its completion, where a publish's wait
        # for the resolve ends and its wait for the loop begins
        self.set_sum_ns = 0
        # mqtt/order.hold: the held members that rode with this batch
        # (staging: fallbacks that joined the order), from each one's
        # host walk done to the batch's hand-over, as the oldest's
        # (start_ns, end_ns), the count and the sum: what the order
        # guarantee cost them
        self.hold: Optional[tuple[int, int]] = None
        self.hold_n = 0
        self.hold_sum_ns = 0

    def order_hold(self, held_ns: list, now_ns: int) -> None:
        """Fold the held members' host-walk-done instants into
        mqtt/order.hold; their completion starts at ``now_ns``."""
        if held_ns:
            self.hold_n = n = len(held_ns)
            self.hold = (min(held_ns), now_ns)
            self.hold_sum_ns = n * now_ns - sum(held_ns)

    def stage_wait(self, submits_ns: list) -> None:
        """Fold the stamped members' park() instants into
        mqtt/stage.wait (``formed_ns`` is already set)."""
        if submits_ns:
            self.wait_n = n = len(submits_ns)
            self.submit_first_ns = min(submits_ns)
            self.wait_sum_ns = n * self.formed_ns - sum(submits_ns)

    def spans(self) -> list:
        """The tree as ``(name, start_ns, end_ns, args)``, root first (a
        batch with no stamped submit starts when it formed, or with its
        oldest held member's host walk); a span
        whose boundaries were never stamped is left out. Served on
        ``/traces`` for the newest slice's batches (``slice_events``)."""
        out = []
        seq = self.seq
        first = self.submit_first_ns
        if self.formed_ns is not None and self.deliver is not None:
            start = self.formed_ns if first is None else first
            if self.hold is not None:
                start = min(start, self.hold[0])
            out.append((
                "mqtt/batch", start, self.deliver[1],
                {"batch": seq, "topics": self.topics,
                 "bucket": self.bucket, "depth": self.depth},
            ))
        if first is not None:
            out.append((
                "mqtt/stage.wait", first, self.formed_ns,
                {"batch": seq, "n": self.wait_n, "sum_ns": self.wait_sum_ns},
            ))
        for name, t0, t1 in (
            ("mqtt/issue.handoff", self.formed_ns, self.issue_start_ns),
            ("mqtt/pipeline.wait", self.issue_end_ns, self.sync_start_ns),
        ):
            if t0 is not None and t1 is not None:
                out.append((name, t0, t1, {"batch": seq}))
        if self.hold is not None:
            out.append((
                "mqtt/order.hold", self.hold[0], self.hold[1],
                {"batch": seq, "n": self.hold_n, "sum_ns": self.hold_sum_ns},
            ))
        for slot, name in BUSY_SPANS.items():
            window = getattr(self, slot)
            if window is not None:
                out.append((name, window[0], window[1], {"batch": seq}))
        return out


@contextlib.contextmanager
def span(rec: Optional[BatchProfile], slot: str):
    """One busy span of ``rec`` around the work: stamps
    ``rec.<slot> = (start_ns, end_ns)`` when the work completes and,
    while a profiler session keeps the record, is also a
    ``jax.profiler.TraceAnnotation`` carrying the batch's number (so the
    span lies on the device trace's own clock). ``rec`` None: nothing."""
    if rec is None:
        yield
        return
    if rec.kept:
        from jax.profiler import TraceAnnotation

        with TraceAnnotation(BUSY_SPANS[slot], batch=rec.seq):
            t0 = time.perf_counter_ns()
            yield
            setattr(rec, slot, (t0, time.perf_counter_ns()))
    else:
        t0 = time.perf_counter_ns()
        yield
        setattr(rec, slot, (t0, time.perf_counter_ns()))


# -- collections ----------------------------------------------------------------

YOUNG_PAUSE_NS = 1_000_000  # a young collection worth keeping: over 1 ms


class Gen2Pauses:
    """The interpreter's collections and how long each held the process:
    the one ``gc.callbacks`` hook, for all three generations. Over a heap
    of a million subscriptions a full collection is the first suspect for
    a whole-broker stall, and a young one for a stall of the loop; these
    are the counters that can convict or clear them. Full collections
    keep what they had (``recent``, ``hist``: ``/metrics``
    ``mqtt_tpu_gc_gen2_pause_seconds``; ``/traces``: the pauses inside
    the newest profiler slice). Every generation adds to
    ``gc_pause_ns``; a young collection of over 1 ms is kept in
    ``young_recent``. While a profiler session is live (``annotate``,
    set by the armed ``DeviceProfiler``) each collection is a
    ``mqtt/gc`` annotation too."""

    def __init__(self) -> None:
        # (end_ns, duration_ns) of the newest full pauses, perf_counter_ns
        self.recent: collections.deque = collections.deque(maxlen=64)
        self.hist = Histogram()
        # by generation: cumulative pause, and when the newest one
        # ended; and the pauses' sum over all three
        self.gc_pause_ns = [0, 0, 0]
        self.last_end_ns = [0, 0, 0]
        self.pause_ns_total = 0
        # (end_ns, duration_ns, generation) of the newest collections
        # of generations 0 and 1 that took over YOUNG_PAUSE_NS
        self.young_recent: collections.deque = collections.deque(maxlen=64)
        # jax.profiler.TraceAnnotation while a session is live, else None
        self.annotate: Any = None
        self._span: Any = None
        self._t0 = 0
        self._installed = False

    def install(self) -> None:
        if not self._installed:
            self._installed = True
            gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        gen = info["generation"]
        if phase == "start":
            annotate = self.annotate
            if annotate is not None:
                self._span = annotate("mqtt/gc", gen=gen)
                self._span.__enter__()
            self._t0 = time.perf_counter_ns()
            return
        now = time.perf_counter_ns()
        dt = now - self._t0
        span, self._span = self._span, None
        if span is not None:
            span.__exit__(None, None, None)
        self.gc_pause_ns[gen] += dt
        self.last_end_ns[gen] = now
        self.pause_ns_total += dt
        if gen == 2:
            self.recent.append((now, dt))
            self.hist.observe(dt / 1e9)
        elif dt > YOUNG_PAUSE_NS:
            self.young_recent.append((now, dt, gen))


# process-wide, as the collector is (ops/devicestats.LEDGER's posture);
# hooked in by the first DeviceProfiler
GC2 = Gen2Pauses()


# -- the slice a profiler session leaves behind ---------------------------------

MAX_SLICE_BATCHES = 16_384
BEAT_NS = 5_000_000  # the loop heartbeat's interval while armed
CLOCK_NS = 1_000_000_000  # a mqtt/clock mark at the loop's first turn this long after the last
TRACES_BATCHES = 256  # a slice's newest batches, as /traces serves them


def thread_group(name: str) -> str:
    """Which of the slice's CPU groups a thread of this process is in:
    the event loops; the match path off the loop (the h2d issue thread,
    the resolver threads and the resilience guard pool, which is where
    tokenize, dispatch, sync and resolve run when ``matcher_resilience``
    is on: the threads cannot be told apart by stage); or ``other``
    (rebuild thread, sampler, breaker probe, flight writers)."""
    if name == "MainThread" or name.startswith("mqtt-tpu-shard-"):
        return "loop"
    if name.startswith(("mqtt-tpu-h2d", "mqtt-tpu-resolve", "mqtt-tpu-guard")):
        return "match"
    return "other"


_TICK_NS = 1_000_000_000 // os.sysconf("SC_CLK_TCK")


def thread_cpu_ns() -> dict:
    """CPU time (user + system) of every live Python-visible thread, by
    name, from ``/proc/self/task/<tid>/stat``: the kernel's own
    accounting, at its tick (10 ms), and safe for a thread that exits
    meanwhile (the file is gone: skipped), which a ``pthread_t`` is not."""
    out: dict[str, int] = {}
    for t in threading.enumerate():
        try:
            with open(f"/proc/self/task/{t.native_id}/stat", "rb") as f:
                # after "(comm)": state is field 3, utime 14, stime 15
                fields = f.read().rpartition(b")")[2].split()
            ns = (int(fields[11]) + int(fields[12])) * _TICK_NS
        except (OSError, IndexError, ValueError):
            continue  # the thread ended, or no procfs here
        out[t.name] = out.get(t.name, 0) + ns
    return out


class TraceSlice:
    """What one profiler session left: snapshot ``a`` from when the
    program noticed the session, ``b`` from when it noticed its end, and
    the records of the batches in flight or formed in between
    (``batches``, at most ``MAX_SLICE_BATCHES``; one still in flight at
    ``b`` completes later, on the same object). Plain data: the readers
    (``benchmark/layer_metrics``) do the arithmetic."""

    __slots__ = ("a", "b", "batches")

    def __init__(self, a: dict, b: dict, batches: list) -> None:
        self.a, self.b, self.batches = a, b, batches

    def cpu_ns_by_group(self) -> dict:
        """CPU between ``a`` and ``b`` by thread group; ``other`` also
        takes what no named thread accounts for (process CPU less the
        named sum: the XLA runtime's own threads)."""
        out = {"loop": 0, "match": 0, "other": 0}
        before = self.a["thread_cpu_ns"]
        named = 0
        for name, ns in self.b["thread_cpu_ns"].items():
            d = ns - before.get(name, 0)
            if d > 0:
                out[thread_group(name)] += d
                named += d
        whole = self.b["process_cpu_ns"] - self.a["process_cpu_ns"]
        out["other"] += max(0, whole - named)
        return out

    def gen2_pauses(self) -> list:
        """``(end_ns, duration_ns)`` of the full collections that ended
        between ``a`` and ``b``."""
        return [
            p for p in self.b["gc2_recent"]
            if self.a["t_ns"] < p[0] <= self.b["t_ns"]
        ]

    def young_pauses(self) -> list:
        """``(end_ns, duration_ns, generation)`` of the collections of
        generations 0 and 1 that took over ``YOUNG_PAUSE_NS`` and ended
        between ``a`` and ``b``."""
        return [
            p for p in self.b.get("young_recent", ())
            if self.a["t_ns"] < p[0] <= self.b["t_ns"]
        ]


_LAST_SLICE: Optional[TraceSlice] = None


def last_slice() -> Optional[TraceSlice]:
    """The newest frozen slice of this process, or None."""
    return _LAST_SLICE


def slice_events(sl: TraceSlice, anchor: float, pid: int) -> list:
    """The newest slice as Chrome trace events for ``/traces``: the span
    tree of its newest ``TRACES_BATCHES`` batches, one track a batch
    (``args.batch`` is the number a sampled publish's root span names),
    the collections that ended inside it (every full one, the young
    ones of over a millisecond), and the loop's longest iteration with
    its parts (``stall``: ``_LoopFrame``)."""
    events = []

    def add(name, cat, t0_ns, t1_ns, tid, args):
        events.append({
            "name": name, "cat": cat, "ph": "X",
            "ts": round((t0_ns / 1e9 + anchor) * 1e6, 3),
            "dur": round((t1_ns - t0_ns) / 1e3, 3),
            "pid": pid, "tid": tid, "args": args,
        })

    for rec in sl.batches[-TRACES_BATCHES:]:
        for name, t0, t1, args in rec.spans():
            add(name, "batch", t0, t1, 1_000_000 + rec.seq % 1_000_000, args)
    for end, dur in sl.gen2_pauses():
        add("gc/gen2", "gc", end - dur, end, 999_999, {})
    for end, dur, gen in sl.young_pauses():
        add(f"gc/gen{gen}", "gc", end - dur, end, 999_999, {})
    stall = sl.b.get("stall")
    if stall is not None:
        t0 = stall["t0_ns"]
        add("loop/stall", "loop", t0, t0 + stall["busy_ns"], 999_998,
            {k: v for k, v in stall.items() if k != "t0_ns"})
    return events


class _LoopFrame:
    """The frame around an event loop's iterations: a proxy of the
    loop's selector that forwards everything and times ``select()``. It
    stands in ``loop._selector`` only while its profiler is armed
    (``DeviceProfiler._frame_in`` / ``_frame_out``, on the loop's own
    thread). Every ``select()`` closes one iteration (from the previous
    ``select()``'s return to this call: the loop's working time) and is
    itself booked as a poll that could block (``pollw``: the loop's idle
    time, plus the call) or one that could not (``poll0``: the ready
    queue was not empty, so the pure cost of the system call). The three
    add up to the time the frame stood. The longest iteration is kept
    with what was inside it: the deltas across it of the phase counters
    (``_phases``), against their values at its start: it is the loop's
    longest hold (``loop_stall_max_ns``), so no heartbeat runs through a
    loop that is framed, and the turns of the loop are its beats. Two
    clock reads and a dozen plain adds a ``select()``, on the profiler's
    counters; once a second a ``mqtt/clock`` mark."""

    def __init__(
        self, selector: Any, prof: "DeviceProfiler", began_ns: int, phases: tuple
    ) -> None:
        self._sel = selector
        self._select = selector.select
        self._prof = prof
        self._idle = prof.annotation
        # the calls the loop makes on its selector besides select()
        for name in ("register", "unregister", "modify", "get_key", "get_map", "close"):
            setattr(self, name, getattr(selector, name))
        # > 0: in an iteration since then; < 0: inside select() since
        # then (one field, so a snapshot off the loop reads it whole).
        # The first iteration is booked from snapshot A: the frame is
        # stood by a callback that the arming queued, so but for one
        # select() that could not block the loop worked all the while
        self.mark = began_ns
        self._saved = phases

    def __getattr__(self, name: str) -> Any:
        return getattr(self._sel, name)

    def _phases(self) -> tuple:
        prof = self._prof
        return (
            prof.ingest_busy_ns, prof.ack_busy_ns, prof.fanout_busy_ns,
            prof.slice_flush_ns, prof.send_busy_ns, GC2.pause_ns_total,
        )

    def select(self, timeout: Optional[float] = None) -> list:
        prof = self._prof
        t0 = time.perf_counter_ns()
        began = self.mark
        if began > 0:
            busy = t0 - began
            prof.iter_busy_ns += busy
            if busy > prof.stall_busy_ns:
                self._note_stall(began, busy)
        self.mark = -t0
        if timeout is None or timeout > 0:
            with self._idle("mqtt/loop.idle"):
                events = self._select(timeout)
            t1 = time.perf_counter_ns()
            prof.pollw_n += 1
            prof.pollw_ns += t1 - t0
        else:
            events = self._select(timeout)
            t1 = time.perf_counter_ns()
            prof.poll0_n += 1
            prof.poll0_ns += t1 - t0
        prof.poll_ready_n += len(events)
        self._saved = self._phases()
        self.mark = t1
        if t1 - prof._clock_ns >= CLOCK_NS:
            prof._clock_mark()
        return events

    def _note_stall(self, began: int, busy: int) -> None:
        """The iteration that just closed is the slice's longest."""
        prof = self._prof
        prof.stall_busy_ns = busy
        ingest, ack, fanout, flush, send, gc_ns = (
            now - was for now, was in zip(self._phases(), self._saved)
        )
        prof.stall = {
            "t0_ns": began, "busy_ns": busy,
            "ingest_ns": ingest, "ack_ns": ack,
            # the slice's joined writes are inside fan-out, the sends
            # and the collections inside whichever phase made them
            "fanout_ns": fanout, "flush_ns": flush,
            "send_ns": send, "gc_ns": gc_ns,
            # the oldest generation collected inside it, -1 where none
            "gc_gen": max(
                (g for g, end in enumerate(GC2.last_end_ns) if end > began),
                default=-1,
            ),
        }


# D2H transfer sizes: single compact rows (~tens of bytes) up to the
# dense padded geometries (tens of MB)
BYTE_BOUNDS = (
    256.0, 1024.0, 4096.0, 16384.0, 65536.0, 262144.0,
    1048576.0, 4194304.0, 16777216.0, 67108864.0,
)


class _DevWindow:
    """One device's replica of the profiler's busy/overlap/idle fold
    (ISSUE 18): same arithmetic, keyed by device id, so a single-device
    run's window 0 is bit-identical to the unlabeled aggregates (the
    test parity oracle) and a sharded run gets one window per chip."""

    __slots__ = (
        "first_t", "last_t", "busy_until", "busy_s", "window_s",
        "overlap_s", "batches", "d2h_bytes_total",
        "issue_hist", "d2h_hist", "idle_hist", "bytes_hist",
    )

    def __init__(self) -> None:
        self.first_t: Optional[float] = None
        self.last_t = 0.0
        self.busy_until = 0.0
        self.busy_s = 0.0
        self.window_s = 0.0
        self.overlap_s = 0.0
        self.batches = 0
        self.d2h_bytes_total = 0
        self.issue_hist = Histogram()
        self.d2h_hist = Histogram()
        self.idle_hist = Histogram()
        self.bytes_hist = Histogram(bounds=BYTE_BOUNDS)

    def duty_cycle(self) -> float:
        if self.first_t is None or self.last_t <= self.first_t:
            return 0.0
        return self.busy_s / (self.last_t - self.first_t)

    def overlap_ratio(self) -> float:
        return self.overlap_s / self.window_s if self.window_s > 0 else 0.0


class DeviceProfiler:
    """Host-side device pipeline profiler: each batch's boundaries land
    on its own :class:`BatchProfile` record and fold into duty-cycle /
    overlap / idle-gap aggregates.

    A batch's **in-flight window** runs from dispatch-return (the kernel
    is queued and the host moves on) to the end of the blocking D2H
    sync. That is what the HOST can see: it contains the kernel and the
    transfer but also every wait around them, so the aggregates are
    upper bounds on device busy time, never device busy time (on the
    v5e the device trace read 0.04% busy where these read tens of
    percent; the device's own number is ``device_idle_share`` from a
    ``jax.profiler`` trace). Aggregates:

    - ``duty_cycle`` = union of in-flight windows / wall time since the
      first dispatch: the share of wall time with a batch in flight as
      the host sees it.
    - ``overlap_ratio`` = overlapped window time / summed window time —
      how deep the staging pipeline actually runs (0 = strictly serial,
      approaching (depth-1)/depth for a depth-N pipeline).
    - ``idle_gap`` histogram = stretches with no batch in flight.

    Dispatches and resolves may come from different threads (the
    staging loop issues on the h2d thread; resolves run in an executor
    or on resilience guard threads); everything mutates under one lock,
    held for arithmetic only.

    **The armed state.** ``poll()`` (once per batch from the staging
    collector, once per sweep from the sampling profiler's thread)
    compares ``jax.profiler.TraceAnnotation.is_enabled()`` with
    ``armed``. Off -> on: snapshot A, keep every record from now on
    (and those in flight), start the loop heartbeat. On -> off:
    snapshot B, freeze a :class:`TraceSlice` (``last_slice()``). The
    per-publish loop counters (``note_ingest`` / ``note_acks`` /
    ``note_fanout``) count only while armed, and only then does a
    :class:`_LoopFrame` stand around the selector of ``loop``: the
    loop's ledger (``poll0_*``, ``pollw_*``, ``poll_ready_n``,
    ``iter_busy_ns``, ``stall``), whose longest iteration is
    ``loop_stall_max_ns`` and whose turns are ``loop_beats``. A loop
    without a ``_selector`` (uvloop, proactor) leaves the ledger out of
    the snapshots and gets the 5 ms heartbeat for those two instead.
    ``mqtt/clock`` annotations (at arming, then one a second, from the
    frame or the heartbeat) carry ``perf_counter_ns`` into the
    profiler's own file: the offset between its clock and every
    boundary stamped here."""

    def __init__(self, registry: Any = None) -> None:
        self._lock = threading.Lock()
        self._registry = registry
        # per-device window replicas (ISSUE 18), keyed by device id;
        # mutated under _lock, child registration happens outside it
        self._dev: dict[int, _DevWindow] = {}
        self.batches = 0
        self._first_t: Optional[float] = None
        self._last_t = 0.0
        self._busy_until = 0.0
        self._busy_s = 0.0  # union of device windows
        self._window_s = 0.0  # sum of device windows
        self._overlap_s = 0.0
        # -- the armed state (class docstring) --
        self.armed = False
        self._arm_lock = threading.Lock()
        self._seq = itertools.count(1)
        # the newest records, so that arming finds the batches in flight
        self._recent: collections.deque = collections.deque(maxlen=8)
        self._kept: list = []
        self._snap_a: Optional[dict] = None
        self._is_enabled: Any = None  # TraceAnnotation.is_enabled, on first poll
        self._annotation: Any = None  # TraceAnnotation, on first use
        # set by whoever owns them: the served matcher's MatcherStats
        # (server), the loop the stage runs on (MatchStage.start), and
        # the broker's own running counts for the snapshots (server:
        # ``order_held``, ``deliveries``, ``socket_sends``)
        self.matcher_stats: Any = None
        self.loop: Any = None
        self.counters: Any = None
        # per-publish loop counters, cumulative ns / counts, armed only:
        # a scan's frames in hand -> its publishes parked (ingest), the
        # batch's results in hand -> this publish's fan-out starts
        # (fanout_wait), fan-out start -> flush done (fanout_busy)
        self.ingest_busy_ns = 0
        self.ingest_n = 0
        # the same stretch of a scan that held no publish, over its
        # PUBACK frames
        self.ack_busy_ns = 0
        self.ack_n = 0
        self.fanout_wait_ns = 0
        self.fanout_busy_ns = 0
        self.fanout_n = 0
        # of fanout_busy_ns, the joined writes at the slices' ends
        self.slice_flush_ns = 0
        # around the calls that reach a socket and count
        # ``_Ops.socket_sends`` (clients._send, the native flush):
        # inside ingest, fan-out or neither
        self.send_busy_ns = 0
        # the loop's ledger (_LoopFrame adds to it, on the loop's own
        # thread): select() calls that could not block and that could,
        # events they returned, the summed iterations, and the longest
        # iteration since arming with its parts
        self.poll0_n = 0
        self.poll0_ns = 0
        self.pollw_n = 0
        self.pollw_ns = 0
        self.poll_ready_n = 0
        self.iter_busy_ns = 0
        self.stall: Optional[dict] = None
        self.stall_busy_ns = 0
        self._frame: Optional[_LoopFrame] = None  # around loop's selector, while armed
        self._framed = False  # this slice has a ledger (loop has a selector)
        # the heartbeat's longest missed interval since arming, where
        # no frame stands (a framed loop's is its longest iteration)
        self.loop_stall_max_ns = 0
        self._beat_ns = 0
        self._beats = 0
        self._clock_ns = 0  # the newest mqtt/clock mark
        GC2.install()
        if registry is not None:
            self.issue_hist = registry.histogram(
                "mqtt_tpu_device_issue_seconds",
                "Per-batch host tokenize + device dispatch (H2D issue) wall time",
            )
            self.d2h_hist = registry.histogram(
                "mqtt_tpu_device_d2h_seconds",
                "Per-batch blocking D2H result-sync wall time",
            )
            self.idle_gap_hist = registry.histogram(
                "mqtt_tpu_device_idle_gap_seconds",
                "Device-idle stretches between consecutive batch windows",
            )
            self.compact_d2h_hist = registry.histogram(
                "mqtt_tpu_device_compact_d2h_seconds",
                "Blocking D2H sync wall time of compacted-result batches "
                "(the compaction d2h leg)",
            )
            registry.gauge(
                "mqtt_tpu_device_duty_cycle_ratio",
                "Share of wall time since first dispatch with a batch in "
                "flight (dispatch returned to D2H sync done) as the host "
                "sees it: an upper bound on device busy time, not device "
                "busy time",
                fn=self.duty_cycle,
            )
            registry.gauge(
                "mqtt_tpu_device_overlap_ratio",
                "Overlapped in-flight window time over summed in-flight "
                "window time, host-observed (pipeline depth proxy)",
                fn=self.overlap_ratio,
            )
            registry.histogram(
                "mqtt_tpu_gc_gen2_pause_seconds",
                "Full (generation-2) garbage collections of the "
                "interpreter: how long each held the process",
                fn=lambda: GC2.hist,
            )
        else:
            self.issue_hist = Histogram()
            self.d2h_hist = Histogram()
            self.idle_gap_hist = Histogram()
            self.compact_d2h_hist = Histogram()

    # -- recording (matcher hooks) -----------------------------------------

    def open_batch(self) -> BatchProfile:
        """A fresh, numbered per-batch record; staging and the matcher
        fill it and whoever holds the batch (the staging drain loop)
        reads it. While armed the record is kept for the slice."""
        rec = BatchProfile()
        rec.seq = next(self._seq)
        if self.armed and len(self._kept) < MAX_SLICE_BATCHES:
            rec.kept = True
            self._kept.append(rec)
        self._recent.append(rec)
        return rec

    # -- the armed state ------------------------------------------------------

    def poll(self) -> bool:
        """Follow the profiler session: arm on its start, freeze a slice
        on its end. Returns ``armed``."""
        is_enabled = self._is_enabled
        if is_enabled is None:
            from jax.profiler import TraceAnnotation

            is_enabled = self._is_enabled = TraceAnnotation.is_enabled
        on = bool(is_enabled())
        if on != self.armed:
            with self._arm_lock:
                if on != self.armed:
                    if on:
                        self._arm()
                    else:
                        self._disarm()
        return self.armed

    def _snapshot(self) -> dict:
        """One edge of a slice: the instant, CPU (process and per
        thread), topics the matcher took in, the in-flight union so far
        (``duty_cycle``'s numerator), the collections (the newest full
        ones, the pause by generation, the newest long young ones), the
        armed-only loop counters, the loop's longest hold and its beats
        (the ledger's where a frame stands, else the heartbeat's), the
        loop's ledger, and the owner's ``counters()``."""
        # what the loop moves, first and together: a snapshot is taken
        # off the loop, which goes on while the slow parts below are
        # read. The phases before the ledger, so that the iterations it
        # holds are no shorter than the phases inside them
        snap = {
            "t_ns": time.perf_counter_ns(),
            "ingest_busy_ns": self.ingest_busy_ns,
            "ingest_n": self.ingest_n,
            "ack_busy_ns": self.ack_busy_ns,
            "ack_n": self.ack_n,
            "fanout_wait_ns": self.fanout_wait_ns,
            "fanout_busy_ns": self.fanout_busy_ns,
            "fanout_n": self.fanout_n,
            "slice_flush_ns": self.slice_flush_ns,
            "send_busy_ns": self.send_busy_ns,
            "gc_pause_ns": list(GC2.gc_pause_ns),
            "loop_beats": self._beats,
            "loop_stall_max_ns": self.loop_stall_max_ns,
            **self._ledger(),
        }
        stats = self.matcher_stats
        snap.update({
            "process_cpu_ns": time.process_time_ns(),
            "thread_cpu_ns": thread_cpu_ns(),
            "topics": stats.topics if stats is not None else 0,
            "inflight_s": self._busy_s,
            "gc2_recent": list(GC2.recent),
            "young_recent": list(GC2.young_recent),
            **(self.counters() if self.counters is not None else {}),
        })
        return snap

    def _ledger(self) -> dict:
        """The loop's ledger as a snapshot takes it (nothing where the
        loop has no selector to frame). A snapshot is taken off the
        loop: what the iteration or the ``select()`` in progress has run
        so far is added from the frame's ``mark``, so the parts come to
        the time the frame stood. The loop's longest hold and its beats
        are the ledger's own: the longest iteration (the one in
        progress counts) and the turns of the loop."""
        if not self._framed:
            return {}
        busy, waited, held = self.iter_busy_ns, self.pollw_ns, self.stall_busy_ns
        frame = self._frame
        if frame is not None:
            now = time.perf_counter_ns()
            mark = frame.mark
            if mark > 0:
                busy += max(0, now - mark)
                held = max(held, now - mark)
            else:
                waited += max(0, now + mark)
        return {
            "poll0_n": self.poll0_n, "poll0_ns": self.poll0_ns,
            "pollw_n": self.pollw_n, "pollw_ns": waited,
            "poll_ready_n": self.poll_ready_n,
            "iter_busy_ns": busy,
            "stall": self.stall,
            "loop_beats": self.poll0_n + self.pollw_n,
            "loop_stall_max_ns": held,
        }

    def _trace_annotation(self) -> Any:
        cls = self._annotation
        if cls is None:
            from jax.profiler import TraceAnnotation

            cls = self._annotation = TraceAnnotation
        return cls

    def annotation(self, name: str, **args: Any) -> Any:
        """A ``jax.profiler.TraceAnnotation`` block, for the armed
        callers on the loop (``mqtt/loop.*``)."""
        return self._trace_annotation()(name, **args)

    def _clock_mark(self) -> None:
        """``perf_counter_ns`` as the argument of an annotation: the
        profiler's file then holds the offset between its own clock and
        the clock of every boundary stamped here."""
        now = self._clock_ns = time.perf_counter_ns()
        with self.annotation("mqtt/clock", perf_ns=now):
            pass

    def _arm(self) -> None:
        # the class itself, and before snapshot A: the collector's hook
        # must never import (a collection inside an import in progress
        # would import again)
        annotate = self._trace_annotation()
        inflight = [r for r in self._recent if r.deliver is None]
        for r in inflight:
            r.kept = True
        self._kept = inflight
        self.loop_stall_max_ns = 0
        self._beat_ns = 0
        self.stall = None
        self.stall_busy_ns = 0
        loop = self.loop
        self._framed = getattr(loop, "_selector", None) is not None
        self._snap_a = self._snapshot()
        self.armed = True
        GC2.annotate = annotate
        self._clock_mark()
        if loop is not None:
            self._on_loop(self._frame_in if self._framed else self._beat)

    def _on_loop(self, fn: Any, *args: Any) -> None:
        try:
            self.loop.call_soon_threadsafe(fn, *args)
        except RuntimeError:
            pass  # the loop is closed: nothing left to time or to stall

    def _frame_in(self) -> None:
        """Stand a frame around the loop's selector (on its own thread)."""
        loop = self.loop
        selector = getattr(loop, "_selector", None)
        a = self._snap_a
        if (
            self.armed and a is not None and selector is not None
            and not isinstance(selector, _LoopFrame)
        ):
            self._frame = loop._selector = _LoopFrame(
                selector, self, a["t_ns"],
                (a["ingest_busy_ns"], a["ack_busy_ns"], a["fanout_busy_ns"],
                 a["slice_flush_ns"], a["send_busy_ns"], sum(a["gc_pause_ns"])),
            )

    def _frame_out(self, frame: _LoopFrame) -> None:
        if getattr(self.loop, "_selector", None) is frame:
            self.loop._selector = frame._sel

    def _disarm(self) -> None:
        global _LAST_SLICE
        self.armed = False
        GC2.annotate = None
        if self._beat_ns:  # a stall still in progress counts
            self._note_beat(time.perf_counter_ns())
        kept, self._kept = self._kept, []
        a, self._snap_a = self._snap_a, None
        if a is not None:
            _LAST_SLICE = TraceSlice(a, self._snapshot(), kept)
        frame, self._frame = self._frame, None
        if frame is not None:
            self._on_loop(self._frame_out, frame)

    def _note_beat(self, now: int) -> None:
        late = now - self._beat_ns - BEAT_NS
        if late > self.loop_stall_max_ns:
            self.loop_stall_max_ns = late

    def _beat(self) -> None:
        """The loop heartbeat: every 5 ms while armed; how late the
        latest one ran is how long the loop was held."""
        if not self.armed:
            self._beat_ns = 0
            return
        now = time.perf_counter_ns()
        if self._beat_ns:
            self._note_beat(now)
        self._beat_ns = now
        self._beats += 1
        if now - self._clock_ns >= CLOCK_NS:
            self._clock_mark()
        self.loop.call_later(BEAT_NS / 1e9, self._beat)

    def note_ingest(self, busy_ns: int, n: int) -> None:
        """Loop time of one socket read's frame loop (armed only): from
        the scan's frames in hand to their handlers returned, with its
        ``n`` publishes decoded, admitted, acknowledged and parked with
        the stage (``clients.read``, once a scan)."""
        self.ingest_busy_ns += busy_ns
        self.ingest_n += n

    def note_acks(self, busy_ns: int, n: int) -> None:
        """Loop time of the frame loop of one socket read that held no
        publish (armed only), over its ``n`` PUBACK frames, whether an
        ack run took them or they went a frame at a time: what a
        subscriber's acknowledgements cost the loop (``clients.read``,
        once a scan)."""
        self.ack_busy_ns += busy_ns
        self.ack_n += n

    def note_fanout(self, set_ns: int, start_ns: int, done_ns: int) -> None:
        """One publish's fan-out in its batch's completion (a kept batch
        only): the batch's results were in hand at ``set_ns``
        (``BatchProfile.set_sum_ns``), this publish's ``_fan_out``
        started at ``start_ns`` (the wait for the loop: the publishes
        ahead of it in the batch, and the yields between slices) and its
        flush was done at ``done_ns`` (``server._complete_staged``)."""
        self.fanout_wait_ns += start_ns - set_ns
        self.fanout_busy_ns += done_ns - start_ns
        self.fanout_n += 1

    def note_slice_flush(self, busy_ns: int) -> None:
        """The joined writes at a completion slice's end (the sockets the
        slice corked, ``server._complete_staged``): fan-out time of the
        slice's publishes, and no publish of its own; ``slice_flush_ns``
        is the "of which"."""
        self.fanout_busy_ns += busy_ns
        self.slice_flush_ns += busy_ns

    def ensure_device(self, did: int) -> _DevWindow:
        """The window replica for one device id, creating it (and its
        ``device``-labeled metric children) on first sight. Idempotent;
        registration runs outside the fold lock."""
        with self._lock:
            dw = self._dev.get(did)
        if dw is not None:
            return dw
        dw = _DevWindow()
        with self._lock:
            have = self._dev.setdefault(did, dw)
        if have is not dw:
            return have  # lost the race: the winner registered children
        reg = self._registry
        if reg is not None:
            dev = str(did)
            reg.histogram(
                "mqtt_tpu_device_issue_seconds",
                fn=lambda d=dw: d.issue_hist, device=dev,
            )
            reg.histogram(
                "mqtt_tpu_device_d2h_seconds",
                fn=lambda d=dw: d.d2h_hist, device=dev,
            )
            reg.histogram(
                "mqtt_tpu_device_idle_gap_seconds",
                fn=lambda d=dw: d.idle_hist, device=dev,
            )
            reg.histogram(
                "mqtt_tpu_device_d2h_bytes",
                "Per-batch D2H result bytes attributed to each device "
                "(even split across a sharded batch's mesh)",
                bounds=BYTE_BOUNDS,
                fn=lambda d=dw: d.bytes_hist, device=dev,
            )
            reg.gauge(
                "mqtt_tpu_device_duty_cycle_ratio",
                fn=lambda d=dw: d.duty_cycle(), device=dev,
            )
            reg.gauge(
                "mqtt_tpu_device_overlap_ratio",
                fn=lambda d=dw: d.overlap_ratio(), device=dev,
            )
        return dw

    def note_dispatch(self, rec: BatchProfile, t0: float, t1: float) -> None:
        """One batch issued: tokenize + device dispatch ran [t0, t1];
        the device window opens at t1."""
        rec.dispatch = (t0, t1)
        self.issue_hist.observe(t1 - t0)
        for did in rec.devices or (0,):
            self.ensure_device(did).issue_hist.observe(t1 - t0)

    def note_resolve(self, rec: BatchProfile, sync_start: float, sync_end: float) -> None:
        """One batch's blocking D2H sync ran [sync_start, sync_end];
        fold its device window (dispatch-return -> sync end) into the
        busy/overlap/idle accounting. Pairing is exact — the window
        boundaries live on the batch's own record."""
        rec.d2h = (sync_start, sync_end)
        self.d2h_hist.observe(sync_end - sync_start)
        if getattr(rec, "compact", False):
            self.compact_d2h_hist.observe(sync_end - sync_start)
        if rec.dispatch is None:
            return  # never dispatched (shouldn't happen): histogram only
        t_disp = rec.dispatch[1]
        devs = rec.devices or (0,)
        windows = [self.ensure_device(d) for d in devs]
        # transfer bytes attribute evenly across a sharded batch's mesh
        # (each chip moved ~1/n of the result) — exact for one device
        per_dev_bytes = getattr(rec, "d2h_bytes", 0) // len(devs)
        with self._lock:
            end = max(sync_end, t_disp)
            self.batches += 1
            if self._first_t is None:
                self._first_t = t_disp
            self._last_t = max(self._last_t, end)
            self._window_s += end - t_disp
            if t_disp >= self._busy_until:
                if self._busy_until > 0.0:
                    self.idle_gap_hist.observe(t_disp - self._busy_until)
                self._busy_s += end - t_disp
            else:
                self._overlap_s += max(0.0, min(self._busy_until, end) - t_disp)
                self._busy_s += max(0.0, end - self._busy_until)
            self._busy_until = max(self._busy_until, end)
            # the same fold, replicated per participating device: a
            # single-device run's window 0 tracks the aggregates exactly
            for dw in windows:
                dw.batches += 1
                dw.d2h_hist.observe(sync_end - sync_start)
                if per_dev_bytes:
                    dw.bytes_hist.observe(per_dev_bytes)
                    dw.d2h_bytes_total += per_dev_bytes
                if dw.first_t is None:
                    dw.first_t = t_disp
                dw.last_t = max(dw.last_t, end)
                dw.window_s += end - t_disp
                if t_disp >= dw.busy_until:
                    if dw.busy_until > 0.0:
                        dw.idle_hist.observe(t_disp - dw.busy_until)
                    dw.busy_s += end - t_disp
                else:
                    dw.overlap_s += max(0.0, min(dw.busy_until, end) - t_disp)
                    dw.busy_s += max(0.0, end - dw.busy_until)
                dw.busy_until = max(dw.busy_until, end)

    # -- aggregates ---------------------------------------------------------

    def duty_cycle(self) -> float:
        """Share of wall time with a batch in flight, host-observed: an
        upper bound on device busy time (class docstring)."""
        with self._lock:
            if self._first_t is None or self._last_t <= self._first_t:
                return 0.0
            return self._busy_s / (self._last_t - self._first_t)

    def overlap_ratio(self) -> float:
        with self._lock:
            return self._overlap_s / self._window_s if self._window_s > 0 else 0.0

    def device_snapshot(self) -> dict:
        """Per-device window aggregates keyed by device id — what
        DeviceStatsPlane.snapshot() merges into the /devices body."""
        out: dict[int, dict] = {}
        with self._lock:
            for did, dw in sorted(self._dev.items()):
                out[did] = {
                    "duty_cycle": round(dw.duty_cycle(), 4),
                    "overlap_ratio": round(dw.overlap_ratio(), 4),
                    "batches": dw.batches,
                    "d2h_bytes_total": dw.d2h_bytes_total,
                    "issue_p99_ms": round(
                        dw.issue_hist.percentile(0.99) * 1e3, 3
                    ),
                    "d2h_p99_ms": round(dw.d2h_hist.percentile(0.99) * 1e3, 3),
                    "idle_gap_p99_ms": round(
                        dw.idle_hist.percentile(0.99) * 1e3, 3
                    ),
                }
        return out
