"""Unified telemetry plane: low-overhead metrics registry, per-publish
stage clock, Prometheus text exposition, and a trigger-dumped flight
recorder.

The $SYS gauges from the overload governor (mqtt_tpu.overload) and the
matcher breaker (mqtt_tpu.resilience) are point-in-time counters; broker
benchmarking shows the differentiator under load is TAIL latency, not
throughput (PAPERS: "Benchmarking Message Brokers for IoT Edge
Computing"), and the broker itself is the right place for in-band
introspection (MQTT+). This module therefore instruments the publish
pipeline itself:

- ``MetricsRegistry``: monotonic counters, gauges (stored or
  callback-sampled at scrape time), and fixed-bucket log-scale
  ``Histogram``s with p50/p95/p99 extraction. Families carry Prometheus
  ``# HELP``/``# TYPE`` metadata and labeled children;
  ``exposition()`` renders the text format served at ``GET /metrics``
  (listeners/http.py) and ``sys_tree()`` renders the retained
  ``$SYS/broker/telemetry/#`` map (server.publish_sys_topics).
- ``StageClock``: one sampled publish's trip through the pipeline —
  decode -> admission -> staging wait -> device batch -> fanout write —
  stamped at each boundary and aggregated per-stage into histograms.
  Sampling is 1-in-N (``Options.telemetry_sample``, default 64): the
  unsampled hot path pays one integer increment and one modulo.
- ``FlightRecorder``: a bounded ring of recent stage-clock records that
  auto-dumps a JSON snapshot to disk when the overload governor enters
  SHED or the matcher breaker trips — the first storm in production
  comes with a trace, not a shrug. Dumps are rate-limited.

All knobs live on ``Options`` (``telemetry_*``) and the config file; the
plane is ON by default.
"""

from __future__ import annotations

import json
import logging
import math
import os
import re
import tempfile
import threading
import time
from bisect import bisect_left
from collections import deque
from typing import Any, Callable, Optional

_log = logging.getLogger("mqtt_tpu.telemetry")

# the publish pipeline's stage names, in pipeline order (the flight
# recorder keys on these). The trace plane (mqtt_tpu.tracing) resolves
# ``device_batch`` into the three device sub-stages when the device
# profiler is wired; ``device_batch`` stays populated as their sum.
PUBLISH_STAGES = (
    "decode",
    "admission",
    "staging_wait",
    "h2d",
    "device_dispatch",
    "d2h",
    "device_batch",
    "encode",
    "flush",
    "fanout",
)

# the device sub-stages the staging drain loop stamps when a device
# profiler is attached (canonical here — mqtt_tpu.tracing re-exports)
DEVICE_SUBSTAGES = ("h2d", "device_dispatch", "d2h")

# the fan-out sub-stages the batched write path stamps (ISSUE 13):
# ``encode`` covers variant grouping + the per-variant frame encodes,
# ``flush`` the delivery flush (batched writev + queue fallbacks).
# ``fanout`` stays populated as their sum — same continuity contract as
# the device_batch split.
FANOUT_SUBSTAGES = ("encode", "flush")

# the MQTT v5 user-property key a trace id rides on (client-visible
# traces, and adoption of client-supplied ids — mqtt_tpu.tracing)
TRACE_USER_PROPERTY = "trace-id"

# delivery-path labels on the per-tenant delivery-latency SLI
# (ISSUE 14): "local" is arrival-at-decode -> frame-flush on one
# worker; "remote" is the origin worker's elapsed stamp plus the
# receiving worker's delivery segment (network transit between the two
# is not measurable without synced clocks — the trace plane joins the
# two segments by id instead)
DELIVERY_PATHS = ("local", "remote")


def _fmt(v) -> str:
    """A Prometheus-compatible number: integral floats render without
    the trailing ``.0`` so counters read as counts."""
    if isinstance(v, float):
        if v == math.inf:
            return "+Inf"
        if v != v:  # NaN
            return "NaN"
        if v == int(v) and abs(v) < 1e15:
            return str(int(v))
        return repr(v)
    return str(v)


def _exemplar_str(exemplars: Optional[list], i: int) -> str:
    """The OpenMetrics-style exemplar suffix for one bucket line —
    ``# {trace_id="..."} <value>`` — or "" when the bucket has none."""
    if exemplars is None or exemplars[i] is None:
        return ""
    v, trace_id = exemplars[i]
    return f' # {{trace_id="{escape_label_value(trace_id)}"}} {_fmt(float(v))}'


def escape_label_value(v: str) -> str:
    """Prometheus label-value escaping: backslash, double-quote, and
    newline must be escaped inside the quoted value."""
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def escape_help(v: str) -> str:
    """# HELP escaping: backslash and newline only (quotes are legal)."""
    return v.replace("\\", "\\\\").replace("\n", "\\n")


class Histogram:
    """A fixed-bucket log-scale histogram.

    Bucket upper bounds are ``base * growth**i`` (defaults: 1us growing
    x2 for 36 buckets, topping out around 34s) plus a +Inf overflow
    bucket — Prometheus ``le`` semantics (a value equal to a boundary
    counts in that bucket). Log-scale keeps relative error bounded at
    every magnitude, which is what latency percentiles need.

    Single-writer per instance (asyncio data plane or one worker
    thread); cross-thread aggregation goes through ``merge`` — each
    thread owns a shard and the scrape merges them. A registry child
    may instead be backed by a scrape-time callback returning a merged
    snapshot (``fn``, see :meth:`live`): the sharded matcher's
    per-shard compile histograms render this way without the workers
    ever sharing a hot write path.
    """

    __slots__ = ("bounds", "counts", "count", "sum", "fn", "exemplars")

    def __init__(
        self,
        base: float = 1e-6,
        growth: float = 2.0,
        n_buckets: int = 36,
        bounds: Optional[tuple] = None,
    ) -> None:
        if bounds is not None:
            self.bounds = tuple(float(b) for b in bounds)
        else:
            self.bounds = tuple(base * growth**i for i in range(n_buckets))
        self.counts = [0] * (len(self.bounds) + 1)  # [-1] is +Inf
        self.count = 0
        self.sum = 0.0
        self.fn: Optional[Callable[[], "Histogram"]] = None
        # per-bucket (value, trace_id) exemplars, last-write-wins; None
        # until enable_exemplars() — the off-trace observe() path pays
        # one is-None check (mqtt_tpu.tracing / OpenMetrics exemplars)
        self.exemplars: Optional[list] = None

    def enable_exemplars(self) -> None:
        """Retain the last sampled (value, trace_id) per bucket; the
        exposition cross-links a p99 bucket to a concrete recorded
        trace. Merge() deliberately ignores exemplars (shard merges are
        scrape-time aggregates; the shards keep their own)."""
        if self.exemplars is None:
            self.exemplars = [None] * (len(self.bounds) + 1)

    def live(self) -> "Histogram":
        """The histogram to render at scrape time: the callback's merged
        snapshot when one is attached, else this instance. A failing
        callback renders the (empty) stored instance — a scrape must
        never take the broker down."""
        if self.fn is None:
            return self
        try:
            merged = self.fn()
        except Exception:
            _log.exception("histogram callback failed")
            return self
        return merged if isinstance(merged, Histogram) else self

    def observe(self, v: float, trace_id: Optional[str] = None) -> None:
        # bisect_left(bounds, v): first bound >= v — exactly `le`
        i = bisect_left(self.bounds, v)
        self.counts[i] += 1
        self.count += 1
        self.sum += v
        if trace_id is not None and self.exemplars is not None:
            self.exemplars[i] = (v, trace_id)

    def percentile(self, q: float) -> float:
        """The q-quantile's bucket upper bound (0.0 when empty; the
        largest finite bound for observations past it). Rank uses the
        ceiling so a single observation answers every quantile with its
        own bucket."""
        if self.count == 0:
            return 0.0
        rank = max(1, math.ceil(q * self.count))
        acc = 0
        for i, c in enumerate(self.counts):
            acc += c
            if acc >= rank:
                return self.bounds[i] if i < len(self.bounds) else self.bounds[-1]
        return self.bounds[-1]  # pragma: no cover - rank <= count

    def merge(self, other: "Histogram") -> None:
        """Fold another shard (identical bucket layout) into this one."""
        if other.bounds != self.bounds:
            raise ValueError("histogram bucket layouts differ; cannot merge")
        for i, c in enumerate(other.counts):
            self.counts[i] += c
        self.count += other.count
        self.sum += other.sum

    def count_le(self, v: float) -> int:
        """Observations in buckets whose upper bound is <= ``v`` — the
        'good event' count for a latency SLO threshold. The threshold is
        snapped DOWN to the largest bucket bound at or below it, so an
        off-bucket threshold errs toward counting borderline
        observations as bad (an SLO gate should alarm early, not late —
        mqtt_tpu.slo)."""
        # bisect_right-style: first bound strictly greater than v
        i = bisect_left(self.bounds, v)
        if i < len(self.bounds) and self.bounds[i] == v:
            i += 1
        return sum(self.counts[:i])

    def summary(self) -> dict:
        return {
            "count": self.count,
            "sum": round(self.sum, 6),
            "p50": self.percentile(0.50),
            "p95": self.percentile(0.95),
            "p99": self.percentile(0.99),
        }


class Counter:
    """A monotonic counter (single-writer; the GIL makes ``+=`` on the
    slot safe enough for telemetry from helper threads). Like Gauge it
    may instead be backed by a scrape-time callback — for mirroring
    counters another layer already maintains (system.Info,
    MatcherStats) without a second bookkeeping path, while still
    exposing honest ``# TYPE counter`` metadata for the ``_total``
    series."""

    __slots__ = ("_value", "fn")

    def __init__(self, fn: Optional[Callable[[], float]] = None) -> None:
        self._value = 0
        self.fn = fn

    def inc(self, n: int = 1) -> None:
        self._value += n

    @property
    def value(self):
        if self.fn is not None:
            try:
                return self.fn()
            except Exception:  # a scrape must not take the broker down
                _log.exception("counter callback failed")
                return 0
        return self._value


class Gauge:
    """A point-in-time value: either ``set()`` by the owner or backed by
    a zero-arg callable sampled at scrape time."""

    __slots__ = ("_value", "fn")

    def __init__(self, fn: Optional[Callable[[], float]] = None) -> None:
        self._value = 0.0
        self.fn = fn

    def set(self, v: float) -> None:
        self._value = v

    def value(self):
        if self.fn is not None:
            try:
                return self.fn()
            except Exception:  # a scrape must not take the broker down
                _log.exception("gauge callback failed")
                return 0.0
        return self._value


_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")


class _Family:
    __slots__ = ("name", "mtype", "help", "children", "maker")

    def __init__(self, name: str, mtype: str, help_: str, maker) -> None:
        self.name = name
        self.mtype = mtype
        self.help = help_
        # Counter | Gauge | Histogram, keyed on the sorted label tuple;
        # Any because the renderers isinstance-dispatch per child
        self.children: dict[tuple, Any] = {}
        self.maker = maker


class MetricsRegistry:
    """Named metric families with labeled children and two renderers:
    Prometheus text exposition and the flat $SYS topic map."""

    def __init__(self) -> None:
        # lock-plane adoption (mqtt_tpu.utils.locked): every scrape
        # walks this lock against concurrent child registration, so it
        # is itself a measured contention point. Lazy import — locked.py
        # imports this module's Histogram.
        from .utils.locked import InstrumentedLock

        self._lock = InstrumentedLock("metrics_registry")
        self._families: dict[str, _Family] = {}
        # render per-bucket trace exemplars in exposition() (OpenMetrics
        # style; set via Telemetry.attach_tracer — Options.trace_exemplars)
        self.emit_exemplars = False

    def _child(self, name: str, mtype: str, help_: str, labels: dict, maker):
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name: {name!r}")
        key = tuple(sorted((str(k), str(v)) for k, v in labels.items()))
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = self._families[name] = _Family(name, mtype, help_, maker)
            elif fam.mtype != mtype:
                raise ValueError(
                    f"metric {name!r} re-registered as {mtype} (was {fam.mtype})"
                )
            child = fam.children.get(key)
            if child is None:
                child = fam.children[key] = maker()
            return child

    def counter(
        self, name: str, help: str = "", fn: Optional[Callable] = None, **labels
    ) -> Counter:
        c = self._child(name, "counter", help, labels, Counter)
        if fn is not None:
            c.fn = fn
        return c

    def gauge(
        self, name: str, help: str = "", fn: Optional[Callable] = None, **labels
    ) -> Gauge:
        g = self._child(name, "gauge", help, labels, Gauge)
        if fn is not None:
            g.fn = fn
        return g

    def histogram(
        self,
        name: str,
        help: str = "",
        bounds: Optional[tuple] = None,
        fn: Optional[Callable] = None,
        **labels,
    ) -> Histogram:
        h = self._child(
            name, "histogram", help, labels, lambda: Histogram(bounds=bounds)
        )
        if fn is not None:
            # scrape-time snapshot callback (per-thread shard merging):
            # the renderers resolve through Histogram.live()
            h.fn = fn
        return h

    # -- rendering ---------------------------------------------------------

    @staticmethod
    def _labels_str(key: tuple, extra: str = "") -> str:
        parts = [f'{k}="{escape_label_value(v)}"' for k, v in key]
        if extra:
            parts.append(extra)
        return "{" + ",".join(parts) + "}" if parts else ""

    def exposition(self) -> str:
        """The Prometheus text exposition format (version 0.0.4) served
        at ``GET /metrics``."""
        with self._lock:
            families = sorted(self._families.items())
        out: list[str] = []
        for name, fam in families:
            if fam.help:
                out.append(f"# HELP {name} {escape_help(fam.help)}")
            out.append(f"# TYPE {name} {fam.mtype}")
            for key, child in sorted(fam.children.items()):
                if isinstance(child, Counter):
                    out.append(f"{name}{self._labels_str(key)} {_fmt(child.value)}")
                elif isinstance(child, Gauge):
                    out.append(
                        f"{name}{self._labels_str(key)} {_fmt(child.value())}"
                    )
                else:  # Histogram (callback-backed ones snapshot here)
                    child = child.live()
                    ex = child.exemplars if self.emit_exemplars else None
                    acc = 0
                    for i, bound in enumerate(child.bounds):
                        acc += child.counts[i]
                        le = self._labels_str(key, f'le="{_fmt(float(bound))}"')
                        out.append(
                            f"{name}_bucket{le} {acc}" + _exemplar_str(ex, i)
                        )
                    le = self._labels_str(key, 'le="+Inf"')
                    out.append(
                        f"{name}_bucket{le} {_fmt(child.count)}"
                        + _exemplar_str(ex, -1)
                    )
                    out.append(
                        f"{name}_sum{self._labels_str(key)} {_fmt(child.sum)}"
                    )
                    out.append(
                        f"{name}_count{self._labels_str(key)} {_fmt(child.count)}"
                    )
        return "\n".join(out) + "\n"

    def sys_tree(self) -> dict:
        """A flat ``topic-suffix -> value`` map for the retained
        ``$SYS/broker/telemetry/#`` tree. ``*_seconds`` histograms
        surface their percentile summary in milliseconds (readability —
        the raw seconds live on /metrics); dimensionless histograms
        (fill ratios) surface the raw quantile values."""
        with self._lock:
            families = sorted(self._families.items())
        out: dict[str, object] = {}
        for name, fam in families:
            short = name.removeprefix("mqtt_tpu_")
            in_seconds = name.endswith("_seconds")
            for key, child in sorted(fam.children.items()):
                suffix = "/".join(v for _, v in key)
                base = f"{short}/{suffix}" if suffix else short
                if isinstance(child, Counter):
                    out[base] = child.value
                elif isinstance(child, Gauge):
                    v = child.value()
                    out[base] = round(v, 6) if isinstance(v, float) else v
                else:
                    s = child.live().summary()
                    out[f"{base}/count"] = s["count"]
                    for q in ("p50", "p95", "p99"):
                        if in_seconds:
                            out[f"{base}/{q}_ms"] = round(s[q] * 1e3, 3)
                        else:
                            out[f"{base}/{q}"] = round(s[q], 6)
        return out

    def family_children(self, name: str) -> list:
        """Snapshot of one family's ``(label-key, child)`` pairs (the
        SLO engine walks the delivery-latency family through this — the
        children themselves are read lock-free, like exposition())."""
        with self._lock:
            fam = self._families.get(name)
            return [] if fam is None else list(fam.children.items())

    def summary(self) -> dict:
        """The wire summary one worker contributes to mesh metric
        federation (ISSUE 14, cluster ``_T_METRICS`` frames): every
        family's type plus per-child values — counters/gauges as
        numbers, histograms as ``{n, s, c}`` (count, sum, bucket-count
        vector with trailing zeros trimmed) beside the family's shared
        ``le`` bounds. Values are ABSOLUTE cumulative snapshots, not
        deltas: the receiver keys them by (worker, boot, seq), so a
        re-delivered or reordered frame can never double-count and a
        restarted worker's reset counters simply replace its entry."""
        with self._lock:
            families = sorted(self._families.items())
        fams: dict[str, dict] = {}
        for name, fam in families:
            children: list = []
            bounds: Optional[list] = None
            for key, child in sorted(fam.children.items()):
                labels = [[k, v] for k, v in key]
                if isinstance(child, Counter):
                    children.append([labels, child.value])
                elif isinstance(child, Gauge):
                    children.append([labels, child.value()])
                else:
                    h = child.live()
                    if bounds is None:
                        bounds = list(h.bounds)
                    elif list(h.bounds) != bounds:
                        continue  # a mixed-layout child cannot fold
                    counts = list(h.counts)
                    while counts and counts[-1] == 0:
                        counts.pop()
                    children.append(
                        [labels, {"n": h.count, "s": round(h.sum, 9), "c": counts}]
                    )
            entry: dict = {"t": fam.mtype, "c": children}
            if fam.mtype == "histogram" and bounds is not None:
                entry["le"] = bounds
            fams[name] = entry
        return fams


class StageClock:
    """One sampled publish's trip through the pipeline: ``stamp(stage)``
    records the time since the previous stamp as that stage's duration.
    Cheap by construction — two perf_counter calls and a list append per
    stage, and only 1-in-N publishes carry one at all."""

    __slots__ = ("t0", "last", "stages", "batch")

    def __init__(self) -> None:
        self.t0 = self.last = time.perf_counter()
        self.stages: list[tuple[str, float]] = []
        # the number of the device batch that carried this publish
        # (tracing.BatchProfile.seq), stamped by the staging loop
        self.batch: Optional[int] = None

    def stamp(self, stage: str) -> None:
        now = time.perf_counter()
        self.stages.append((stage, now - self.last))
        self.last = now

    def stamp_until(self, stage: str, t: float) -> None:
        """Stamp a stage ending at an EXPLICIT perf_counter time (the
        staging drain loop splits device_batch into h2d/dispatch/d2h
        using boundaries measured on the resolver's thread). Clamped so
        a boundary that raced behind the previous stamp records a
        zero-length stage instead of corrupting the running total."""
        if t < self.last:
            t = self.last
        self.stages.append((stage, t - self.last))
        self.last = t

    def total(self) -> float:
        return self.last - self.t0


class RemoteStageClock(StageClock):
    """The receiving-side stage clock of a mesh-forwarded publish
    (ISSUE 14): carries the origin worker's elapsed-at-forward stamp
    (``el`` on the frame head) so the remote-path delivery SLI reads
    origin-segment + local-segment, and the origin's trace id (when the
    forward was traced) so the sample's histogram exemplar joins the
    cross-worker trace. Never routed through observe_publish — remote
    deliveries must not skew the local pipeline-stage histograms or the
    flight ring; only the delivery-latency family sees them."""

    __slots__ = ("remote_base", "trace_id")

    def __init__(
        self, remote_base: float = 0.0, trace_id: Optional[str] = None
    ) -> None:
        super().__init__()
        self.remote_base = remote_base
        self.trace_id = trace_id


class FlightRecorder:
    """A bounded ring of recent stage-clock records, JSON-dumped to disk
    when a degradation trigger fires (overload SHED, breaker trip).
    Dumps are rate-limited so a flapping posture cannot fill the disk."""

    def __init__(
        self,
        size: int = 256,
        dump_dir: str = "",
        min_interval_s: float = 30.0,
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        self.ring: deque = deque(maxlen=max(1, size))
        # "" = a private mkdtemp created lazily at the first dump: a FIXED
        # path in the shared tempdir would let any local user pre-create
        # the directory (symlink-clobber the predictable filenames) and
        # read the dumped topic names; mkdtemp is 0700 and unpredictable,
        # and the dump log line carries the chosen path
        self.dump_dir = dump_dir
        self.min_interval_s = min_interval_s
        self.clock = clock
        self.dumps = 0
        self.dumps_suppressed = 0
        self._last_dump = float("-inf")
        # lock-plane adoption: the event loop appends to the ring under
        # this lock on every sampled publish while dump threads snapshot
        from .utils.locked import InstrumentedLock

        self._lock = InstrumentedLock("flight_ring")
        self._writers: list[threading.Thread] = []

    def add(self, record: dict) -> None:
        # under the lock: a cross-thread dump() iterating the ring while
        # the event loop appends would raise "deque mutated during
        # iteration" and silently lose the trigger's trace. The critical
        # section is one append — dump()'s file IO runs OUTSIDE the lock
        with self._lock:
            self.ring.append(record)

    def dump_async(
        self,
        reason: str,
        extra: Optional[dict] = None,
        after: Optional[Callable[[str, str], None]] = None,
    ) -> None:
        """Fire-and-forget dump on a daemon thread: degradation triggers
        run under the breaker lock / on the event loop's hot path, where
        synchronous disk IO would stall the data plane at exactly peak
        load. Rate-limiting still applies inside dump(); ``after`` runs
        on the writer thread with (path, reason) only when a dump was
        actually written (the trace-plane sibling dump rides it)."""

        def _write() -> None:
            path = self.dump(reason, extra)
            if path is not None and after is not None:
                try:
                    after(path, reason)
                except Exception:
                    _log.exception("post-dump hook failed (reason=%s)", reason)

        t = threading.Thread(
            target=_write,
            daemon=True,
            name="mqtt-tpu-flight-dump",
        )
        with self._lock:
            # track EVERY live writer, not just the newest: a rate-limited
            # no-op thread must not mask an earlier write still on disk
            self._writers = [w for w in self._writers if w.is_alive()]
            self._writers.append(t)
        t.start()

    def join_writer(self, timeout: float = 5.0) -> None:
        """Wait for all in-flight async dumps (tests, orderly shutdown)."""
        with self._lock:
            writers = list(self._writers)
        for t in writers:
            t.join(timeout)

    def dump(self, reason: str, extra: Optional[dict] = None) -> Optional[str]:
        """Write the ring (plus trigger context) to one JSON file;
        returns the path, or None when rate-limited or the write failed.
        Thread-safe: triggers fire from the event loop, the breaker's
        probe thread, and sweep paths."""
        with self._lock:
            now = self.clock()
            if now - self._last_dump < self.min_interval_s:
                self.dumps_suppressed += 1
                return None
            self._last_dump = now
            records = list(self.ring)
        if not self.dump_dir:
            # first dump: a private 0700 dir (see __init__'s note). The
            # mkdtemp disk I/O runs OUTSIDE the lock (brokerlint R1 — the
            # event loop appends to the ring under it); two racing first
            # dumps each get a dir and the double-checked store below picks
            # one winner (the loser's empty tmpdir is harmless)
            ddir = tempfile.mkdtemp(prefix="mqtt_tpu_flight_")
            with self._lock:
                if not self.dump_dir:
                    self.dump_dir = ddir
        snapshot = {
            "reason": reason,
            "time_unix": int(time.time()),  # brokerlint: ok=R3 dump timestamps are wall-clock by design (operator-correlatable)
            "records": records,
            "context": extra or {},
            # the trace cross-link: every trace id active in the ring at
            # trigger time, deduped (records keep their own trace_id too)
            "trace_ids": sorted(
                {
                    r["trace_id"]
                    for r in records
                    if isinstance(r, dict) and "trace_id" in r
                }
            ),
        }
        try:
            os.makedirs(self.dump_dir, exist_ok=True)
            safe = re.sub(r"[^a-zA-Z0-9_.-]", "_", reason)
            path = os.path.join(
                self.dump_dir,
                # brokerlint: ok=R3 dump filenames carry the wall-clock stamp
                f"flight_{int(time.time())}_{safe}.json",
            )
            with open(path, "w") as f:
                json.dump(snapshot, f, indent=1)
        except OSError:
            _log.exception("flight-recorder dump failed (dir=%s)", self.dump_dir)
            return None
        self.dumps += 1
        _log.warning(
            "flight recorder dumped %d records to %s (reason=%s)",
            len(records),
            path,
            reason,
        )
        return path


# batch fill ratio buckets: linear deciles (a ratio is not log-shaped)
FILL_BOUNDS = tuple(round(0.1 * i, 1) for i in range(1, 11))


class Telemetry:
    """The broker's telemetry facade: owns the registry, the per-stage
    publish histograms, the flight recorder, and the sampling counters.
    Every instrumented layer (server, staging, clients, matcher,
    cluster) talks to this object; every exposition surface (/metrics,
    $SYS) renders from it."""

    def __init__(
        self,
        sample: int = 64,
        ring: int = 256,
        dump_dir: str = "",
        dump_min_interval_s: float = 30.0,
    ) -> None:
        self.registry = MetricsRegistry()
        self.sample = max(0, int(sample))  # 0 disables stage sampling
        self._n = 0  # publish counter for 1-in-N sampling
        self._out_n = 0  # outbound-enqueue counter (same 1-in-N rate)
        # the trace plane (mqtt_tpu.tracing.Tracer) or None; attached by
        # the server via attach_tracer() — publish_clock consults it so
        # 1-in-trace_sample publishes carry a full trace context
        self.tracer: Any = None
        # the host profiler (mqtt_tpu.profiling.SamplingProfiler) or
        # None; attached by the server via attach_profiler() — serves
        # GET /profile and rides trigger dumps
        self.host_profiler: Any = None
        # the lock-contention plane (mqtt_tpu.utils.locked.LockPlane)
        # or None; attached via attach_lock_plane()
        self.lock_plane: Any = None
        # the per-device observability plane (ops/devicestats.
        # DeviceStatsPlane) or None; attached via attach_device_stats()
        # — serves GET /devices, $SYS/broker/devices/#, and grows
        # trigger dumps a ``devices_*.json`` sibling
        self.device_stats: Any = None
        # cluster-wide SLO observatory (ISSUE 14): the delivery-latency
        # SLI gate (one bool test on the sampled path; Options.slo), the
        # SLO burn-rate engine (mqtt_tpu.slo.SLOEngine) and the mesh
        # metric-federation store (ClusterMetrics, attached by the
        # cluster so /metrics/cluster and /cluster/slo can render)
        self.delivery_sli = True
        self._delivery_cache: dict[tuple, Histogram] = {}
        self.slo: Any = None
        self.cluster_metrics: Any = None
        # this worker's id as a federation label (the cluster stamps it
        # when it attaches; single-worker brokers render as "0")
        self.local_worker = "0"
        self.recorder = FlightRecorder(
            size=ring, dump_dir=dump_dir, min_interval_s=dump_min_interval_s
        )
        r = self.registry
        self.stage_hist = {
            s: r.histogram(
                "mqtt_tpu_publish_stage_seconds",
                "Sampled per-publish latency by pipeline stage",
                stage=s,
            )
            for s in PUBLISH_STAGES
        }
        self.sampled_publishes = r.counter(
            "mqtt_tpu_publish_sampled_total",
            "Publishes that carried a stage clock (1-in-N sampling)",
        )
        self.batch_service = r.histogram(
            "mqtt_tpu_stage_batch_service_seconds",
            "Device match-batch resolve wall time (every batch)",
        )
        self.batch_fill = r.histogram(
            "mqtt_tpu_stage_batch_fill_ratio",
            "Match-batch occupancy against the adaptive batch cap",
            bounds=FILL_BOUNDS,
        )
        self.outbound_wait = r.histogram(
            "mqtt_tpu_outbound_queue_wait_seconds",
            "Sampled wait of an outbound publish in a client queue",
        )
        # per-leg pipeline handoff waits (ROADMAP item 1's 3-deep
        # overlapped staging): how long a formed batch waited before the
        # h2d issue thread picked it up, and how long an issued batch
        # waited before the d2h drain thread started its sync — both sit
        # near zero when the pipeline is actually full
        self.leg_wait = {
            leg: r.histogram(
                "mqtt_tpu_staging_leg_wait_seconds",
                "Per-batch handoff wait before a staging pipeline leg "
                "started",
                leg=leg,
            )
            for leg in ("h2d", "d2h")
        }
        self.fallback = {
            k: r.counter(
                "mqtt_tpu_stage_fallback_total",
                "Publishes resolved by the host walk instead of the "
                "device batch, by cause",
                **{"class": k},
            )
            for k in ("admission", "issue_error", "resolve_error", "stop")
        }
        self.rebuild_hist = r.histogram(
            "mqtt_tpu_matcher_rebuild_seconds",
            "Device index compile/rebuild/fold wall time",
        )
        r.counter(
            "mqtt_tpu_flight_dumps_total",
            "Flight-recorder dumps written",
            fn=lambda: self.recorder.dumps,
        )
        # write-path / fan-out amplification accounting (ROADMAP item 3:
        # the per-subscriber re-encode waste the encode-once rewrite will
        # eliminate — encodes / inbound publishes is its success metric)
        self.publish_encodes = r.counter(
            "mqtt_tpu_publish_encodes_total",
            "Outbound PUBLISH packet encodes (clients.write_packet + "
            "the fan-out frame cache's per-variant encodes)",
        )
        self.fanout_deliveries = r.counter(
            "mqtt_tpu_fanout_deliveries_total",
            "Outbound PUBLISH deliveries written (shared-frame and "
            "per-subscriber legs)",
        )
        self.outbound_bytes = r.counter(
            "mqtt_tpu_outbound_bytes_total",
            "Bytes written to client transports by the outbound write "
            "paths",
        )
        self.outbound_writes = r.counter(
            "mqtt_tpu_outbound_writes_total",
            "Socket write calls issued by the outbound write paths",
        )
        # zero-materialization fan-out accounting (ISSUE 13): variants
        # are the encode-once unit (amplification ~1 per variant is the
        # success metric), writev batches count the GIL-released flush
        # calls. View materializations are exported separately as a
        # callback counter over the C module's own stats (server wiring).
        self.fanout_variants = r.counter(
            "mqtt_tpu_fanout_variants_total",
            "Distinct (version, QoS, retain) encode variants the batched "
            "fan-out produced — one wire encode each",
        )
        self.fanout_writev_batches = r.counter(
            "mqtt_tpu_fanout_writev_batches_total",
            "GIL-released batched socket flush calls issued by the "
            "fan-out write path",
        )

    # -- delivery-latency SLIs (ISSUE 14) ----------------------------------

    def delivery_hist(self, tenant: str, qos: int, path: str) -> Histogram:
        """The labeled delivery-latency child for one (tenant, qos,
        path) cell, cached so the sampled path pays one dict probe
        instead of the registry lock."""
        key = (tenant, qos, path)
        h = self._delivery_cache.get(key)
        if h is None:
            h = self.registry.histogram(
                "mqtt_tpu_delivery_latency_seconds",
                "Publish arrival (decode) to frame flushed toward the "
                "subscriber socket, by tenant, publish QoS and delivery "
                "path (sampled 1-in-N; path=remote adds the origin "
                "worker's elapsed stamp to the receiving segment)",
                tenant=tenant,
                qos=str(qos),
                path=path,
            )
            if self.registry.emit_exemplars:
                h.enable_exemplars()
            self._delivery_cache[key] = h
        return h

    def observe_delivery(
        self,
        seconds: float,
        tenant: str,
        qos: int,
        path: str,
        trace_id: Optional[str] = None,
    ) -> None:
        """Record one sampled publish's arrival->flush delivery latency
        — the headline SLI the SLO engine burns against (mqtt_tpu.slo).
        Disabled (one bool test) when Options.slo is off."""
        if not self.delivery_sli:
            return
        self.delivery_hist(tenant, qos, path).observe(seconds, trace_id)

    def delivery_summary(self) -> dict:
        """Per-path delivery-latency fold across every (tenant, qos)
        cell of the SLI family (rows ``delivery_local`` /
        ``delivery_remote``)."""
        out: dict = {}
        for path in DELIVERY_PATHS:
            merged: Optional[Histogram] = None
            for (_t, _q, p), h in list(self._delivery_cache.items()):
                if p != path or not h.count:
                    continue
                if merged is None:
                    merged = Histogram(bounds=h.bounds)
                merged.merge(h)
            if merged is not None and merged.count:
                out[f"delivery_{path}"] = {
                    "count": merged.count,
                    "p50_ms": round(merged.percentile(0.5) * 1e3, 3),
                    "p99_ms": round(merged.percentile(0.99) * 1e3, 3),
                }
        return out

    def attach_slo(self, engine: Any) -> None:
        """Attach the SLO burn-rate engine (mqtt_tpu.slo.SLOEngine):
        GET /cluster/slo serves its state beside the federated view."""
        self.slo = engine

    def attach_cluster_metrics(self, cm: Any) -> None:
        """Attach the mesh metric-federation store (ClusterMetrics,
        fed by cluster ``_T_METRICS`` frames): GET /metrics/cluster
        renders the per-worker + cluster-folded exposition from it."""
        self.cluster_metrics = cm

    # -- publish stage sampling --------------------------------------------

    def attach_tracer(self, tracer: Any, exemplars: bool = True) -> None:
        """Attach the trace plane (mqtt_tpu.tracing.Tracer): sampled
        publish clocks become trace contexts, finished clocks emit span
        trees, and (when ``exemplars``) the stage histograms retain
        per-bucket trace exemplars rendered on /metrics."""
        self.tracer = tracer
        if exemplars:
            for h in self.stage_hist.values():
                h.enable_exemplars()
            self.registry.emit_exemplars = True

    def attach_profiler(self, profiler: Any) -> None:
        """Attach the host sampling profiler
        (mqtt_tpu.profiling.SamplingProfiler): GET /profile serves its
        exports and trigger dumps grow a ``profile_*.txt`` sibling."""
        self.host_profiler = profiler

    def attach_device_stats(self, plane: Any) -> None:
        """Attach the per-device observability plane
        (mqtt_tpu.ops.devicestats.DeviceStatsPlane): GET /devices and
        the $SYS devices tree serve its snapshot, and trigger dumps
        write a ``devices_*.json`` sibling beside flight/traces/
        profile."""
        self.device_stats = plane

    def attach_lock_plane(self, plane: Any) -> None:
        """Attach the lock-contention plane
        (mqtt_tpu.utils.locked.LockPlane): every canonical lock name
        exports wait/hold histograms, acquisition/contention counters,
        and the wait-share gauge set (the top-K contended-locks view is
        this family sorted by share)."""
        self.lock_plane = plane
        # local import: utils.locked imports telemetry.Histogram, so the
        # reverse edge must resolve lazily
        from .utils.locked import LOCK_NAMES

        r = self.registry
        for name in LOCK_NAMES:
            st = plane.stats(name)
            r.histogram(
                "mqtt_tpu_lock_wait_seconds",
                "Time acquirers spent blocked on a named broker lock",
                lock=name,
                fn=lambda s=st: s.wait_hist,
            )
            r.histogram(
                "mqtt_tpu_lock_hold_seconds",
                "Time holders kept a named broker lock",
                lock=name,
                fn=lambda s=st: s.hold_hist,
            )
            r.counter(
                "mqtt_tpu_lock_acquisitions_total",
                "Acquisitions of a named broker lock",
                lock=name,
                fn=lambda s=st: s.acquisitions,
            )
            r.counter(
                "mqtt_tpu_lock_contended_total",
                "Acquisitions that actually blocked on a named broker lock",
                lock=name,
                fn=lambda s=st: s.contended,
            )
            r.gauge(
                "mqtt_tpu_lock_wait_share_ratio",
                "This lock's share of all measured lock wait time "
                "(sort descending for the top-K contended locks)",
                lock=name,
                fn=lambda n=name: plane.wait_share(n),
            )

    def publish_clock(self) -> Optional[StageClock]:
        """A StageClock for 1-in-N publishes, None for the rest; when
        the trace plane is attached, 1-in-trace_sample publishes get a
        PublishTrace (a StageClock that also carries a trace id). The
        unsampled path is one increment and two modulos."""
        self._n += 1
        tracer = self.tracer
        if (
            tracer is not None
            and tracer.sample
            and self._n % tracer.sample == 0
        ):
            return tracer.publish_trace()
        if self.sample == 0 or self._n % self.sample:
            return None
        return StageClock()

    def quiet_draws(self, limit: int) -> int:
        """How many of the next ``limit`` :meth:`publish_clock` draws are
        sure to return None: a caller that takes publishes in by the run
        (server.ingest_run) makes only the draw after them for real and
        adds the quiet ones at once (:meth:`skip_draws`)."""
        n = self._n
        tracer = self.tracer
        if tracer is not None and tracer.sample:
            limit = min(limit, tracer.sample - 1 - n % tracer.sample)
        if self.sample:
            limit = min(limit, self.sample - 1 - n % self.sample)
        return limit

    def skip_draws(self, n: int) -> None:
        """``n`` draws that :meth:`quiet_draws` said return None."""
        self._n += n

    def adopt_trace(self, pk: Any) -> Optional[StageClock]:
        """Adopt a client-supplied trace id: an inbound v5 PUBLISH whose
        user properties carry ``trace-id`` gets a trace context with
        THAT id (TD-MQTT-style transparent tracing — the client picks
        the id, the broker's spans join it), keeping any stamps the read
        loop already recorded. Returns the packet's (possibly new)
        clock; cost off the adopted path is the caller's empty-list
        check."""
        tracer = self.tracer
        clock = getattr(pk, "_tclock", None)
        if tracer is None or getattr(clock, "trace_id", None) is not None:
            return clock
        tid = ""
        for u in pk.properties.user:
            if u.key == TRACE_USER_PROPERTY and u.val:
                tid = u.val
                break
        if not tid or not tracer.allow_adopt():
            # adoption is rate-bounded (Tracer.allow_adopt): a client
            # stamping every publish cannot bypass trace_sample or
            # flood the ring; over-budget publishes flow untraced
            return clock
        trace = tracer.publish_trace(tid)
        if clock is not None:  # graft the read loop's decode stamp over
            trace.t0 = clock.t0
            trace.last = clock.last
            trace.stages = clock.stages
        pk._tclock = trace
        return trace

    def observe_publish(self, clock: StageClock, topic: str = "", qos: int = 0) -> None:
        """Fold one finished stage clock into the per-stage histograms
        and the flight-recorder ring; a traced clock additionally emits
        its span tree into the trace ring and stamps bucket exemplars."""
        trace_id = getattr(clock, "trace_id", None)
        hist = self.stage_hist
        sub_total = 0.0
        have_sub = False
        explicit_batch = False
        fan_total = 0.0
        have_fan = False
        explicit_fanout = False
        for stage, dt in clock.stages:
            h = hist.get(stage)
            if h is not None:
                h.observe(dt, trace_id)
            if stage in DEVICE_SUBSTAGES:
                sub_total += dt
                have_sub = True
            elif stage == "device_batch":
                explicit_batch = True
            elif stage in FANOUT_SUBSTAGES:
                fan_total += dt
                have_fan = True
            elif stage == "fanout":
                explicit_fanout = True
        if have_sub and not explicit_batch:
            # continuity across the sub-stage split: device_batch stays
            # populated as the sum (an explicitly-stamped device_batch —
            # the exact-map / host fallback path — must not be observed
            # twice)
            hist["device_batch"].observe(sub_total, trace_id)
        if have_fan and not explicit_fanout:
            # same continuity contract for the fan-out split: the batched
            # write path stamps encode/flush, legacy paths stamp fanout —
            # either way the coarse stage stays populated
            hist["fanout"].observe(fan_total, trace_id)
        self.sampled_publishes.inc()
        record = {
            # brokerlint: ok=R3 flight records carry wall-clock stamps
            "t": round(time.time(), 3),
            "topic": topic,
            "qos": qos,
            "total_ms": round(clock.total() * 1e3, 3),
            "stages_ms": {
                s: round(dt * 1e3, 4) for s, dt in clock.stages
            },
        }
        if trace_id is not None:
            # the flight-dump <-> trace cross-link: a SHED dump's records
            # name the concrete traces active at trigger time
            record["trace_id"] = trace_id
        self.recorder.add(record)
        tracer = self.tracer
        if trace_id is not None and tracer is not None:
            tracer.finish_publish(clock, topic, qos)

    def sample_outbound(self) -> bool:
        """1-in-N gate for outbound queue-wait stamps (same rate as the
        stage clock)."""
        if self.sample == 0:
            return False
        self._out_n += 1
        return self._out_n % self.sample == 0

    # -- batch-level observations (staging loop) ---------------------------

    def observe_batch(self, service_s: float, n: int, cap: int) -> None:
        self.batch_service.observe(service_s)
        if cap > 0:
            self.batch_fill.observe(min(1.0, n / cap))

    def observe_leg_wait(self, leg: str, dt: float) -> None:
        """One pipeline-leg handoff wait (called from the staging loop's
        h2d/resolve dispatch threads)."""
        h = self.leg_wait.get(leg)
        if h is not None:
            h.observe(dt)

    def note_fallback(self, klass: str, n: int = 1) -> None:
        c = self.fallback.get(klass)
        if c is not None:
            c.inc(n)

    # -- degradation triggers ----------------------------------------------

    def trigger_dump(self, reason: str, extra: Optional[dict] = None) -> None:
        """Dump the flight recorder WITHOUT blocking the caller: triggers
        fire under the breaker lock and on the governor's evaluate path
        (both on the data plane), so the file IO moves to a daemon
        thread. When the trace plane is attached, the same thread also
        writes a sibling ``traces_*.json`` (Perfetto-loadable) next to
        the flight dump — the dump's trace_ids point into it — and when
        the host profiler is attached, a ``profile_*.txt`` collapsed
        snapshot of where every broker thread was spending wall time
        as the trigger fired. Use ``recorder.dump`` directly for a
        synchronous dump."""
        after = (
            self._dump_siblings
            if self.tracer is not None
            or self.host_profiler is not None
            or self.device_stats is not None
            else None
        )
        self.recorder.dump_async(reason, extra, after=after)

    def _dump_siblings(self, dump_path: str, reason: str) -> None:
        """Write the trace ring, the profiler's collapsed stacks, and
        the device-plane snapshot beside a just-written flight dump
        (recorder writer thread)."""
        if self.tracer is not None:
            self._dump_traces(dump_path, reason)
        if self.host_profiler is not None:
            self._dump_profile(dump_path, reason)
        if self.device_stats is not None:
            self._dump_devices(dump_path, reason)

    def _dump_devices(self, dump_path: str, reason: str) -> None:
        base = os.path.basename(dump_path)
        stem = base[len("flight_"):] if base.startswith("flight_") else base
        name = "devices_" + os.path.splitext(stem)[0] + ".json"
        path = os.path.join(os.path.dirname(dump_path), name)
        try:
            with open(path, "w") as f:
                json.dump(self.device_stats.snapshot(), f, indent=1)
        except OSError:
            _log.exception("device-plane dump failed (path=%s)", path)
            return
        _log.warning("device snapshot dumped to %s (reason=%s)", path, reason)

    def _dump_profile(self, dump_path: str, reason: str) -> None:
        base = os.path.basename(dump_path)
        stem = base[len("flight_"):] if base.startswith("flight_") else base
        name = "profile_" + os.path.splitext(stem)[0] + ".txt"
        path = os.path.join(os.path.dirname(dump_path), name)
        try:
            with open(path, "w") as f:
                f.write(self.host_profiler.collapsed())
        except OSError:
            _log.exception("profile dump failed (path=%s)", path)
            return
        _log.warning("profiler stacks dumped to %s (reason=%s)", path, reason)

    def _dump_traces(self, dump_path: str, reason: str) -> None:
        """Write the trace ring beside a just-written flight dump (runs
        on the recorder's daemon writer thread, never on a data-plane
        path)."""
        base = os.path.basename(dump_path)
        name = "traces_" + (
            base[len("flight_"):] if base.startswith("flight_") else base
        )
        path = os.path.join(os.path.dirname(dump_path), name)
        try:
            with open(path, "w") as f:
                f.write(self.tracer.export_json())
        except OSError:
            _log.exception("trace dump failed (path=%s)", path)
            return
        _log.warning("trace ring dumped to %s (reason=%s)", path, reason)

    # -- rendering ---------------------------------------------------------

    def exposition(self) -> str:
        return self.registry.exposition()

    def sys_tree(self) -> dict:
        out = self.registry.sys_tree()
        out["flight/ring_depth"] = len(self.recorder.ring)
        out["flight/dumps"] = self.recorder.dumps
        out["flight/dumps_suppressed"] = self.recorder.dumps_suppressed
        return out


class ClusterMetrics:
    """Mesh-federated metric summaries (ISSUE 14): the per-worker
    registry snapshots that ride cluster ``_T_METRICS`` frames, stored
    latest-wins per (worker, boot incarnation, sequence) and rendered
    as ONE Prometheus exposition at ``GET /metrics/cluster`` — every
    sample with a ``worker`` label, plus pre-folded cluster totals
    (counters summed, histogram bucket vectors added) with no worker
    label, so the 32-worker drill is scrapable from the root alone.

    Idempotence: entries carry absolute cumulative values keyed by
    (boot, seq) — a re-delivered or reordered frame is a no-op, and a
    restarted worker's fresh boot nonce replaces its dead incarnation.
    Entries older than ``max_age_s`` age out of scrapes (a dead worker
    must not pin stale totals forever).

    Loop-affine by design: ingest runs on the cluster's event loop and
    the HTTP scrape handlers run on the same loop, so no lock is needed
    (the multi-process drill gives each worker its own store)."""

    def __init__(
        self,
        max_age_s: float = 120.0,
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        self.max_age_s = max_age_s
        self.clock = clock
        # worker id -> {"b": boot, "q": seq, "f": fams, "at": monotonic}
        self._workers: dict[str, dict] = {}
        self.frames_ingested = 0  # accepted summary entries
        self.frames_stale = 0  # re-delivered/reordered entries dropped

    def ingest(
        self,
        worker: str,
        boot: int,
        seq: int,
        fams: dict,
        now: Optional[float] = None,
    ) -> bool:
        """Store one worker's summary; False = already have this (or a
        newer) snapshot from the same incarnation — the re-delivery
        no-op that keeps counter folding idempotent."""
        now = self.clock() if now is None else now
        cur = self._workers.get(worker)
        if cur is not None and cur["b"] == boot and seq <= cur["q"]:
            self.frames_stale += 1
            return False
        self._workers[worker] = {"b": boot, "q": seq, "f": fams, "at": now}
        self.frames_ingested += 1
        return True

    def entries(self, now: Optional[float] = None) -> dict[str, dict]:
        """Fresh per-worker entries (aged ones pruned in place) — also
        what an intermediate tree hop forwards up toward the root (the
        per-subtree fold: its own summary plus everything learned on
        child edges)."""
        now = self.clock() if now is None else now
        for wid in [
            w
            for w, e in self._workers.items()
            if now - e["at"] > self.max_age_s
        ]:
            del self._workers[wid]
        return dict(self._workers)

    @property
    def worker_count(self) -> int:
        # through entries() so aged-out workers prune here too: the
        # mqtt_tpu_cluster_metrics_workers gauge is often the ONLY
        # reader on a worker nobody scrapes (the root never sends
        # uphill), and a dead worker must drop out of it on time
        return len(self.entries())

    # -- rendering ---------------------------------------------------------

    @staticmethod
    def _label_str(pairs: list, extra: str = "") -> str:
        # one label-rendering rule for both expositions: wire labels
        # (json round-tripped) coerce to str, then the registry's own
        # formatter applies the escaping
        return MetricsRegistry._labels_str(
            tuple((str(k), str(v)) for k, v in pairs), extra
        )

    def _sources(
        self, local_registry: Optional["MetricsRegistry"], local_worker: str
    ) -> dict[str, dict]:
        """worker id -> family summary, the local registry's LIVE
        summary shadowing any stale federated copy of this worker."""
        sources: dict[str, dict] = {}
        for wid, ent in sorted(self.entries().items()):
            sources[str(wid)] = ent["f"]
        if local_registry is not None:
            sources[str(local_worker)] = local_registry.summary()
        return sources

    def exposition(
        self,
        local_registry: Optional["MetricsRegistry"] = None,
        local_worker: str = "0",
    ) -> str:
        """The federated Prometheus text exposition: per-worker samples
        labeled ``worker="<id>"`` plus cluster-folded totals (counters
        and histograms only — point-in-time gauges do not fold
        meaningfully) carrying no worker label in the same family."""
        sources = self._sources(local_registry, local_worker)
        # family name -> {"t": type, "le": bounds, "rows": [...]}
        fams: dict[str, dict] = {}
        for wid, summary in sources.items():
            if not isinstance(summary, dict):
                continue
            for name, ent in summary.items():
                if not isinstance(ent, dict) or not _NAME_RE.match(name):
                    continue
                fam = fams.setdefault(
                    name, {"t": ent.get("t"), "le": ent.get("le"), "rows": []}
                )
                if fam["t"] != ent.get("t"):
                    continue  # cross-worker type conflict: first type wins
                if (
                    ent.get("t") == "histogram"
                    and ent.get("le") != fam["le"]
                ):
                    # cross-worker bucket-layout skew (a mid-upgrade
                    # mesh): index-wise adding counts against mismatched
                    # bounds would render silently-wrong folds — skip
                    # this worker's children for the family instead
                    # (the same posture summary() takes within a worker)
                    continue
                for child in ent.get("c") or []:
                    if not isinstance(child, (list, tuple)) or len(child) != 2:
                        continue
                    labels, value = child
                    fam["rows"].append((wid, list(labels), value))
        out: list[str] = []
        for name in sorted(fams):
            fam = fams[name]
            mtype = fam["t"]
            if mtype not in ("counter", "gauge", "histogram"):
                continue
            out.append(f"# TYPE {name} {mtype}")
            folds: dict[tuple, Any] = {}
            for wid, labels, value in sorted(
                fam["rows"], key=lambda r: (r[1], r[0])
            ):
                wl = labels + [["worker", wid]]
                if mtype == "histogram":
                    if not isinstance(value, dict):
                        continue
                    self._render_hist(out, name, wl, fam["le"], value)
                    key = tuple((str(k), str(v)) for k, v in labels)
                    agg = folds.get(key)
                    if agg is None:
                        folds[key] = {
                            "n": int(value.get("n", 0)),
                            "s": float(value.get("s", 0.0)),
                            "c": list(value.get("c") or []),
                        }
                    else:
                        agg["n"] += int(value.get("n", 0))
                        agg["s"] += float(value.get("s", 0.0))
                        counts = list(value.get("c") or [])
                        if len(counts) > len(agg["c"]):
                            agg["c"].extend(
                                [0] * (len(counts) - len(agg["c"]))
                            )
                        for i, c in enumerate(counts):
                            agg["c"][i] += c
                elif isinstance(value, (int, float)):
                    out.append(
                        f"{name}{self._label_str(wl)} {_fmt(value)}"
                    )
                    if mtype == "counter":
                        key = tuple((str(k), str(v)) for k, v in labels)
                        folds[key] = folds.get(key, 0) + value
            # pre-folded cluster totals (no worker label, same family)
            for key in sorted(folds):
                pairs = [list(kv) for kv in key]
                agg = folds[key]
                if mtype == "histogram":
                    self._render_hist(out, name, pairs, fam["le"], agg)
                else:
                    out.append(
                        f"{name}{self._label_str(pairs)} {_fmt(folds[key])}"
                    )
        return "\n".join(out) + "\n"

    def _render_hist(
        self, out: list, name: str, pairs: list, bounds: Any, value: dict
    ) -> None:
        if not isinstance(bounds, list):
            return
        counts = list(value.get("c") or [])
        counts.extend([0] * (len(bounds) + 1 - len(counts)))
        acc = 0
        for i, bound in enumerate(bounds):
            acc += counts[i]
            le = self._label_str(pairs, f'le="{_fmt(float(bound))}"')
            out.append(f"{name}_bucket{le} {acc}")
        le = self._label_str(pairs, 'le="+Inf"')
        out.append(f"{name}_bucket{le} {_fmt(int(value.get('n', 0)))}")
        out.append(
            f"{name}_sum{self._label_str(pairs)} "
            f"{_fmt(float(value.get('s', 0.0)))}"
        )
        out.append(
            f"{name}_count{self._label_str(pairs)} "
            f"{_fmt(int(value.get('n', 0)))}"
        )

    def slo_state(
        self,
        local_registry: Optional["MetricsRegistry"] = None,
        local_worker: str = "0",
    ) -> dict:
        """Mesh-wide SLO objective state for ``GET /cluster/slo``: every
        worker's ``mqtt_tpu_slo_*`` gauge values keyed by worker id —
        the federated face of each worker's own SLOEngine gauges."""
        out: dict = {}
        for wid, summary in self._sources(local_registry, local_worker).items():
            rows: dict = {}
            if isinstance(summary, dict):
                for name, ent in summary.items():
                    if not name.startswith("mqtt_tpu_slo_"):
                        continue
                    for child in (ent or {}).get("c") or []:
                        if (
                            not isinstance(child, (list, tuple))
                            or len(child) != 2
                            or not isinstance(child[1], (int, float))
                        ):
                            continue
                        labels, value = child
                        suffix = ",".join(
                            f"{k}={v}" for k, v in sorted(map(tuple, labels))
                        )
                        rows[f"{name}{{{suffix}}}" if suffix else name] = value
            if rows:
                out[wid] = rows
        return out


def check_exposition(text: str) -> int:
    """A minimal pure-Python Prometheus text-format checker (CI's scrape
    gate and the test suite's oracle): every non-comment line must be a
    well-formed sample, every # TYPE must name a known type, and at
    least one sample must exist. OpenMetrics-style bucket exemplars
    (``... 5 # {trace_id="..."} 0.003``) are accepted. Returns the
    sample count."""
    sample_re = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{([a-zA-Z_][a-zA-Z0-9_]*="
        r'"(\\.|[^"\\])*"(,[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*")*)?\})?'
        r" (NaN|[+-]?Inf|[+-]?[0-9.eE+-]+)( [0-9]+)?"
        r'( # \{[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*"\}'
        r" (NaN|[+-]?Inf|[+-]?[0-9.eE+-]+)( [0-9.eE+-]+)?)?$"
    )
    samples = 0
    for i, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) != 4 or parts[3] not in (
                "counter", "gauge", "histogram", "summary", "untyped",
            ):
                raise ValueError(f"line {i}: bad # TYPE: {line!r}")
        elif line.startswith("#"):
            if not line.startswith("# HELP "):
                raise ValueError(f"line {i}: unknown comment: {line!r}")
        elif sample_re.match(line):
            samples += 1
        else:
            raise ValueError(f"line {i}: malformed sample: {line!r}")
    if samples == 0:
        raise ValueError("no samples in exposition")
    return samples
