"""Per-device observability plane: HBM gauges, a compile-event ledger,
and shard-skew instruments for the multi-chip frontier (ISSUE 18).

Three cooperating pieces, all host-side (no new kernels):

* ``CompileLedger`` / ``KernelWatch`` — every jitted entry point funnels
  through ``_LazyJit`` (ops/flat.py) or the sharded step caches
  (parallel/sharded.py); both wrap the built callable in a
  ``KernelWatch`` that detects the FIRST call per (shapes, dtypes,
  statics) signature and notes its wall duration into the module-level
  ``LEDGER``. jax.jit compiles synchronously on that first call, so the
  note is a faithful compile event without touching XLA internals —
  and a *steady-state* note is exactly the PR 11 capacity-hysteresis
  incident (one recompile per step, a silent 3x e2e loss), now a
  watched quantity: ``mqtt_tpu_matcher_recompiles_total{kernel}`` plus
  a compile-seconds histogram, with a bounded event ring carrying
  kernel/shape attribution for test failure messages.

* ``DeviceStatsPlane`` — per-device HBM gauges (live/peak/limit via
  ``jax.Device.memory_stats()``; backends without it report the -1
  sentinel on /metrics and ``null`` in JSON), the ``device_skew_ratio``
  gauge and per-tile hit/fill families (fed by ``ShardedTpuMatcher``),
  and the JSON snapshot behind ``GET /devices``, the
  ``$SYS/broker/devices/#`` tree, and the ``devices_*.json`` trigger
  dump sibling. Per-device duty/overlap/idle-gap windows live in
  ``tracing.DeviceProfiler`` (per-device generalization); the plane
  only *reads* them for the snapshot.

The ledger lock is ``device_stats`` (LOCK_NAMES/LOCK_ORDER blessed); it
is a leaf — registry child registration happens OUTSIDE it so no
device_stats -> metrics_registry edge exists.
"""

from __future__ import annotations

import contextlib
import threading
import time
import weakref
from collections import deque
from typing import Any, Callable, Optional

import numpy as np

from ..telemetry import Histogram

# compile wall-times: ~1ms trace-cache hits up to minute-scale XLA runs
COMPILE_BOUNDS = (
    0.001, 0.003, 0.01, 0.03, 0.1, 0.3, 1.0, 3.0, 10.0, 30.0, 60.0,
)

# one attribution ring, not per kernel: recent-first is what a failing
# steady-state assert wants to print
_EVENT_RING = 256

# HBM gauge value when the backend cannot answer (CPU-jax has no
# memory_stats); /metrics carries the sentinel, JSON carries null
HBM_UNKNOWN = -1.0


def _sig_of(args: tuple, kwargs: dict) -> tuple:
    """The jit-signature key for one call: array args by (shape, dtype),
    hashable non-array args (the statics) by value. Mirrors what jax.jit
    keys its compile cache on closely enough that a NEW key here is a
    new traced/compiled program for our kernels (all statics pass by
    keyword, all arrays positionally)."""
    key: list = []
    for a in args:
        shp = getattr(a, "shape", None)
        if shp is not None:
            # the dtype OBJECT, not str(dtype): numpy/jax dtypes hash and
            # compare by identity semantics, and their __str__ costs ~4us
            # per array — 20x the rest of the probe
            key.append((tuple(shp), getattr(a, "dtype", None)))
        else:
            key.append(a if isinstance(a, (int, float, bool, str, type(None))) else type(a).__name__)
    for k in sorted(kwargs):
        v = kwargs[k]
        shp = getattr(v, "shape", None)
        if shp is not None:
            key.append((k, tuple(shp), getattr(v, "dtype", None)))
        else:
            key.append((k, v if isinstance(v, (int, float, bool, str, type(None))) else type(v).__name__))
    return tuple(key)


def _shape_bucket(args: tuple, kwargs: dict) -> str:
    """Human-readable signature for the attribution ring: array shapes
    plus the static kwargs, e.g. ``"64x8,64x8,capacity=512"``."""
    parts: list[str] = []
    for a in args:
        shp = getattr(a, "shape", None)
        if shp is not None:
            parts.append("x".join(str(d) for d in shp) or "scalar")
    for k in sorted(kwargs):
        v = kwargs[k]
        if isinstance(v, (int, float, bool, str)):
            parts.append(f"{k}={v}")
    return ",".join(parts)[:160]


class CompileLedger:
    """Bounded record of compile events with per-kernel counts. One
    module-level instance (``LEDGER``) serves every kernel in the
    process; broker instances bind their registries to it so the
    labeled counter family and the compile-seconds histogram appear on
    each broker's /metrics without the ledger holding them alive."""

    def __init__(self) -> None:
        # lazy import: telemetry <- locked <- telemetry is already a
        # settled cycle; devicestats itself is imported lazily from the
        # kernel modules so `import mqtt_tpu.ops` stays light
        from ..utils.locked import InstrumentedLock

        self._lock = InstrumentedLock("device_stats")
        self._counts: dict[str, int] = {}
        self._events: deque = deque(maxlen=_EVENT_RING)
        self._total = 0
        # the compile clock (compile_clock): seconds of FINISHED
        # first-signature calls, plus the start stamps of running ones
        self._compile_done_s = 0.0
        self._compiling: dict[int, float] = {}
        self._compile_seq = 0
        self.compile_hist = Histogram(bounds=COMPILE_BOUNDS)
        self._registries: "weakref.WeakSet" = weakref.WeakSet()

    # -- registry binding --------------------------------------------------

    def bind_registry(self, registry) -> None:
        """Expose this ledger on one broker's /metrics: the
        compile-seconds histogram plus a labeled recompiles counter per
        already-seen kernel (later first-seen kernels register their
        child on the fly). Idempotent; holds no ledger lock while
        talking to the registry."""
        with self._lock:
            kernels = list(self._counts)
        self._registries.add(registry)
        registry.histogram(
            "mqtt_tpu_matcher_compile_seconds",
            "Wall seconds of each jit compile (first call per signature)",
            bounds=COMPILE_BOUNDS,
            fn=lambda: self.compile_hist,
        )
        for kernel in kernels:
            self._register_kernel(registry, kernel)

    def _register_kernel(self, registry, kernel: str) -> None:
        registry.counter(
            "mqtt_tpu_matcher_recompiles_total",
            "jit compile events per kernel (a NONZERO steady-state rate "
            "is the PR 11 recompile-churn failure mode)",
            fn=lambda k=kernel: self.count(k),
            kernel=kernel,
        )

    # -- event intake ------------------------------------------------------

    @contextlib.contextmanager
    def compiling(self):
        """The compile clock runs while the body does: KernelWatch wraps
        every first-signature call in it."""
        with self._lock:
            self._compile_seq += 1
            token = self._compile_seq
            self._compiling[token] = time.perf_counter()
        try:
            yield
        finally:
            now = time.perf_counter()
            with self._lock:
                self._compile_done_s += now - self._compiling.pop(token)

    def compile_clock(self) -> float:
        """Seconds this process has spent inside first-signature jit
        calls, INCLUDING the ones still running. Cold compile is set-up,
        not service and not a hang: the staging controller drops any
        service-time sample during which this clock moved
        (staging._drain_loop), and the dispatch watchdog runs on wall
        time minus this clock (resilience._GuardTask.wait). Concurrent
        compiles each count, so the clock may outrun the wall — it only
        ever errs toward treating a window as set-up."""
        now = time.perf_counter()
        with self._lock:
            return self._compile_done_s + sum(
                now - t0 for t0 in self._compiling.values()
            )

    def note_compile(self, kernel: str, shape_bucket: str, seconds: float) -> None:
        """Record one compile event; the single seam every jit entry
        point funnels through."""
        with self._lock:
            first = kernel not in self._counts
            self._counts[kernel] = self._counts.get(kernel, 0) + 1
            self._total += 1
            self.compile_hist.observe(seconds)
            self._events.append(
                {
                    "kernel": kernel,
                    "shape_bucket": shape_bucket,
                    "seconds": round(seconds, 6),
                    "time_unix": time.time(),  # brokerlint: ok=R3 wall-clock event timestamp for the attribution ring, not an interval
                }
            )
        if first:
            # child registration outside the ledger lock: device_stats
            # stays a leaf in the lock-order graph
            for registry in list(self._registries):
                self._register_kernel(registry, kernel)

    # -- reads -------------------------------------------------------------

    def count(self, kernel: str) -> int:
        with self._lock:
            return self._counts.get(kernel, 0)

    def counts(self) -> dict:
        with self._lock:
            return dict(self._counts)

    def total(self) -> int:
        with self._lock:
            return self._total

    def events(self, n: Optional[int] = None) -> list:
        """Most-recent-last compile events (the attribution ring)."""
        with self._lock:
            evs = list(self._events)
        return evs if n is None else evs[-n:]

    def attribution(self, since_total: int = 0) -> str:
        """Human-readable blame for compile events past ``since_total``
        — what a failed steady-state-recompile assert prints."""
        evs = self.events()
        new = max(0, self.total() - since_total)
        tail = evs[-new:] if new else []
        if not tail:
            return "no compile events recorded"
        lines = [
            f"  {e['kernel']}[{e['shape_bucket']}] {e['seconds'] * 1e3:.1f}ms"
            for e in tail
        ]
        return f"{new} compile event(s):\n" + "\n".join(lines)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "total": self._total,
                "kernels": dict(self._counts),
                "recent": list(self._events)[-32:],
                "seconds": self.compile_hist.summary(),
            }


LEDGER = CompileLedger()

class KernelWatch:
    """Wrap a jitted callable; time the first call per new signature and
    note it as a compile event. The steady-state cost is one signature
    tuple per *batch* (not per message) plus a set lookup."""

    __slots__ = ("kernel", "fn", "ledger", "_seen", "_lock")

    def __init__(self, kernel: str, fn: Callable, ledger: Optional[CompileLedger] = None) -> None:
        self.kernel = kernel
        self.fn = fn
        self.ledger = LEDGER if ledger is None else ledger
        self._seen: set = set()
        self._lock = threading.Lock()  # anonymous: guards _seen only, never calls out

    def __call__(self, *args, **kwargs):
        key = _sig_of(args, kwargs)
        if key in self._seen:
            return self.fn(*args, **kwargs)
        t0 = time.perf_counter()
        with self.ledger.compiling():
            out = self.fn(*args, **kwargs)
        seconds = time.perf_counter() - t0
        with self._lock:
            new = key not in self._seen
            self._seen.add(key)
        if new:
            self.ledger.note_compile(self.kernel, _shape_bucket(args, kwargs), seconds)
        return out


def skew_of(tile_hits) -> float:
    """max/mean over per-tile hit counts — 1.0 is a perfectly balanced
    mesh, ``n_tiles`` is one hot tile doing all the work, 0.0 means no
    hits yet (no skew claim before traffic)."""
    arr = np.asarray(tile_hits, dtype=np.float64)
    if arr.size == 0:
        return 0.0
    mean = float(arr.mean())
    if mean <= 0.0:
        return 0.0
    return float(arr.max()) / mean


class DeviceStatsPlane:
    """The per-device snapshot/surface layer: owns the HBM gauges and
    the skew gauge, binds the ledger to the broker's registry, and
    renders the JSON for /devices, $SYS/broker/devices/#, and the
    trigger-dump sibling. Stateless beyond its attachment points — all
    live numbers come from jax, the profiler, the matcher, and the
    ledger at read time."""

    def __init__(
        self,
        registry=None,
        hbm_watermark: float = 0.9,
        ledger: Optional[CompileLedger] = None,
        devices: Optional[list] = None,
    ) -> None:
        self.registry = registry
        self.hbm_watermark = float(hbm_watermark)
        self.ledger = LEDGER if ledger is None else ledger
        self.profiler = None  # tracing.DeviceProfiler, per-device windows
        self.matcher = None  # ShardedTpuMatcher for tile/skew state
        # ``devices=None`` enumerates the default backend, which
        # INITIALIZES it — and a chip belongs to one process. A broker
        # with no device engine passes ``[]`` (ledger-only plane) so a
        # host-only worker never takes the chip from the process that
        # serves from it (server.py).
        if devices is None:
            import jax

            devices = jax.devices()
        self._devices: list = list(devices)
        if registry is not None:
            self.ledger.bind_registry(registry)
            for d in self._devices:
                did = str(getattr(d, "id", 0))
                for name, key in (
                    ("mqtt_tpu_device_hbm_live_bytes", "bytes_in_use"),
                    ("mqtt_tpu_device_hbm_peak_bytes", "peak_bytes_in_use"),
                    ("mqtt_tpu_device_hbm_limit_bytes", "bytes_limit"),
                ):
                    registry.gauge(
                        name,
                        "Per-device HBM occupancy via memory_stats() "
                        "(-1: backend cannot answer)",
                        fn=lambda d=d, k=key: self._mem(d, k),
                        device=did,
                    )
                registry.gauge(
                    "mqtt_tpu_device_hbm_ratio",
                    "live/limit HBM occupancy per device (0.0 unknown) — "
                    "the HBM-watermark SLO source",
                    fn=lambda d=d: self._mem_ratio(d),
                    device=did,
                )
            registry.gauge(
                "mqtt_tpu_device_skew_ratio",
                "max/mean per-tile hit counts across the shard mesh "
                "(1.0 balanced, 0.0 no traffic)",
                fn=self.skew_ratio,
            )

    # -- HBM ---------------------------------------------------------------

    @staticmethod
    def _mem(device, key: str) -> float:
        try:
            stats = device.memory_stats()
        except Exception:  # brokerlint: ok=R4 memory_stats is per-backend best effort (CPU-jax raises); sentinel keeps the scrape alive
            return HBM_UNKNOWN
        if not stats or key not in stats:
            return HBM_UNKNOWN
        return float(stats[key])

    @classmethod
    def _mem_ratio(cls, device) -> float:
        live = cls._mem(device, "bytes_in_use")
        limit = cls._mem(device, "bytes_limit")
        if live < 0.0 or limit <= 0.0:
            return 0.0
        return live / limit

    def hbm_ratio(self) -> float:
        """The worst (max) per-device live/limit ratio — what the
        watermark objective and the /healthz degraded entry read."""
        ratios = [self._mem_ratio(d) for d in self._devices]
        return max(ratios) if ratios else 0.0

    def hbm_degraded(self) -> bool:
        ratio = self.hbm_ratio()
        # a backend that cannot answer (ratio 0.0) is never degraded
        return ratio > 0.0 and ratio >= self.hbm_watermark

    # -- attachments -------------------------------------------------------

    def attach_profiler(self, profiler) -> None:
        self.profiler = profiler

    def attach_matcher(self, matcher) -> None:
        """Adopt a matcher's tile-skew state (ShardedTpuMatcher exports
        tile_hit_counts/tile_fill_hists; a single-device TpuMatcher has
        neither and the skew gauge stays 0.0)."""
        self.matcher = matcher
        hists = getattr(matcher, "tile_fill_hists", None)
        if self.registry is not None and hists:
            for t, h in enumerate(hists):
                self.registry.counter(
                    "mqtt_tpu_device_tile_hits_total",
                    "Cumulative matcher hits landing on each batch tile",
                    fn=lambda m=matcher, t=t: int(m.tile_hit_counts()[t]),
                    tile=str(t),
                )
                self.registry.histogram(
                    "mqtt_tpu_device_tile_fill_ratio",
                    "Per-batch fill of each tile's compact capacity",
                    bounds=h.bounds,
                    fn=lambda h=h: h,
                    tile=str(t),
                )

    def skew_ratio(self) -> float:
        m = self.matcher
        if m is None:
            return 0.0
        fn = getattr(m, "device_skew_ratio", None)
        return float(fn()) if fn is not None else 0.0

    # -- renders -----------------------------------------------------------

    def snapshot(self) -> dict:
        """The /devices + dump-sibling JSON body."""
        prof = self.profiler
        windows = prof.device_snapshot() if prof is not None else {}
        devices = []
        for d in self._devices:
            did = int(getattr(d, "id", 0))
            live = self._mem(d, "bytes_in_use")
            peak = self._mem(d, "peak_bytes_in_use")
            limit = self._mem(d, "bytes_limit")
            entry: dict = {
                "id": did,
                "platform": str(getattr(d, "platform", "unknown")),
                "hbm": {
                    "live_bytes": None if live < 0 else int(live),
                    "peak_bytes": None if peak < 0 else int(peak),
                    "limit_bytes": None if limit < 0 else int(limit),
                    "ratio": round(self._mem_ratio(d), 6),
                },
            }
            entry.update(
                windows.get(
                    did,
                    {
                        "duty_cycle": 0.0,
                        "overlap_ratio": 0.0,
                        "batches": 0,
                        "d2h_bytes_total": 0,
                        "issue_p99_ms": 0.0,
                        "d2h_p99_ms": 0.0,
                        "idle_gap_p99_ms": 0.0,
                    },
                )
            )
            devices.append(entry)
        m = self.matcher
        tile_hits = (
            [int(x) for x in m.tile_hit_counts()]
            if m is not None and hasattr(m, "tile_hit_counts")
            else []
        )
        return {
            "time_unix": int(time.time()),  # brokerlint: ok=R3 wall-clock snapshot stamp, not an interval
            "n_devices": len(self._devices),
            "devices": devices,
            "skew": {
                "ratio": round(self.skew_ratio(), 6),
                "tile_hits": tile_hits,
            },
            "hbm": {
                "watermark": self.hbm_watermark,
                "ratio": round(self.hbm_ratio(), 6),
                "degraded": self.hbm_degraded(),
            },
            "compiles": self.ledger.snapshot(),
        }

    def sys_tree(self) -> dict:
        """Flat ``suffix -> value`` rows for ``$SYS/broker/devices/#``."""
        out: dict[str, Any] = {}
        snap = self.snapshot()
        for dev in snap["devices"]:
            base = str(dev["id"])
            hbm = dev["hbm"]
            out[f"{base}/hbm_live_bytes"] = (
                -1 if hbm["live_bytes"] is None else hbm["live_bytes"]
            )
            out[f"{base}/hbm_ratio"] = hbm["ratio"]
            out[f"{base}/duty_cycle"] = round(float(dev["duty_cycle"]), 6)
            out[f"{base}/d2h_bytes_total"] = int(dev["d2h_bytes_total"])
            out[f"{base}/batches"] = int(dev["batches"])
        out["skew_ratio"] = snap["skew"]["ratio"]
        out["hbm_watermark_degraded"] = int(snap["hbm"]["degraded"])
        out["compiles/total"] = snap["compiles"]["total"]
        for kernel, n in sorted(snap["compiles"]["kernels"].items()):
            out[f"compiles/{kernel}"] = n
        return out
