"""Lock-guarded shared state plus the broker's lock-contention plane.

The reference wraps every shared map in a small mutex-guarded struct
(e.g. topics.go:249-301, packets/packets.go:66-117); ``LockedMap`` is
the one Python equivalent they all reuse.

ROADMAP item 3 says the broker path collapses 50x per-client going
10->100 clients — but which locks actually contend was guesswork until
now. ``InstrumentedLock`` is a drop-in ``threading.Lock``/``RLock``
wrapper that measures, per named lock, how long acquirers WAIT and how
long holders HOLD, aggregated by name in a process-wide ``LockPlane``
(same-named locks share one stats record, so per-test/per-server lock
churn stays bounded). The hot registries adopt it (the trie, the client
map, the governor, the metrics registry, the trace/flight rings, the
breaker, the cluster's remote-interest trie) and the telemetry plane
exports the histograms at ``GET /metrics``
(``Telemetry.attach_lock_plane``).

Overhead discipline: the plane is DISARMED by default — a disarmed
acquire is one extra attribute read and a bool test over the bare lock.
Armed, the uncontended path pays one non-blocking try-acquire plus two
``perf_counter`` reads (hold timing); the wait histogram is touched
only when the try-acquire actually missed. Stats writes happen while
the writing lock INSTANCE is held — but same-named instances on
different objects (two brokers in one process, the local and remote
tries' retained stores) share one record, so concurrent ``+=`` updates
can occasionally lose an increment under GIL preemption. That is the
same deliberately-unlocked posture as telemetry.Counter: telemetry-
grade accuracy, never a lock on the measurement path itself.

Lock-order verification (ISSUE 10) rides the same plane:

- ``LockWitness`` is the runtime half of the whole-program lock-order
  graph (tools/brokerlint/lockgraph.py is the static half): armed, every
  outermost acquire records this thread's held NAME set and merges the
  implied acquisition-order edges process-wide; an edge that closes a
  cycle is a potential-deadlock violation, recorded (and optionally
  raised) at the acquire that completed it. The tier-1 gate
  (tests/test_zz_lockwitness.py) asserts every witnessed edge appears in
  the statically extracted graph, so an extraction gap fails loudly.
- ``PreemptionInjector`` is the schedule fuzzer's hook: a seeded,
  per-thread-deterministic "maybe yield the GIL here" at every armed
  acquire/release boundary, so tests can drive hostile interleavings at
  exactly the points the lock graph says are interesting (same seed +
  same thread names => same per-thread decision sequence).

Both are opt-in and share the plane's single fast-path test: a plane
with stats, witness, and fuzz all off costs one attribute read and one
bool test per acquire, exactly as before.
"""

from __future__ import annotations

import random
import threading
from time import perf_counter, sleep
from typing import Any, Callable, Generic, Hashable, Iterable, Optional, TypeVar

from ..telemetry import Histogram

K = TypeVar("K", bound=Hashable)
V = TypeVar("V")

# the canonical lock-plane names (label values of the mqtt_tpu_lock_*
# metric families): Telemetry.attach_lock_plane registers an exposition
# child per name up front, so construction order between locks and the
# telemetry plane never decides what /metrics shows
LOCK_NAMES = (
    "clients",
    "tenants",
    "recrypt_keys",
    "topics_trie",
    "cluster_remote_trie",
    "predicate_rules",
    "retained",
    "inflight",
    "durable_store",
    "metrics_registry",
    "flight_ring",
    "trace_ring",
    "device_stats",
    "overload_governor",
    "overload_peer_pressure",
    "matcher_breaker",
    "shard_fabric",
    "mesh_topology",
    "interest_bloom",
    "dup_suppressor",
)


class LockStats:
    """Aggregate wait/hold accounting for one lock NAME (all same-named
    lock instances share one record)."""

    __slots__ = (
        "name",
        "acquisitions",
        "contended",
        "wait_s",
        "hold_s",
        "wait_hist",
        "hold_hist",
    )

    def __init__(self, name: str) -> None:
        self.name = name
        self.clear()

    def clear(self) -> None:
        """Zero IN PLACE: live locks and registered metric closures hold
        references to this record, so reset must never replace it."""
        self.acquisitions = 0
        self.contended = 0  # acquires that actually blocked
        self.wait_s = 0.0  # total seconds spent waiting (contended only)
        self.hold_s = 0.0  # total seconds the lock was held
        self.wait_hist = Histogram()
        self.hold_hist = Histogram()

    def as_dict(self) -> dict:
        return {
            "name": self.name,
            "acquisitions": self.acquisitions,
            "contended": self.contended,
            "wait_s": round(self.wait_s, 6),
            "hold_s": round(self.hold_s, 6),
            "wait_p99_ms": round(self.wait_hist.percentile(0.99) * 1e3, 4),
            "hold_p99_ms": round(self.hold_hist.percentile(0.99) * 1e3, 4),
        }


class LockOrderViolation(AssertionError):
    """An armed ``LockWitness`` observed an acquisition-order edge that
    closes a cycle: two threads taking the same named locks in opposite
    orders is a latent deadlock even when this run got lucky."""


class LockWitness:
    """The runtime lock-order witness (ISSUE 10): per-thread held NAME
    stacks plus a process-wide merged edge set ``(held, acquired)``.

    Cost discipline: a KNOWN edge costs one dict probe per held name on
    the acquiring thread; only a never-seen edge takes the witness mutex
    (to merge + cycle-check once). Disarmed (plane.witness is None) the
    whole machinery is a single ``is None`` test inside the already-slow
    armed path — and the plane's fast path skips even that.

    Same-name nesting (two instances sharing one stats record, or RLock
    re-entry races where depth bookkeeping is per-instance) is recorded
    as a held-stack push but never as a self-edge: name-level order has
    nothing to say about one name, and the static graph models re-entry
    the same way.
    """

    def __init__(self, raise_on_cycle: bool = False) -> None:
        self._mutex = threading.Lock()
        self._tls = threading.local()
        self.raise_on_cycle = raise_on_cycle
        # (held_name, acquired_name) -> first-observed (thread, stack) —
        # the evidence the cross-validation gate prints on a mismatch
        self.edges: dict[tuple[str, str], tuple[str, tuple[str, ...]]] = {}
        # cycle descriptions, in observation order
        self.violations: list[str] = []

    def held(self) -> tuple[str, ...]:
        """This thread's current held-name stack (outermost first)."""
        return tuple(getattr(self._tls, "stack", ()))

    def note_acquire(self, name: str) -> None:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        fresh = None
        for h in stack:
            if h != name and (h, name) not in self.edges:
                if fresh is None:
                    fresh = []
                fresh.append((h, name))
        stack.append(name)
        if fresh is None:
            return
        evidence = (threading.current_thread().name, tuple(stack))
        mine: list[str] = []
        with self._mutex:
            for edge in fresh:
                if edge in self.edges:
                    continue
                self.edges[edge] = evidence
                cyc = self._cycle_through(edge)
                if cyc is not None:
                    msg = (
                        "lock-order cycle: " + " -> ".join(cyc)
                        + f" (closed by {evidence[0]} holding {evidence[1]})"
                    )
                    self.violations.append(msg)
                    mine.append(msg)
        if mine and self.raise_on_cycle:
            # only violations THIS acquire created raise — an innocent
            # later edge must not re-raise someone else's old cycle. The
            # refused acquire's push unwinds here, and
            # InstrumentedLock.acquire releases the just-taken inner
            # lock before re-raising, so the tripwire fails the
            # offending acquire instead of leaking held state.
            stack.pop()
            raise LockOrderViolation(mine[0])

    def note_release(self, name: str) -> None:
        stack = getattr(self._tls, "stack", None)
        if not stack:
            return
        # releases are usually LIFO but the API does not require it
        # (acquire A, acquire B, release A): drop the LAST occurrence
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] == name:
                del stack[i]
                return

    def _cycle_through(self, edge: tuple[str, str]) -> Optional[list[str]]:
        """A cycle containing ``edge`` if one now exists: DFS from the
        edge's destination back to its source over the observed edges.
        Called under ``_mutex`` with a consistent edge set."""
        src, dst = edge
        adj: dict[str, list[str]] = {}
        for a, b in self.edges:
            adj.setdefault(a, []).append(b)
        path = [dst]
        seen = {dst}

        def dfs(node: str) -> bool:
            if node == src:
                return True
            for nxt in adj.get(node, ()):
                if nxt in seen:
                    continue
                seen.add(nxt)
                path.append(nxt)
                if dfs(nxt):
                    return True
                path.pop()
            return False

        if dfs(dst):
            return [src] + path + ([] if path[-1] == src else [src])
        return None


class PreemptionInjector:
    """Seeded, deterministic preemption injection at the lock plane's
    acquire/release boundaries (the schedule fuzzer's engine,
    tests/test_race.py).

    Determinism contract: each thread draws from its OWN
    ``random.Random(f"{seed}:{thread.name}")`` stream, so the decision
    SEQUENCE a thread sees depends only on (seed, thread name, that
    thread's own lock-op order) — never on how the OS interleaved the
    threads. Same seed + same per-thread workload => identical per-thread
    decision logs (``trace()``), which is what "same seed => same
    schedule" means under a preemptive GIL.

    ``names`` restricts injection to the graph's interesting edges (the
    hot staging/governor/breaker/cluster set); None fuzzes every named
    lock. A hit yields the GIL (``sleep(pause_s)``; 0 is a bare yield),
    which is precisely the "preempt at the boundary" primitive the blunt
    setswitchinterval sweep could only apply globally."""

    def __init__(
        self,
        seed: int,
        rate: float = 0.4,
        pause_s: float = 0.0,
        names: Optional[frozenset[str]] = None,
    ) -> None:
        self.seed = seed
        self.rate = rate
        self.pause_s = pause_s
        self.names = names
        self._tls = threading.local()
        self._mutex = threading.Lock()
        # thread name -> [(op_index, lock name, phase, preempted)]
        self._logs: dict[str, list[tuple[int, str, str, bool]]] = {}

    def _state(self) -> tuple[random.Random, list]:
        st = getattr(self._tls, "state", None)
        if st is None:
            tname = threading.current_thread().name
            with self._mutex:
                # a re-used thread name CONTINUES its own log (its RNG
                # stream restarts with the new thread — the combined
                # log is still deterministic for deterministic
                # per-thread workloads)
                log = self._logs.setdefault(tname, [])
            st = self._tls.state = (random.Random(f"{self.seed}:{tname}"), log)
        return st

    def __call__(self, name: str, phase: str) -> None:
        if self.names is not None and name not in self.names:
            return
        rng, log = self._state()
        hit = rng.random() < self.rate
        log.append((len(log), name, phase, hit))
        if hit:
            sleep(self.pause_s)

    def trace(self) -> dict[str, list[tuple[int, str, str, bool]]]:
        """Per-thread decision logs (the determinism assertion's key)."""
        with self._mutex:
            return {t: list(ops) for t, ops in self._logs.items()}


class LockPlane:
    """The process-wide registry of named lock stats, plus the optional
    order witness and preemption-fuzz hook. Armed/disarmed by the server
    (``Options.profile_locks``); arming is refcounted so two in-process
    brokers (tests) cannot disarm each other.

    ``active`` is the single fast-path test ``InstrumentedLock.acquire``
    reads: true when ANY of stats arming, the witness, or the fuzz hook
    is on. ``enabled`` keeps its historical meaning (stats arming only)
    because the stats writes are the expensive part."""

    def __init__(self) -> None:
        self._names_mutex = threading.Lock()
        self._stats: dict[str, LockStats] = {}
        self._armed = 0
        self.enabled = False
        self.active = False
        self.witness: Optional[LockWitness] = None
        self.fuzz: Optional[Callable[[str, str], None]] = None

    def stats(self, name: str) -> LockStats:
        with self._names_mutex:
            st = self._stats.get(name)
            if st is None:
                st = self._stats[name] = LockStats(name)
            return st

    def _refresh_active_locked(self) -> None:
        self.active = (
            self.enabled or self.witness is not None or self.fuzz is not None
        )

    def arm(self) -> None:
        with self._names_mutex:
            self._armed += 1
            self.enabled = True
            self._refresh_active_locked()

    def disarm(self) -> None:
        with self._names_mutex:
            self._armed = max(0, self._armed - 1)
            self.enabled = self._armed > 0
            self._refresh_active_locked()

    def arm_witness(self, raise_on_cycle: bool = False) -> LockWitness:
        """Attach (or return the already-attached) order witness.
        ``raise_on_cycle=True`` ESCALATES an existing witness to the
        raising tripwire (a caller that asked for hard failures must
        get them even when conftest armed a recording witness first);
        it never de-escalates — disarm and re-arm for that."""
        with self._names_mutex:
            if self.witness is None:
                self.witness = LockWitness(raise_on_cycle=raise_on_cycle)
            elif raise_on_cycle:
                self.witness.raise_on_cycle = True
            self._refresh_active_locked()
            return self.witness

    def disarm_witness(self) -> None:
        with self._names_mutex:
            self.witness = None
            self._refresh_active_locked()

    def arm_fuzz(self, fuzz: Callable[[str, str], None]) -> None:
        """Attach the preemption hook, called as ``fuzz(name, phase)``
        with phase in {"acquire", "release"} at every armed boundary."""
        with self._names_mutex:
            self.fuzz = fuzz
            self._refresh_active_locked()

    def disarm_fuzz(self) -> None:
        with self._names_mutex:
            self.fuzz = None
            self._refresh_active_locked()

    def reset(self) -> None:
        """Zero every stats record (tests) — in
        place, so locks and metric closures created BEFORE the reset
        keep feeding the same records afterwards."""
        with self._names_mutex:
            for st in self._stats.values():
                st.clear()

    def snapshot(self) -> list[LockStats]:
        with self._names_mutex:
            return list(self._stats.values())

    def total_wait_s(self) -> float:
        return sum(st.wait_s for st in self.snapshot())

    def top_contended(self, k: int = 3) -> list[dict]:
        """The k most-contended lock names by total wait time."""
        ranked = sorted(self.snapshot(), key=lambda s: s.wait_s, reverse=True)
        return [st.as_dict() for st in ranked[:k] if st.acquisitions]

    def wait_share(self, name: str) -> float:
        """One lock's share of ALL measured lock wait (the top-K
        contended-locks gauge set renders this per name)."""
        total = self.total_wait_s()
        if total <= 0.0:
            return 0.0
        return self.stats(name).wait_s / total


# the process default: broker locks register here by name; the server
# arms it (Options.profile_locks) and Telemetry exports it
DEFAULT_PLANE = LockPlane()


class InstrumentedLock:
    """A named, plane-registered ``threading.Lock``/``RLock`` drop-in:
    context manager, ``acquire``/``release``/``locked``. Re-entrant
    acquires (``rlock=True``) time only the outermost hold."""

    __slots__ = ("_inner", "_plane", "stats", "_local")

    def __init__(
        self,
        name: str,
        rlock: bool = False,
        plane: Optional[LockPlane] = None,
    ) -> None:
        self._inner: Any = threading.RLock() if rlock else threading.Lock()
        self._plane = plane if plane is not None else DEFAULT_PLANE
        self.stats = self._plane.stats(name)
        self._local = threading.local()  # re-entrancy depth + hold start

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        plane = self._plane
        if not plane.active:
            return self._inner.acquire(blocking, timeout)
        fuzz = plane.fuzz
        if fuzz is not None:
            # pre-acquire boundary: the injector may yield the GIL here,
            # widening the window in which another thread takes this (or
            # a conflicting) lock first
            fuzz(self.stats.name, "acquire")
        ok = self._inner.acquire(False)
        wait = 0.0
        if not ok:
            if not blocking:
                return False
            t0 = perf_counter()
            ok = self._inner.acquire(True, timeout)
            if not ok:
                return False
            wait = perf_counter() - t0
        local = self._local
        depth = getattr(local, "depth", 0)
        local.depth = depth + 1
        if depth == 0:
            witness = plane.witness
            if witness is not None:
                try:
                    witness.note_acquire(self.stats.name)
                except BaseException:
                    # raise_on_cycle tripwire: fail THIS acquire cleanly —
                    # unwind the depth we claimed and release the inner
                    # lock we just took, or every other thread deadlocks
                    # on a lock nobody will ever release
                    local.depth = depth
                    self._inner.release()
                    raise
            if plane.enabled:
                # stats writes below happen while THIS lock is held, so
                # the shared per-name record is single-writer in practice
                local.t_held = perf_counter()
                st = self.stats
                st.acquisitions += 1
                if wait > 0.0:
                    st.contended += 1
                    st.wait_s += wait
                    st.wait_hist.observe(wait)
        return True

    def release(self) -> None:
        plane = self._plane
        local = self._local
        depth = getattr(local, "depth", 0)
        if depth > 0:
            # the depth bookkeeping must unwind even when the plane was
            # disarmed MID-HOLD (Server.close() racing a writer thread):
            # skipping the decrement would leave this thread's counter
            # stuck and silently blind the stats after a later re-arm
            local.depth = depth - 1
            if depth == 1:
                witness = plane.witness
                if witness is not None:
                    witness.note_release(self.stats.name)
                if plane.enabled:
                    held = perf_counter() - getattr(
                        local, "t_held", perf_counter()
                    )
                    st = self.stats
                    st.hold_s += held
                    st.hold_hist.observe(held)
        self._inner.release()
        if plane.active:
            fuzz = plane.fuzz
            if fuzz is not None:
                # post-release boundary: yield so a waiter can run NOW,
                # while this thread is about to re-contend (the
                # convoy/AB-BA shape)
                fuzz(self.stats.name, "release")

    def locked(self) -> bool:
        return bool(self._inner.locked()) if hasattr(self._inner, "locked") else False

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc: object) -> None:
        self.release()


class LockedMap(Generic[K, V]):
    """RLock-protected dict with copy-on-iterate semantics. Pass a
    ``name`` to register the lock with the contention plane (the hot
    singletons — the client registry, the retained store); unnamed maps
    (per-client state, a trie particle's subscription containers) keep
    the bare RLock and stay off the plane. Bare is not free: a map is an
    object, a dict and an ``RLock`` the collector tracks, so a trie
    particle makes one only with its first entry of the kind and drops
    it with the last (``topics._Particle``); a particle that holds
    nothing has no map and no lock."""

    def __init__(self, name: Optional[str] = None) -> None:
        self._lock: Any = (
            threading.RLock() if name is None else InstrumentedLock(name, rlock=True)
        )
        self.internal: dict[K, V] = {}

    def add(self, key: K, val: V) -> None:
        with self._lock:
            self.internal[key] = val

    def get(self, key: K) -> Optional[V]:
        with self._lock:
            return self.internal.get(key)

    def present(self, keys: Iterable[K]) -> dict[K, V]:
        """The entries whose key is among ``keys``, read under ONE
        acquisition with plain dict probes inside: a batch completion
        resolves a whole slice's fan-out targets this way instead of
        one ``get`` (an instrumented lock pair) a target."""
        with self._lock:
            internal = self.internal
            return {k: internal[k] for k in internal.keys() & keys}

    def get_all(self) -> dict[K, V]:
        with self._lock:
            return dict(self.internal)

    def delete(self, key: K) -> None:
        with self._lock:
            self.internal.pop(key, None)

    def __len__(self) -> int:
        with self._lock:
            return len(self.internal)
