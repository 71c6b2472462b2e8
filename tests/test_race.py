"""Race hardening for the threaded matcher machinery.

The fold/rebuild/observer paths rest on hand-written concurrency
contracts — the copy-on-write fold clone (ops/flat.py), the lock-order
rule that a sharded rebuild must not run under the trie lock
(ops/delta.py:_rebuild_snapshot), torn-read retries in the lock-free trie
walks. ``go test -race`` has no CPython analog, so these tests do what
the reference's race detector did empirically: hammer the structures
from multiple threads and assert bit-parity and liveness throughout.

The main test churns subscriptions from two writer threads while a
matcher thread matches continuously; every batch is checked for parity
against the live trie (topics the overlay routes to the host are always
correct; device-served topics must match the trie too whenever the trie
is quiescent for the comparison instant — we assert the DeltaMatcher
contract instead: every result equals a host walk taken immediately
after, with all raced filters routed). Deadlock shows up as the
``timeout`` marker killing the test.
"""

import contextlib
import faulthandler
import random
import sys
import threading
import time

import pytest

from mqtt_tpu.ops.delta import DeltaMatcher
from mqtt_tpu.packets import Subscription
from mqtt_tpu.topics import SHARE_PREFIX, TopicsIndex


@contextlib.contextmanager
def switch_interval(interval_s: float):
    """Thread-schedule fuzzing fixture (ROADMAP "Correctness tooling"):
    pin ``sys.setswitchinterval`` for the block's duration — a tiny
    interval preempts threads mid-bytecode-run orders of magnitude more
    often than the 5ms default, shaking out interleavings the default
    schedule practically never produces — and ALWAYS restore the
    original, or the whole session runs degraded afterwards."""
    prev = sys.getswitchinterval()
    sys.setswitchinterval(interval_s)
    try:
        yield
    finally:
        sys.setswitchinterval(prev)

SEGS = ["alpha", "beta", "gamma", "delta", "x"]


def canon(s):
    return (
        {c: (sub.qos, sub.no_local) for c, sub in s.subscriptions.items()},
        {f: frozenset(m) for f, m in s.shared.items()},
        frozenset(s.inline_subscriptions),
    )


def _rand_filter(r):
    parts = [r.choice(SEGS + ["+"]) for _ in range(r.randint(1, 3))]
    if r.random() < 0.2:
        parts[-1] = "#"
    return "/".join(parts)


def _rand_topic(r):
    return "/".join(r.choice(SEGS) for _ in range(r.randint(1, 3)))


def test_churn_while_matching_two_writers():
    """>=2 writer threads mutate the trie for several seconds while the
    main thread matches continuously through a background-rebuilding
    DeltaMatcher; every batch must be served (no deadlock, no exception)
    and spot-checked batches must be bit-identical to the live trie under
    a writer pause."""
    index = TopicsIndex()
    r0 = random.Random(1)
    for i in range(2000):
        index.subscribe(f"base{i}", Subscription(filter=_rand_filter(r0), qos=i % 3))

    # deadlock backstop (no pytest-timeout in the image): a wedged lock
    # pair dumps all thread stacks and kills the process instead of
    # hanging the suite forever
    faulthandler.dump_traceback_later(110, exit=True)
    m = DeltaMatcher(
        index, max_levels=4, rebuild_after=64, rebuild_interval=0.05, background=True
    )
    stop = threading.Event()
    pause = threading.Event()
    resume = threading.Event()
    paused = threading.Barrier(3, timeout=30)
    errors: list = []

    def writer(seed: int) -> None:
        r = random.Random(seed)
        i = 0
        try:
            while not stop.is_set():
                if pause.is_set():
                    paused.wait()  # rendezvous with the checker
                    resume.wait()  # released when the parity check is done
                    continue
                flt = _rand_filter(r)
                kind = r.random()
                if kind < 0.45:
                    index.subscribe(f"w{seed}_{i}", Subscription(filter=flt, qos=1))
                elif kind < 0.9:
                    index.unsubscribe(flt, f"w{seed}_{r.randint(0, max(1, i))}")
                else:
                    index.subscribe(
                        f"w{seed}_{i}",
                        Subscription(filter=f"{SHARE_PREFIX}/g{seed}/{flt}", qos=1),
                    )
                i += 1
                time.sleep(0.0005)  # ~2k mutations/s per writer; leaves
                # the GIL to the matcher thread on small hosts
        except Exception as e:  # pragma: no cover - the assertion target
            errors.append(e)

    writers = [threading.Thread(target=writer, args=(s,), daemon=True) for s in (7, 8)]
    for t in writers:
        t.start()

    r = random.Random(42)
    t_end = time.time() + 10.0
    batches = 0
    try:
        while time.time() < t_end:
            topics = [_rand_topic(r) for _ in range(256)]
            results = m.match_topics(topics)  # must not deadlock or raise
            assert len(results) == len(topics)
            batches += 1
            if batches % 5 == 0:
                # parity checkpoint: pause the writers at a barrier so the
                # trie is quiescent, then device results must equal the
                # host walk exactly
                resume.clear()
                pause.set()
                paused.wait()  # both writers parked at resume.wait()
                check = [_rand_topic(r) for _ in range(64)]
                got = m.match_topics(check)
                for topic, res in zip(check, got):
                    assert canon(res) == canon(index.subscribers(topic)), topic
                pause.clear()
                resume.set()
    finally:
        stop.set()
        pause.clear()
        resume.set()
        for t in writers:
            t.join(timeout=10)
        m.close()
    faulthandler.cancel_dump_traceback_later()
    assert not errors, errors
    # liveness floor, not a throughput claim: the CPU-jax kernel on a
    # loaded 1-core host manages a few hundred ms per 256-topic batch
    # (a wedged matcher produces 0-1; anything near the floor is alive)
    assert batches >= 5, f"matcher starved: only {batches} batches in 10s"
    # the run must have exercised the incremental machinery, not just
    # full rebuilds
    assert m.stats.rebuilds + m.stats.folds > 2


def _lazy_view_churn(duration_s: float, seed: int) -> int:
    """Lazy-view lifetime drill (ISSUE 13 satellite): writer threads
    churn subscriptions (subscribe/unsubscribe/$SHARE, plus whole-client
    unsubscribes — the disconnect/session-takeover analog) while the
    main thread resolves LAZY SubscribersView batches and consumes them
    only AFTER a delay + forced GC — so unsubscribes land exactly
    between device resolve and fan-out consumption. The snapshot table
    must keep every captured (client, Subscription) alive and coherent
    (no UAF, no torn objects); quiescent parity checkpoints pin the
    materialized views against the live host walk. Returns batches
    consumed."""
    import gc

    from mqtt_tpu import native

    if native.accel() is None:
        pytest.skip("no C toolchain: lazy views cannot exist")
    index = TopicsIndex()
    r0 = random.Random(seed)
    for i in range(800):
        index.subscribe(
            f"base{i}", Subscription(filter=_rand_filter(r0), qos=i % 3)
        )
    faulthandler.dump_traceback_later(110, exit=True)
    m = DeltaMatcher(
        index, max_levels=4, rebuild_after=32, rebuild_interval=0.05,
        background=True, lazy=True,
    )
    stop = threading.Event()
    pause = threading.Event()
    resume = threading.Event()
    paused = threading.Barrier(3, timeout=30)
    errors: list = []

    def writer(wseed: int) -> None:
        r = random.Random(wseed)
        i = 0
        owned: dict = {}  # this writer's client -> [filters] mirror
        try:
            while not stop.is_set():
                if pause.is_set():
                    paused.wait()
                    resume.wait()
                    continue
                flt = _rand_filter(r)
                kind = r.random()
                if kind < 0.4:
                    cid = f"w{wseed}_{i}"
                    index.subscribe(cid, Subscription(filter=flt, qos=1))
                    owned.setdefault(cid, []).append(flt)
                elif kind < 0.8:
                    index.unsubscribe(
                        flt, f"w{wseed}_{r.randint(0, max(1, i))}"
                    )
                elif kind < 0.9:
                    index.subscribe(
                        f"w{wseed}_{i}",
                        Subscription(
                            filter=f"{SHARE_PREFIX}/g{wseed}/{flt}", qos=1
                        ),
                    )
                elif owned:
                    # the disconnect/takeover analog: drop EVERY filter
                    # a client holds, like server.unsubscribe_client
                    victim = r.choice(list(owned))
                    for f2 in owned.pop(victim):
                        index.unsubscribe(f2, victim)
                i += 1
                time.sleep(0.0005)
        except Exception as e:  # pragma: no cover - the assertion target
            errors.append(e)

    writers = [
        threading.Thread(target=writer, args=(s,), daemon=True)
        for s in (seed + 1, seed + 2)
    ]
    for t in writers:
        t.start()

    r = random.Random(seed + 99)
    t_end = time.time() + duration_s
    batches = 0
    held: list = []  # views outliving several churn windows
    try:
        while time.time() < t_end:
            topics = [_rand_topic(r) for _ in range(128)]
            views = m.match_topics(topics)
            # let unsubscribes/disconnects land between resolve and
            # consumption, then drop any dead references they freed
            time.sleep(0.002)
            if batches % 7 == 0:
                gc.collect()
            for v in views:
                consume = getattr(v, "targets", None)
                if consume is None:
                    continue  # host-routed row: plain Subscribers
                for cid, sub in consume():
                    # snapshot-time coherence: every captured object is
                    # intact, whatever the trie did since
                    assert isinstance(cid, str) and cid
                    assert isinstance(sub.filter, str)
                    assert sub.qos in (0, 1, 2)
            # a slice of views deliberately outlives the batch (the
            # slow-consumer analog): consuming them batches later must
            # still be safe
            if batches % 3 == 0:
                # views only: a host-routed row is a plain Subscribers
                # with nothing lazy to outlive its batch
                held.extend(v for v in views[:4] if hasattr(v, "materialize"))
                if len(held) > 32:
                    for v in held[:16]:
                        mzd = v.materialize()
                        assert mzd.subscriptions is not None
                    del held[:16]
            batches += 1
            if batches % 10 == 0:
                resume.clear()
                pause.set()
                paused.wait()
                check = [_rand_topic(r) for _ in range(32)]
                got = m.match_topics(check)
                for topic, res in zip(check, got):
                    assert canon(res) == canon(index.subscribers(topic)), topic
                pause.clear()
                resume.set()
    finally:
        stop.set()
        pause.clear()
        resume.set()
        for t in writers:
            t.join(timeout=10)
        # final parity checkpoint (always runs, however slow the box
        # was: the writers are joined, so the trie is quiescent) —
        # lazy views materialized against the live host walk
        try:
            check = [_rand_topic(r) for _ in range(32)]
            got = m.match_topics(check)
            for topic, res in zip(check, got):
                assert canon(res) == canon(index.subscribers(topic)), topic
        finally:
            m.close()
    faulthandler.cancel_dump_traceback_later()
    assert not errors, errors
    return batches


def test_lazy_view_lifetime_churn_quick():
    """Tier-1 leg of the lazy-view lifetime drill (one seed, short).
    The floor is a LIVENESS bar (a wedged pipeline yields 0-1 batches
    on any box); the invariants are per-batch asserts + the final
    quiescent parity checkpoint inside the drill."""
    assert _lazy_view_churn(4.0, seed=17) >= 2


@pytest.mark.slow
@pytest.mark.parametrize("interval_s", [1e-6, 1e-5])
@pytest.mark.parametrize("seed", [11, 23, 47])
def test_lazy_view_lifetime_switch_sweep(interval_s, seed):
    """Nightly seeded schedule sweep over the lazy-view lifetime drill:
    pathological GIL handover points between resolve, churn, GC and
    consumption."""
    with switch_interval(interval_s):
        assert _lazy_view_churn(5.0, seed=seed) >= 2


@pytest.mark.slow
@pytest.mark.parametrize("interval_s", [1e-6, 1e-5, 1e-4])
def test_churn_switch_interval_sweep(interval_s):
    """The nightly thread-schedule sweep: the two-writer churn drill
    re-run under seeded switch intervals far below the 5ms default
    (1us/10us/100us), so the GIL hands over at pathological points —
    torn trie walks, observer re-entries, fold/rebuild interleavings the
    default schedule essentially never exercises. Each leg is a
    shortened copy of the main churn test: every batch served, final
    parity bit-identical under a writer pause."""
    index = TopicsIndex()
    seed = int(interval_s * 1e7) or 1
    r0 = random.Random(seed)
    for i in range(800):
        index.subscribe(f"base{i}", Subscription(filter=_rand_filter(r0), qos=i % 3))
    faulthandler.dump_traceback_later(110, exit=True)
    stop = threading.Event()
    errors: list = []

    def writer(wseed: int) -> None:
        r = random.Random(wseed)
        i = 0
        try:
            while not stop.is_set():
                flt = _rand_filter(r)
                if r.random() < 0.5:
                    index.subscribe(f"w{wseed}_{i}", Subscription(filter=flt, qos=1))
                else:
                    index.unsubscribe(flt, f"w{wseed}_{r.randint(0, max(1, i))}")
                i += 1
                time.sleep(0.0005)
        except Exception as e:  # pragma: no cover - the assertion target
            errors.append(e)

    try:
        with switch_interval(interval_s):
            m = DeltaMatcher(
                index, max_levels=4, rebuild_after=64, rebuild_interval=0.05,
                background=True,
            )
            writers = [
                threading.Thread(target=writer, args=(s,), daemon=True)
                for s in (seed + 1, seed + 2)
            ]
            for t in writers:
                t.start()
            r = random.Random(42)
            t_end = time.time() + 3.0
            batches = 0
            try:
                while time.time() < t_end:
                    topics = [_rand_topic(r) for _ in range(128)]
                    results = m.match_topics(topics)
                    assert len(results) == len(topics)
                    batches += 1
            finally:
                stop.set()
                for t in writers:
                    t.join(timeout=10)
            # final parity once the writers stopped (trie quiescent)
            m.flush()
            try:
                for topic in [_rand_topic(r) for _ in range(48)]:
                    assert canon(m.subscribers(topic)) == canon(
                        index.subscribers(topic)
                    ), topic
            finally:
                m.close()
    finally:
        # disarm even on a failed leg: a still-armed exit=True timer
        # would hard-kill the whole nightly session 110s later
        faulthandler.cancel_dump_traceback_later()
    assert not errors, errors
    assert batches >= 2, f"matcher starved under {interval_s}s switch interval"


@pytest.mark.slow
@pytest.mark.parametrize("interval_s", [1e-6, 1e-5, 1e-4])
def test_tree_epoch_race_sweep(interval_s):
    """Thread-schedule sweep over the spanning-tree state (ISSUE 9):
    concurrent ELECTION (adopt/propose from a gossip thread and a
    health-clock thread), HEAL (membership re-adds + duplicate-window
    traffic, the park-replay shape), and SUMMARY REFRESH (counted-bloom
    churn racing bits() exports) — the three mutation streams
    mqtt_tpu.cluster runs against one Topology. Invariants: the local
    tree is ALWAYS acyclic and spanning for the local view, a racing
    (origin, boot, seq) is claimed by EXACTLY one thread (the
    exactly-once heal guarantee), and the bloom converges to exactly the
    net interest set once the churn stops."""
    from mqtt_tpu.mesh_topology import (
        CountedBloom,
        DuplicateSuppressor,
        Topology,
        TreeEpoch,
        is_spanning_tree,
        tree_neighbors,
    )

    seed = int(interval_s * 1e7) or 1
    faulthandler.dump_traceback_later(110, exit=True)
    stop = threading.Event()
    errors: list = []

    topo = Topology(0, range(16), degree=3, boot_id=99)
    bloom = CountedBloom(1024)
    dup = DuplicateSuppressor(window=4096)
    claims: dict = {}  # (origin, boot, seq) -> claim count (must be 1)
    claims_lock = threading.Lock()

    def electioneer(eseed: int) -> None:
        """The gossip/health stream: adoptions, scoped removals,
        re-join proposals — every step must leave a spanning tree."""
        r = random.Random(eseed)
        try:
            while not stop.is_set():
                op = r.randrange(4)
                if op == 0:
                    topo.propose_remove(r.randrange(16))
                elif op == 1:
                    topo.propose_add(r.randrange(16), boot=r.randrange(4))
                elif op == 2:
                    members = {
                        w: r.randrange(4)
                        for w in r.sample(range(16), r.randint(1, 12))
                    }
                    topo.adopt(
                        TreeEpoch(
                            r.randint(0, 500), r.randrange(4), r.randrange(16)
                        ),
                        members,
                    )
                else:
                    topo.propose_self()
                parents, view = topo.parents(), topo.members()
                # snapshot consistency: both reads under the same lock
                # discipline — a torn pair would fail the validator
                if set(parents) == set(view):
                    assert is_spanning_tree(parents, view)
                time.sleep(0.0002)
        except Exception as e:  # pragma: no cover - the assertion target
            errors.append(e)

    def healer(hseed: int) -> None:
        """The heal stream: replayed (origin, boot, seq) triples racing
        the other healer for the same window slots — each triple must be
        claimed exactly once across BOTH threads."""
        r = random.Random(hseed)
        try:
            for i in range(4000):
                if stop.is_set():
                    break
                # half the space is shared with the other healer (the
                # re-parenting replay race), half is private traffic
                if r.random() < 0.5:
                    key = (1, 7, r.randrange(2000))
                else:
                    key = (hseed, 7, i)
                if not dup.seen(*key):
                    with claims_lock:
                        claims[key] = claims.get(key, 0) + 1
        except Exception as e:  # pragma: no cover
            errors.append(e)

    def refresher(rseed: int) -> None:
        """The summary stream: interest churn racing bits() exports;
        net-zero add/discard pairs must cancel exactly."""
        r = random.Random(rseed)
        try:
            while not stop.is_set():
                f = f"race/{r.randrange(32)}/x"
                bloom.add(f)
                bits = bloom.bits()  # the refresh export, mid-churn
                assert bits.might_match(f) or True  # must not raise
                bloom.discard(f)
                time.sleep(0.0001)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [
        threading.Thread(target=electioneer, args=(seed + 1,), daemon=True),
        threading.Thread(target=electioneer, args=(seed + 2,), daemon=True),
        threading.Thread(target=healer, args=(seed + 3,), daemon=True),
        threading.Thread(target=healer, args=(seed + 4,), daemon=True),
        threading.Thread(target=refresher, args=(seed + 5,), daemon=True),
    ]
    try:
        with switch_interval(interval_s):
            for t in threads:
                t.start()
            t_end = time.time() + 3.0
            while time.time() < t_end:
                # the forward path's reads, continuously: must never
                # raise and must always reflect a consistent tree
                n = topo.neighbors()
                assert 0 not in n
                topo.epoch_num()
                time.sleep(0.0005)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        faulthandler.cancel_dump_traceback_later()
    assert not errors, errors
    # exactly-once: no (origin, boot, seq) was claimed twice
    doubles = {k: v for k, v in claims.items() if v != 1}
    assert not doubles, doubles
    # quiescent convergence: the tree is spanning and neighbor reads
    # agree with a fresh recompute from the final view
    parents, view = topo.parents(), topo.members()
    assert is_spanning_tree(parents, view)
    assert set(topo.neighbors()) == set(tree_neighbors(parents, 0))
    # the bloom drained: every add was cancelled by its discard
    final = bloom.bits()
    assert not any(final.data), "counted bloom failed to drain to empty"


# -- graph-guided schedule fuzzing (ISSUE 10) --------------------------------
#
# The blunt setswitchinterval sweep above preempts EVERYWHERE; the lock
# graph says where preemption actually matters — the acquire/release
# boundaries of the staging/governor/breaker/cluster edge set. The
# PreemptionInjector (mqtt_tpu.utils.locked) yields the GIL at exactly
# those boundaries under a seeded, per-thread-deterministic schedule,
# and the session lock witness (armed in conftest) turns any
# inconsistent acquisition order the schedule provokes into a recorded
# cycle violation.

FUZZ_LOCKS = frozenset(
    {
        "overload_governor",
        "overload_peer_pressure",
        "matcher_breaker",
        "topics_trie",
        "cluster_remote_trie",
        "retained",
        "clients",
    }
)


def _fuzz_schedule(seed: int, ops_per_thread: int = 40) -> dict:
    """One fuzzed schedule: three deterministically-named threads drive
    seeded op scripts over the real broker control/data-plane objects
    (trie + retained store, remote trie, governor + peer signal,
    breaker, clients registry) while the injector preempts at the
    graph's lock boundaries. Returns the injector's per-thread decision
    logs. Asserts liveness (no deadlock: every thread joins) and that
    no thread raised."""
    from mqtt_tpu.clients import Clients
    from mqtt_tpu.overload import OverloadConfig, OverloadGovernor, PeerPressureSignal
    from mqtt_tpu.packets import Packet, Subscription as Sub
    from mqtt_tpu.resilience import CircuitBreaker
    from mqtt_tpu.utils.locked import DEFAULT_PLANE, PreemptionInjector

    index = TopicsIndex()
    remote = TopicsIndex(lock_name="cluster_remote_trie")
    gov = OverloadGovernor(OverloadConfig(eval_interval_s=0.0))
    gov.add_source("fuzz", lambda: 0.2)
    peers = PeerPressureSignal()
    breaker = CircuitBreaker(failure_threshold=3)
    clients = Clients()
    errors: list = []

    def script(tid: int) -> None:
        r = random.Random((seed << 4) | tid)
        try:
            for i in range(ops_per_thread):
                op = r.randrange(8)
                if op == 0:
                    index.subscribe(f"c{tid}_{i}", Sub(filter=_rand_filter(r), qos=1))
                elif op == 1:
                    pk = Packet()
                    pk.topic_name = f"f/{tid}/{r.randrange(8)}"
                    pk.payload = b"x"
                    pk.fixed_header.retain = True
                    index.retain_message(pk)
                elif op == 2:
                    remote.subscribe(f"r{tid}_{i}", Sub(filter=_rand_filter(r), qos=0))
                elif op == 3:
                    gov.evaluate(force=True)
                elif op == 4:
                    peers.observe(tid, r.randrange(3), r.random())
                    peers.value()
                elif op == 5:
                    if r.random() < 0.5:
                        breaker.record_failure("fuzz")
                    else:
                        breaker.record_success()
                    breaker.allow()
                elif op == 6:
                    clients.add(f"cl{tid}_{i % 4}", object())
                    clients.get(f"cl{tid}_{i % 4}")
                else:
                    index.subscribers(_rand_topic(r))
        except Exception as e:  # pragma: no cover - the assertion target
            errors.append(e)

    injector = PreemptionInjector(seed, rate=0.4, names=FUZZ_LOCKS)
    threads = [
        threading.Thread(
            target=script, args=(t,), daemon=True, name=f"fuzz-{t}"
        )
        for t in range(3)
    ]
    DEFAULT_PLANE.arm_fuzz(injector)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        DEFAULT_PLANE.disarm_fuzz()
    stuck = [t.name for t in threads if t.is_alive()]
    assert not stuck, f"deadlocked schedule seed={seed}: {stuck} never joined"
    assert not errors, errors
    return {
        name: ops
        for name, ops in injector.trace().items()
        if name.startswith("fuzz-")
    }


def test_schedule_fuzz_same_seed_same_schedule():
    """The determinism contract: two fresh runs of the same seed produce
    IDENTICAL per-thread decision logs (op index, lock name, phase,
    preempt verdict) — the property that makes a failing seed
    replayable."""
    a = _fuzz_schedule(1234)
    b = _fuzz_schedule(1234)
    assert set(a) == set(b) == {"fuzz-0", "fuzz-1", "fuzz-2"}
    for tname in a:
        assert a[tname] == b[tname], f"schedule diverged on {tname}"
    # and a different seed really produces a different schedule
    c = _fuzz_schedule(4321)
    assert any(a[t] != c[t] for t in a)


def test_schedule_fuzz_quick_sweep():
    """Tier-1 leg: a dozen seeded schedules over the hot edge set with
    zero deadlocks and zero witness violations."""
    from mqtt_tpu.utils.locked import DEFAULT_PLANE

    faulthandler.dump_traceback_later(110, exit=True)
    try:
        witness = DEFAULT_PLANE.witness
        before = len(witness.violations) if witness is not None else 0
        for seed in range(12):
            _fuzz_schedule(seed)
        if witness is not None:
            assert witness.violations[before:] == [], witness.violations[before:]
    finally:
        faulthandler.cancel_dump_traceback_later()


@pytest.mark.slow
def test_schedule_fuzz_200_schedules():
    """The chaos-smoke acceptance sweep (ISSUE 10): >= 200 seeded
    schedules over the staging/governor/breaker/cluster edge set, every
    one deadlock-free, with the session witness recording zero
    lock-order cycles across the entire sweep."""
    from mqtt_tpu.utils.locked import DEFAULT_PLANE, LockWitness

    faulthandler.dump_traceback_later(540, exit=True)
    witness = DEFAULT_PLANE.witness
    owned = witness is None
    if owned:
        witness = DEFAULT_PLANE.arm_witness()
    before = len(witness.violations)
    try:
        for seed in range(200):
            _fuzz_schedule(seed, ops_per_thread=30)
    finally:
        faulthandler.cancel_dump_traceback_later()
        if owned:
            DEFAULT_PLANE.disarm_witness()
    assert witness.violations[before:] == [], witness.violations[before:]


def test_fold_lock_order_regression():
    """The ops/delta.py contract: _rebuild_snapshot must never wrap a
    rebuild in the trie lock while a mutation holds it and waits on the
    rebuild mutex. Interleave explicit flushes with mutations from
    another thread; a lock-order inversion deadlocks (caught by the
    timeout marker)."""
    faulthandler.dump_traceback_later(55, exit=True)
    index = TopicsIndex()
    r = random.Random(3)
    for i in range(500):
        index.subscribe(f"c{i}", Subscription(filter=_rand_filter(r), qos=0))
    m = DeltaMatcher(index, max_levels=4, background=False)
    stop = threading.Event()

    def mutate() -> None:
        rr = random.Random(4)
        i = 0
        while not stop.is_set():
            index.subscribe(f"m{i}", Subscription(filter=_rand_filter(rr), qos=1))
            if i % 3 == 0:
                index.unsubscribe(_rand_filter(rr), f"m{rr.randint(0, i + 1)}")
            i += 1
            time.sleep(0.0002)

    th = threading.Thread(target=mutate, daemon=True)
    th.start()
    try:
        for _ in range(30):
            m.flush()  # synchronous rebuild/fold racing the mutator
            m.match_topics([_rand_topic(r) for _ in range(32)])
    finally:
        stop.set()
        th.join(timeout=10)
        m.close()
    faulthandler.cancel_dump_traceback_later()
    # final parity once the mutator stopped
    m.flush()
    for t in [_rand_topic(r) for _ in range(32)]:
        assert canon(m.subscribers(t)) == canon(index.subscribers(t)), t


# -- shard-fabric handoff drill (ISSUE 15) ----------------------------------


async def _shard_handoff_drill(seed: int, rounds: int = 3) -> None:
    """Seeded churn over the event-loop shard fabric: clients REUSING a
    small id pool connect, publish, and vanish abruptly while a stable
    subscriber counts deliveries — takeovers land on different shards
    (least-loaded dispatch over a moving population), disconnect/stop
    teardowns marshal cross-shard, and the count must come out exact.
    Deadlock shows up as the harness timeout killing the test."""
    import asyncio

    from mqtt_tpu.hooks.auth.allow_all import AllowHook
    from mqtt_tpu.listeners import Config as LConfig
    from mqtt_tpu.listeners.tcp import TCP
    from mqtt_tpu.server import Options, Server
    from tests.test_server import connect_packet, read_wire_packet, sub_packet, pub_packet

    r = random.Random(seed)
    srv = Server(Options(loop_shards=3, overload_control=False))
    srv.add_hook(AllowHook())
    srv.add_listener(TCP(LConfig(type="tcp", id="drill", address="127.0.0.1:0")))
    await srv.serve()
    port = int(srv.listeners.get("drill").address().rsplit(":", 1)[1])

    async def conn(cid):
        cr, cw = await asyncio.open_connection("127.0.0.1", port)
        cw.write(connect_packet(cid, 4))
        await cw.drain()
        ack = await asyncio.wait_for(read_wire_packet(cr, 4), 10)
        assert ack.fixed_header.type == 2  # CONNACK
        return cr, cw

    try:
        sub_r, sub_w = await conn("stable")
        sub_w.write(sub_packet(1, [Subscription(filter="r/#", qos=0)]))
        await sub_w.drain()
        await asyncio.wait_for(read_wire_packet(sub_r, 4), 10)

        from mqtt_tpu.stress import _scan_frames

        got = 0
        published = 0
        buf = bytearray()

        async def drain_subscriber():
            """Read until every published message arrived (QoS0 over
            loopback: exact, as long as no publisher dies mid-flight —
            rounds are sequential so takeovers only hit clients whose
            publishes were already delivered)."""
            nonlocal got
            deadline = time.monotonic() + 15
            while got < published and time.monotonic() < deadline:
                try:
                    data = await asyncio.wait_for(sub_r.read(65536), 0.5)
                except asyncio.TimeoutError:
                    continue
                if not data:
                    break
                buf.extend(data)
                frames, consumed = _scan_frames(buf)
                for first, _bs, _be in frames:
                    if (first >> 4) == 3:  # PUBLISH
                        got += 1
                del buf[:consumed]

        for rnd in range(rounds):
            async def churn(slot):
                nonlocal published
                # same id every round: round N+1's connect takes over
                # round N's lingering session, usually on a DIFFERENT
                # shard (least-loaded over a moving population)
                cr, cw = await conn(f"churn{slot}")
                n = r.randint(5, 20)
                for i in range(n):
                    cw.write(pub_packet(f"r/{slot}", b"p%d" % i))
                await cw.drain()
                published += n
                if slot % 2 == 0:
                    cw.close()  # half vanish abruptly; half linger

            await asyncio.gather(*(churn(s) for s in range(6)))
            await drain_subscriber()
            assert got == published, (
                f"round {rnd}: stable subscriber got {got}/{published}"
            )
        spread = srv._fabric.spread()
        assert sum(spread.values()) >= 1  # stable + lingerers still live
    finally:
        await asyncio.wait_for(srv.close(), 20)


def test_shard_handoff_drill_quick():
    import asyncio

    # a REAL deadline (pytest-timeout is not a dependency): a fabric
    # deadlock fails HERE in 60s with a traceback, not at the CI job cap
    asyncio.run(asyncio.wait_for(_shard_handoff_drill(seed=11), 60))


@pytest.mark.slow
@pytest.mark.parametrize("interval_s", [0.0005, 0.005])
@pytest.mark.parametrize("seed", [7, 23])
def test_shard_handoff_switch_sweep(interval_s, seed):
    import asyncio

    with switch_interval(interval_s):
        asyncio.run(
            asyncio.wait_for(_shard_handoff_drill(seed=seed, rounds=4), 120)
        )


# -- cross-shard handoff schedule fuzzing (ISSUE 19) -------------------------
#
# The lock-boundary fuzzer above shakes the THREADED edge set; this one
# shakes the LOOP-AFFINITY edge set: seeded publish/deliver/takeover
# traffic over a 3-shard fabric (publish lands on shard A, delivery
# marshals to the subscriber's shard B, a same-id reconnect takes the
# session over — usually onto shard C) while the PreemptionInjector
# yields at the graph's lock boundaries AND the session loop witness is
# ESCALATED to raising: any guarded touch a fuzzed schedule drives off
# its owning loop fails the run hard instead of rotting into the next
# hand-found cross-loop bug.


def _handoff_plan(seed: int, slots: int = 3) -> dict:
    """The pure seeded schedule plan — everything a round does derives
    from this (plus the equally seeded PreemptionInjector), which is
    what makes a failing seed replayable."""
    r = random.Random(seed ^ 0x5EAF)
    return {
        "publishes": [r.randint(2, 5) for _ in range(slots)],
        "qos": [r.choice([0, 1]) for _ in range(slots)],
        "takeover_order": r.sample(range(slots), slots),
        "vanish": [r.random() < 0.5 for _ in range(slots)],
    }


class _HandoffRig:
    """One 3-shard broker + one stable cross-shard subscriber, shared
    across a whole sweep so a 200-seed schedule run is dominated by the
    schedules, not by server setup."""

    def __init__(self):
        self.published = 0
        self.got = 0
        self._buf = bytearray()

    async def start(self):
        import asyncio

        from mqtt_tpu.hooks.auth.allow_all import AllowHook
        from mqtt_tpu.listeners import Config as LConfig
        from mqtt_tpu.listeners.tcp import TCP
        from mqtt_tpu.server import Options, Server
        from tests.test_server import read_wire_packet, sub_packet

        self.srv = Server(Options(loop_shards=3, overload_control=False))
        self.srv.add_hook(AllowHook())
        self.srv.add_listener(
            TCP(LConfig(type="tcp", id="hand", address="127.0.0.1:0"))
        )
        await self.srv.serve()
        self.port = int(
            self.srv.listeners.get("hand").address().rsplit(":", 1)[1]
        )
        # the writer is HELD: Python 3.12's StreamWriter.__del__ closes a
        # dropped writer, which would disconnect the stable subscriber
        self.sub_r, self._sub_w = sub_r, sub_w = await self.conn("hand-stable")
        sub_w.write(sub_packet(1, [Subscription(filter="hz/#", qos=0)]))
        await sub_w.drain()
        await asyncio.wait_for(read_wire_packet(self.sub_r, 4), 10)
        return self

    async def conn(self, cid):
        import asyncio

        from tests.test_server import connect_packet, read_wire_packet

        cr, cw = await asyncio.open_connection("127.0.0.1", self.port)
        cw.write(connect_packet(cid, 4))
        await cw.drain()
        ack = await asyncio.wait_for(read_wire_packet(cr, 4), 10)
        assert ack.fixed_header.type == 2  # CONNACK
        return cr, cw

    async def drain(self, deadline_s: float = 15.0):
        """Count PUBLISH frames on the stable subscriber until the
        published total is accounted for (QoS0 over loopback: exact)."""
        import asyncio

        from mqtt_tpu.stress import _scan_frames

        deadline = time.monotonic() + deadline_s
        while self.got < self.published and time.monotonic() < deadline:
            try:
                data = await asyncio.wait_for(self.sub_r.read(65536), 0.5)
            except asyncio.TimeoutError:
                continue
            if not data:
                break
            self._buf.extend(data)
            frames, consumed = _scan_frames(self._buf)
            for first, _bs, _be in frames:
                if (first >> 4) == 3:  # PUBLISH
                    self.got += 1
            del self._buf[:consumed]
        assert self.got == self.published, (
            f"stable subscriber got {self.got}/{self.published}"
        )

    async def round(self, seed: int):
        """One seeded schedule: publish from fresh clients (shard A ->
        subscriber's shard B), drain exact, then take every session
        over by id reuse (-> shard C under least-loaded dispatch) and
        publish once more through the taken-over sessions."""
        import asyncio

        from mqtt_tpu.utils.locked import DEFAULT_PLANE, PreemptionInjector
        from tests.test_server import pub_packet

        plan = _handoff_plan(seed)
        injector = PreemptionInjector(seed, rate=0.3, names=FUZZ_LOCKS)
        DEFAULT_PLANE.arm_fuzz(injector)
        try:
            for slot, n in enumerate(plan["publishes"]):
                _cr, cw = await self.conn(f"hz{seed}x{slot}")
                for i in range(n):
                    if plan["qos"][slot]:
                        cw.write(
                            pub_packet(
                                f"hz/{seed}/{slot}", b"p%d" % i,
                                qos=1, pid=100 + i,
                            )
                        )
                    else:
                        cw.write(pub_packet(f"hz/{seed}/{slot}", b"p%d" % i))
                await cw.drain()
                self.published += n
            await self.drain()
            for slot in plan["takeover_order"]:
                _cr, cw = await self.conn(f"hz{seed}x{slot}")
                cw.write(pub_packet(f"hz/{seed}/t{slot}", b"t"))
                await cw.drain()
                self.published += 1
                if plan["vanish"][slot]:
                    cw.close()  # half vanish abruptly; half linger
            await self.drain()
        finally:
            DEFAULT_PLANE.disarm_fuzz()

    async def stop(self):
        import asyncio

        await asyncio.wait_for(self.srv.close(), 20)


def _run_handoff_sweep(seeds, deadline_s: float) -> None:
    """The sweep harness: one rig, seeded rounds, the session loop
    witness escalated to RAISING for the duration (escalate-only arm;
    the recording default is restored by attribute, mirroring how the
    lock fuzzer treats the session lock witness)."""
    import asyncio

    from mqtt_tpu.utils.loopwitness import DEFAULT_LOOP_PLANE

    witness = DEFAULT_LOOP_PLANE.arm_witness()
    prev_raise = witness.raise_on_violation
    before = len(witness.violations)
    witness.raise_on_violation = True
    faulthandler.dump_traceback_later(int(deadline_s), exit=True)
    try:

        async def sweep():
            rig = await _HandoffRig().start()
            try:
                for seed in seeds:
                    await rig.round(seed)
            finally:
                await rig.stop()

        asyncio.run(asyncio.wait_for(sweep(), deadline_s - 5))
    finally:
        faulthandler.cancel_dump_traceback_later()
        witness.raise_on_violation = prev_raise
    assert witness.violations[before:] == [], witness.violations[before:]


def test_handoff_fuzz_same_seed_is_deterministic():
    """The replayability contract: the WHOLE schedule derives from the
    seed — the op plan here, the preemption decisions in the (already
    covered) per-thread-deterministic injector — so a failing seed
    re-runs as the same schedule."""
    assert _handoff_plan(77) == _handoff_plan(77)
    assert _handoff_plan(77) != _handoff_plan(78)


def test_handoff_fuzz_quick_sweep():
    """Tier-1 leg: 12 seeded publish/deliver/takeover schedules across
    the 3-shard fabric with the loop witness raising — zero affinity
    violations, zero lost deliveries, zero deadlocks."""
    _run_handoff_sweep(range(12), deadline_s=110)


@pytest.mark.slow
def test_handoff_fuzz_200_schedules():
    """The chaos-smoke acceptance sweep (ISSUE 19): >= 200 seeded
    cross-shard handoff schedules under the raising loop witness."""
    _run_handoff_sweep(range(200), deadline_s=540)


def test_bulk_loads_race_live_churn_and_matching():
    """Three loaders open overlapping bulk loads (``bulk_depth`` up to 3,
    from different threads) while a writer churns live subscriptions and
    this thread matches, all under a 100 us switch interval: the hold's
    shared state (``_Gen.held``, the depth, the wake) must lose nothing.
    When the last load has closed the overlay drains on its own and the
    table equals the trie."""
    from mqtt_tpu.staging import bulk_register

    index = TopicsIndex()
    faulthandler.dump_traceback_later(110, exit=True)
    m = DeltaMatcher(
        index, max_levels=4, rebuild_after=64, rebuild_interval=0.02, background=True
    )
    stop = threading.Event()
    errors: list = []
    loaded = [0, 0, 0]

    def loader(k: int) -> None:
        r = random.Random(100 + k)

        def entries(n):
            for i in range(n):
                if i % 64 == 0:
                    time.sleep(0.002)  # leave the lock to the other threads
                yield f"l{k}_{loaded[k] + i}", Subscription(filter=_rand_filter(r), qos=i % 3)

        try:
            for _ in range(8):
                n = r.randint(50, 300)
                bulk_register(index, entries(n), batch=64)
                loaded[k] += n
                time.sleep(0.01 * r.random())
        except Exception as e:  # pragma: no cover - the assertion target
            errors.append(e)

    def writer() -> None:
        r = random.Random(9)
        i = 0
        try:
            while not stop.is_set():
                flt = _rand_filter(r)
                if r.random() < 0.6:
                    index.subscribe(f"w{i}", Subscription(filter=flt, qos=1))
                else:
                    index.unsubscribe(flt, f"w{r.randint(0, max(1, i))}")
                i += 1
                time.sleep(0.0005)
        except Exception as e:  # pragma: no cover - the assertion target
            errors.append(e)

    threads = [threading.Thread(target=loader, args=(k,), daemon=True) for k in range(3)]
    threads.append(threading.Thread(target=writer, daemon=True))
    r = random.Random(42)
    batches = 0
    try:
        with switch_interval(1e-4):
            for t in threads:
                t.start()
            t_end = time.time() + 30.0
            while any(t.is_alive() for t in threads[:3]) and time.time() < t_end:
                topics = [_rand_topic(r) for _ in range(64)]
                assert len(m.match_topics(topics)) == len(topics)
                batches += 1
            stop.set()
            for t in threads:
                t.join(30)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors
        assert batches >= 2 and sum(loaded) > 0
        assert index.bulk_depth == 0
        # nobody flushes: the last close (or the tick after the churn's
        # last mutation) wakes the thread, and the overlay drains
        deadline = time.time() + 30
        while m.pending_deltas and time.time() < deadline:
            time.sleep(0.02)
        assert m.pending_deltas == 0 and m._gen.held == 0
        assert m.rebuild_errors == 0
        assert m.stats.bulk_loads >= 1
        # every loaded client is in the trie exactly once
        r2 = random.Random(7)
        topics = [_rand_topic(r2) for _ in range(512)]
        st = m.stats
        before = (st.host_fallbacks, st.overflows)
        for topic, got in zip(topics, m.match_topics(topics)):
            assert canon(got) == canon(index.subscribers(topic)), topic
        # answered from the table: no topic was routed by the overlay
        assert st.host_fallbacks - before[0] == st.overflows - before[1]
    finally:
        stop.set()
        faulthandler.cancel_dump_traceback_later()
        m.close()
