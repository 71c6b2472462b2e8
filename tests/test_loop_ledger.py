"""The loop's ledger (mqtt_tpu.tracing): while a profiler session is live a
timing frame stands around the event loop's selector and books every
iteration and every ``select()``; reads, sends and collections of every
generation are counted where they happen; and none of it changes what the
broker does. CPU backend throughout: boundaries and counts, never a rate."""

import asyncio
import gc
import time

import pytest

from mqtt_tpu import tracing
from mqtt_tpu.server import Server
from mqtt_tpu.tracing import GC2, DeviceProfiler, Gen2Pauses, _LoopFrame

from tests import test_ack_run, test_ingest_run
from tests.test_batch_completion import staged_options, subscriber
from tests.test_server import Harness, pub_packet, run

MS = 1_000_000
FRAME_KEYS = (
    "poll0_n", "poll0_ns", "pollw_n", "pollw_ns",
    "poll_ready_n", "iter_busy_ns", "stall",
)


@pytest.fixture(autouse=True)
def no_slice(monkeypatch):
    """``last_slice()`` is process-wide: every test starts without one."""
    monkeypatch.setattr(tracing, "_LAST_SLICE", None)


def switched(prof) -> list:
    """A profiler that follows ``on[0]`` in place of a jax session (the
    annotations it enters then record nowhere)."""
    on = [False]
    prof._is_enabled = lambda: on[0]
    return on


def parts(sl) -> int:
    return sum(sl.b[k] - sl.a[k] for k in ("iter_busy_ns", "poll0_ns", "pollw_ns"))


# -- the frame, against a clock and a selector that are scripted -----------------


class Clock:
    """``tracing.time`` for the length of a test: a clock that moves only
    when told to."""

    def __init__(self, now=1_000 * MS):
        self.now = now

    def perf_counter_ns(self):
        return self.now

    process_time_ns = staticmethod(time.process_time_ns)  # a snapshot reads it


class ScriptedSelector:
    """Every ``select()`` takes what the script says and returns that
    many events; ``registered`` shows what the proxy forwards."""

    def __init__(self, clock, script):
        self.clock, self.script, self.registered = clock, list(script), []

    def select(self, timeout=None):
        took_ns, n_events = self.script.pop(0)
        self.clock.now += took_ns
        return [object()] * n_events

    def register(self, fd, events, data=None):
        self.registered.append(fd)

    def unregister(self, fd):
        self.registered.remove(fd)

    def modify(self, *a):
        pass

    def get_key(self, fd):
        return fd

    def get_map(self):
        return {fd: fd for fd in self.registered}

    def close(self):
        self.closed = True

    extra = "forwarded"


class NoSpans:
    """Stands in for ``jax.profiler.TraceAnnotation``: remembers names."""

    def __init__(self):
        self.entered = []

    def __call__(self, name, **args):
        self.entered.append((name, args))
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def scripted(monkeypatch):
    clock = Clock()
    monkeypatch.setattr(tracing, "time", clock)
    prof = DeviceProfiler()
    spans = prof._annotation = NoSpans()
    prof._clock_ns = clock.now  # as arming leaves it: no mark is due yet
    return clock, prof, spans


def test_every_select_lands_in_its_own_pair_and_the_parts_close(scripted):
    clock, prof, spans = scripted
    t_a = clock.now
    sel = ScriptedSelector(clock, [(7_000, 0), (40 * MS, 3), (5_000, 2), (9 * MS, 0)])
    frame = _LoopFrame(sel, prof, t_a, frame_phases(prof))
    clock.now += 2 * MS  # the iteration the arming was queued in
    assert frame.select(0) == []
    clock.now += 3 * MS
    assert len(frame.select(0.25)) == 3  # could block: idle time
    clock.now += 11 * MS
    assert len(frame.select(0)) == 2
    clock.now += 1 * MS
    assert frame.select(None) == []  # no timeout at all can block too
    assert (prof.poll0_n, prof.poll0_ns) == (2, 12_000)
    assert (prof.pollw_n, prof.pollw_ns) == (2, 49 * MS)
    assert prof.poll_ready_n == 5
    assert prof.iter_busy_ns == (2 + 3 + 11 + 1) * MS
    assert prof.iter_busy_ns + prof.poll0_ns + prof.pollw_ns == clock.now - t_a
    # only a select() that could block is an idle span on the profiler's clock
    assert spans.entered == [("mqtt/loop.idle", {})] * 2
    # the proxy forwards what it does not time
    frame.register(5, 1)
    assert sel.registered == [5] and frame.get_map() == {5: 5}
    assert frame.get_key(5) == 5 and frame.extra == "forwarded"
    frame.unregister(5)
    assert sel.registered == []


def frame_phases(prof) -> tuple:
    return (
        prof.ingest_busy_ns, prof.ack_busy_ns, prof.fanout_busy_ns,
        prof.slice_flush_ns, prof.send_busy_ns, GC2.pause_ns_total,
        prof.housekeep_busy_ns, prof.sys_publish_busy_ns,
    )


def test_the_stall_is_the_longest_iteration_with_the_phases_inside_it(
    scripted, monkeypatch
):
    clock, prof, _spans = scripted
    monkeypatch.setattr(tracing, "GC2", Gen2Pauses())  # no real collection lands here
    sel = ScriptedSelector(clock, [(1_000, 1)] * 4)
    frame = _LoopFrame(sel, prof, clock.now, frame_phases(prof))
    # a 10 ms iteration that was all fan-out, 4 ms of it the slice's flush
    clock.now += 10 * MS
    prof.note_fanout(clock.now - 10 * MS, clock.now - 10 * MS, clock.now - 4 * MS)
    prof.note_slice_flush(4 * MS)
    frame.select(0)
    assert prof.stall["busy_ns"] == 10 * MS and prof.stall["fanout_ns"] == 10 * MS
    assert prof.stall["flush_ns"] == 4 * MS and prof.stall["gc_gen"] == -1
    # a 50 ms one: a read's 30 ms of ingest (2 ms of it a send, 5 ms a
    # young collection), the rest unnamed
    t0 = clock.now
    clock.now += 12 * MS
    tracing.GC2._on_gc("start", {"generation": 1})
    clock.now += 5 * MS
    tracing.GC2._on_gc("stop", {"generation": 1})
    prof.send_busy_ns += 2 * MS
    clock.now += 13 * MS
    prof.note_ingest(30 * MS, 64)
    clock.now += 20 * MS
    frame.select(0)
    assert prof.stall == {
        "t0_ns": t0, "busy_ns": 50 * MS, "ingest_ns": 30 * MS, "ack_ns": 0,
        "fanout_ns": 0, "flush_ns": 0, "send_ns": 2 * MS, "gc_ns": 5 * MS,
        "housekeep_ns": 0, "sys_publish_ns": 0,
        "gc_gen": 1,
    }
    rest = prof.stall["busy_ns"] - prof.stall["ingest_ns"] - prof.stall["fanout_ns"]
    assert rest == 20 * MS
    # a shorter one after it (acks) leaves the record alone
    clock.now += 49 * MS
    prof.note_acks(49 * MS, 1000)
    frame.select(0)
    assert prof.stall["busy_ns"] == 50 * MS and prof.stall["ack_ns"] == 0
    assert prof.iter_busy_ns == (10 + 50 + 49) * MS


# -- on a real selector loop ----------------------------------------------------


def test_a_real_loops_ledger_closes_and_the_proxy_is_gone_after():
    """Known sleeps on an ``asyncio`` selector loop: blocking polls, polls
    that cannot block, one long iteration with a stretch of ingest in it;
    the parts come to B − A, and ``_selector`` is the loop's own again."""
    prof = DeviceProfiler()
    prof._annotation = NoSpans()
    on = switched(prof)
    seen = {}

    def long_iteration():
        time.sleep(0.03)
        prof.note_ingest(30 * MS, 8)
        time.sleep(0.02)

    async def scenario():
        loop = prof.loop = asyncio.get_running_loop()
        own = loop._selector
        on[0] = True
        assert prof.poll() and loop._selector is own  # stood by a callback
        await asyncio.sleep(0)
        assert isinstance(loop._selector, _LoopFrame) and loop._selector._sel is own
        await asyncio.sleep(0.04)  # the loop idles: no heartbeat runs through it
        for _ in range(25):
            await asyncio.sleep(0)  # the ready queue is never empty
        loop.call_soon(long_iteration)
        await asyncio.sleep(0.01)
        seen["armed"] = (prof.poll0_n, prof.pollw_n)
        on[0] = False
        assert not prof.poll() and isinstance(loop._selector, _LoopFrame)
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        assert loop._selector is own and prof._frame is None
        seen["after"] = (prof.poll0_n, prof.pollw_n)
        await asyncio.sleep(0.01)
        assert (prof.poll0_n, prof.pollw_n) == seen["after"]  # nothing books now

    run(scenario())
    sl = tracing.last_slice()
    assert all(k in sl.a and k in sl.b for k in FRAME_KEYS)
    wall = sl.b["t_ns"] - sl.a["t_ns"]
    assert wall > 85 * MS
    assert abs(parts(sl) - wall) <= wall // 100
    poll0, pollw = seen["armed"]
    assert poll0 >= 25 and 1 <= pollw <= 4  # the sleeps, and no 5 ms beat
    # the loop's longest hold and its beats are the ledger's own
    assert prof._beats == 0
    assert sl.b["loop_beats"] - sl.a["loop_beats"] == sum(
        sl.b[k] - sl.a[k] for k in ("poll0_n", "pollw_n")
    ) >= poll0 + pollw
    assert sl.b["loop_stall_max_ns"] == sl.b["stall"]["busy_ns"]
    assert sl.a["loop_stall_max_ns"] == 0
    idle = sl.b["pollw_ns"] - sl.a["pollw_ns"]
    assert 30 * MS <= idle <= wall - 50 * MS
    assert (sl.b["poll0_ns"] - sl.a["poll0_ns"]) / poll0 < MS
    stall = sl.b["stall"]
    assert 50 * MS <= stall["busy_ns"] <= 80 * MS
    assert stall["ingest_ns"] == 30 * MS and stall["fanout_ns"] == 0
    assert 20 * MS <= stall["busy_ns"] - stall["ingest_ns"] <= 50 * MS
    assert sl.a["t_ns"] <= stall["t0_ns"] <= sl.b["t_ns"]
    assert sl.a["stall"] is None  # every slice starts its own record


class NoSelectorLoop:
    """A loop of another make (uvloop, proactor): nothing to frame."""

    def __init__(self):
        self.calls = []

    def call_soon_threadsafe(self, fn, *args):
        self.calls.append(fn.__name__)

    def call_later(self, delay, fn):
        pass


def test_a_loop_without_a_selector_leaves_the_ledger_out():
    prof = DeviceProfiler()
    on = switched(prof)
    loop = prof.loop = NoSelectorLoop()
    on[0] = True
    prof.poll()
    assert loop.calls == ["_beat"]  # the heartbeat, and no frame
    prof._beat()
    prof._beat()
    on[0] = False
    prof.poll()
    sl = tracing.last_slice()
    assert not any(k in sl.a or k in sl.b for k in FRAME_KEYS)
    # what needs no frame is there all the same
    for key in ("send_busy_ns", "slice_flush_ns", "gc_pause_ns", "young_recent"):
        assert key in sl.a and key in sl.b
    # and the loop's hold is the heartbeat's
    assert sl.b["loop_beats"] - sl.a["loop_beats"] == 2
    assert sl.b["loop_stall_max_ns"] == prof.loop_stall_max_ns


def test_a_snapshot_off_the_loop_counts_the_iteration_in_progress(scripted):
    """B is taken from another thread while the loop works or waits: what
    the open iteration (or ``select()``) has run so far is in it."""
    clock, prof, _spans = scripted
    prof._framed = True
    frame = prof._frame = _LoopFrame(
        ScriptedSelector(clock, [(1 * MS, 0)]), prof, clock.now, (0,) * 8
    )
    clock.now += 8 * MS
    ledger = prof._ledger()
    assert ledger["iter_busy_ns"] == 8 * MS and prof.iter_busy_ns == 0
    assert ledger["loop_stall_max_ns"] == 8 * MS  # a hold in progress counts
    frame.select(0.1)
    frame.mark = -(clock.now - 3 * MS)  # as inside a select() since 3 ms
    ledger = prof._ledger()
    assert ledger["pollw_ns"] == 1 * MS + 3 * MS and ledger["iter_busy_ns"] == 8 * MS
    assert ledger["loop_stall_max_ns"] == 8 * MS and ledger["loop_beats"] == 1


# -- reads and sends, where they happen ------------------------------------------


def test_reads_are_counted_a_wake_up_on_data_and_sends_timed_only_while_armed():
    async def scenario():
        h = Harness(staged_options())
        srv = h.server
        await srv.serve()
        ops, prof = srv._ops, srv.profiler
        on = switched(prof)
        sub_r, _w = await subscriber(h, "sub", "t/#")
        reader, writer, task = await h.connect("pub")
        reads = ops.socket_reads
        delivery = pub_packet("t/1", b"x")

        async def publish_qos1(pid):
            sends = ops.socket_sends
            frame = pub_packet("t/1", b"x", qos=1, pid=pid)
            writer.write(frame)
            assert await asyncio.wait_for(reader.readexactly(4), 5) == bytes(
                (0x40, 2, 0, pid)
            )
            assert await asyncio.wait_for(
                sub_r.readexactly(len(delivery)), 5
            ) == delivery
            return ops.socket_sends - sends

        sent = await publish_qos1(1)
        assert sent == 2  # the ack, and the delivery to ``sub``
        assert ops.socket_reads - reads == 1
        assert prof.send_busy_ns == 0 and not prof.armed  # untimed
        on[0] = True
        prof.poll()
        sent_armed = await publish_qos1(2)
        assert sent_armed == sent and prof.send_busy_ns > 0
        assert ops.socket_reads - reads == 2
        on[0] = False
        prof.poll()
        busy = prof.send_busy_ns
        await publish_qos1(3)
        assert prof.send_busy_ns == busy
        # end of file is a wake-up that brought nothing
        reads = ops.socket_reads
        writer.close()
        await asyncio.wait_for(task, 5)
        assert ops.socket_reads == reads
        sl = tracing.last_slice()
        assert sl.b["socket_reads"] - sl.a["socket_reads"] == 1
        assert sl.b["send_busy_ns"] - sl.a["send_busy_ns"] == busy
        text = srv.telemetry.registry.exposition()
        assert f"mqtt_tpu_socket_reads_total {ops.socket_reads}" in text
        assert f"mqtt_tpu_socket_sends_total {ops.socket_sends}" in text
        await srv.close()
        await h.shutdown()

    run(scenario())


# -- collections of every generation -----------------------------------------------


def test_one_hook_books_every_generation_and_full_ones_as_before(monkeypatch):
    DeviceProfiler()  # the first one installs the hook
    assert gc.callbacks.count(GC2._on_gc) == 1
    ns = list(GC2.gc_pause_ns)
    full, young = len(GC2.recent), len(GC2.young_recent)
    total = GC2.pause_ns_total
    gc.collect(0)
    assert GC2.gc_pause_ns[0] > ns[0] and GC2.gc_pause_ns[1:] == ns[1:]
    assert GC2.pause_ns_total - total == GC2.gc_pause_ns[0] - ns[0]
    assert len(GC2.recent) == full  # full collections only, as before
    gc.collect()
    assert GC2.gc_pause_ns[2] > ns[2] and len(GC2.recent) == min(64, full + 1)
    assert GC2.recent[-1][1] == GC2.gc_pause_ns[2] - ns[2]
    assert GC2.last_end_ns[2] == GC2.recent[-1][0] > GC2.last_end_ns[0]
    assert len(GC2.young_recent) in (young, young + 1)  # over 1 ms only
    # against a stubbed clock: a young one of 3 ms is kept, one of 1 ms not
    clock = Clock()
    monkeypatch.setattr(tracing, "time", clock)
    hook = Gen2Pauses()
    for gen, took in ((0, 1 * MS), (1, 3 * MS), (0, 2 * MS), (2, 7 * MS)):
        hook._on_gc("start", {"generation": gen})
        clock.now += took
        hook._on_gc("stop", {"generation": gen})
        clock.now += 10 * MS
    end_1 = 1_000 * MS + 11 * MS + 3 * MS
    assert list(hook.young_recent) == [(end_1, 3 * MS, 1), (end_1 + 12 * MS, 2 * MS, 0)]
    assert [ns for _end, ns in hook.recent] == [7 * MS]
    assert hook.gc_pause_ns == [3 * MS, 3 * MS, 7 * MS]
    assert hook.pause_ns_total == 13 * MS and hook.hist.count == 1


def test_the_heartbeat_leaves_a_clock_mark_a_second(scripted):
    clock, prof, spans = scripted
    prof.loop = NoSelectorLoop()
    on = switched(prof)
    on[0] = True
    prof.poll()
    t_arm = clock.now
    for step in (5 * MS, 700 * MS, 400 * MS, 5 * MS, 1_300 * MS):
        clock.now += step
        prof._beat()  # as the loop would run it: late, when it is held
    marks = [args["perf_ns"] for name, args in spans.entered if name == "mqtt/clock"]
    assert marks == [t_arm, t_arm + 1_105 * MS, t_arm + 2_410 * MS]
    on[0] = False
    prof.poll()
    assert [name for name, _ in spans.entered].count("mqtt/clock") == 3  # none at the end


def test_a_framed_loop_leaves_a_clock_mark_a_second_at_its_turns(scripted):
    clock, prof, spans = scripted
    prof._clock_mark()
    t_arm = clock.now
    steps = (300 * MS, 600 * MS, 200 * MS, 5 * MS, 1_300 * MS)
    sel = ScriptedSelector(clock, [(s, 0) for s in steps])
    frame = _LoopFrame(sel, prof, clock.now, frame_phases(prof))
    for _ in steps:
        frame.select(None)  # a mark is left at the return that finds one due
    marks = [args["perf_ns"] for name, args in spans.entered if name == "mqtt/clock"]
    assert marks == [t_arm, t_arm + 1_100 * MS, t_arm + 2_405 * MS]


def test_a_collection_is_an_annotation_while_a_session_is_live():
    prof = DeviceProfiler()
    on = switched(prof)
    spans = prof._annotation = NoSpans()
    on[0] = True
    prof.poll()
    assert spans.entered[0][0] == "mqtt/clock" and spans.entered[0][1]["perf_ns"] > 0
    gc.collect(1)
    assert ("mqtt/gc", {"gen": 1}) in spans.entered
    on[0] = False
    prof.poll()
    count = len(spans.entered)
    gc.collect(1)
    assert len(spans.entered) == count and GC2.annotate is None
    sl = tracing.last_slice()
    assert sl.b["gc_pause_ns"][1] > sl.a["gc_pause_ns"][1]
    assert sl.young_pauses() == [
        p for p in sl.b["young_recent"] if sl.a["t_ns"] < p[0] <= sl.b["t_ns"]
    ]


# -- armed or not, the broker does the same --------------------------------------


@pytest.fixture
def arm_at_serve(monkeypatch):
    """Every broker served inside the test is armed from ``serve()`` on,
    its loop framed (a broker with no device matcher gets a profiler for
    the purpose); ``made`` collects the profilers."""
    made = []
    serve = Server.serve

    async def serve_armed(srv):
        await serve(srv)
        prof = srv.profiler
        if prof is None:
            prof = srv.profiler = srv._ops.profiler = DeviceProfiler()
            prof.loop = asyncio.get_running_loop()
        prof._is_enabled = lambda: True
        assert prof.poll()
        made.append(prof)

    def arm(on: bool):
        if on:
            monkeypatch.setattr(Server, "serve", serve_armed)
        else:
            monkeypatch.setattr(Server, "serve", serve)

    return arm, made


def views(seen: dict) -> dict:
    """``seen`` with its parked packets as a reader sees them (a clock
    compares by identity)."""
    parked = seen.pop("parked", None)
    if parked is not None:
        seen["parked"] = [pk for _, pk, _ in parked]
        seen["parked_views"] = test_ingest_run.packet_views(parked)
    return seen


@pytest.mark.parametrize("name", sorted(test_ingest_run.CASES))
def test_an_armed_ingest_delivers_what_a_disarmed_one_does(
    name, arm_at_serve, monkeypatch
):
    monkeypatch.setattr("mqtt_tpu.server.time.time", lambda: test_ingest_run.NOW)
    arm, made = arm_at_serve
    case = test_ingest_run.CASES[name]
    plain = views(test_ingest_run.observe(case, shut=False))
    arm(True)
    armed = views(test_ingest_run.observe(case, shut=False))
    assert armed == plain
    (prof,) = made
    assert prof.armed and prof._frame is not None and prof.pollw_n > 0
    # a scan that raises books nothing (its span is left all the same)
    assert prof.ingest_n <= plain["pub_count"]
    assert prof.ingest_n == plain["pub_count"] or "None" not in plain["stop_cause"]


@pytest.mark.parametrize("name", sorted(test_ack_run.CASES))
def test_an_armed_ack_path_delivers_what_a_disarmed_one_does(name, arm_at_serve):
    arm, made = arm_at_serve
    case = test_ack_run.CASES[name]
    plain = test_ack_run.observe(case, None)
    arm(True)
    armed = test_ack_run.observe(case, None)
    assert armed == plain
    (prof,) = made
    assert prof.armed and prof.pollw_n > 0
    assert prof.ack_n + prof.ingest_n > 0
