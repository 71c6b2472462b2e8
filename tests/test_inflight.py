"""``Inflight.begin``: one outbound QoS>0 delivery's bookkeeping (the cap
on entries, the packet-id walk, the store, the send quota and its park)
under ONE acquisition of the session's inflight lock, against the three
calls it replaced (``len``, ``Client.next_packet_id``'s walk of ``get``
calls, ``set`` + ``decrease_send_quota``), written out below as the
oracle; and ``Inflight.begin_run``, a run of entries under one
acquisition, against ``begin`` called once an entry."""

import random

import pytest

from mqtt_tpu.clients import ClientState
from mqtt_tpu.inflight import (
    BEGIN_EXHAUSTED,
    BEGIN_FULL,
    BEGIN_PARKED,
    Inflight,
)
from mqtt_tpu.packets import PUBLISH, FixedHeader, Packet
from mqtt_tpu.utils.locked import DEFAULT_PLANE


def entry(pid=0):
    return Packet(fixed_header=FixedHeader(type=PUBLISH, qos=1), packet_id=pid)


def three_calls(state, pk, cap, maximum):
    """The bookkeeping as three calls on the map took it: what it
    answered ("full", "exhausted", ("parked", pid) or pid)."""
    inflight = state.inflight
    if len(inflight) >= cap:
        return "full"
    i = state.packet_id
    started, overflowed = i, False
    while True:
        if overflowed and i == started:
            return "exhausted"
        if i >= maximum:
            overflowed = True
            i = 0
            continue
        i += 1
        if inflight.get(i & 0xFFFF) is None:
            break
    state.packet_id = i
    pk.packet_id = i & 0xFFFF
    sent_quota = inflight.send_quota
    if inflight.set(pk):
        inflight.decrease_send_quota()
    if sent_quota == 0 and inflight.maximum_send_quota > 0:
        return ("parked", pk.packet_id)
    return pk.packet_id


def as_oracle(code, pk):
    if code == BEGIN_FULL:
        return "full"
    if code == BEGIN_EXHAUSTED:
        return "exhausted"
    if code == BEGIN_PARKED:
        return ("parked", pk.packet_id)
    return code


def session(rng, maximum):
    st = ClientState(0, 16)
    for pid in rng.sample(range(1, maximum + 1), rng.randint(0, maximum)):
        st.inflight.set(entry(pid))
    st.packet_id = rng.randint(0, maximum)
    st.inflight.reset_send_quota(rng.choice([0, 1, 3, 20]))
    st.inflight.send_quota = rng.randint(0, st.inflight.maximum_send_quota)
    return st


def snapshot(st):
    inf = st.inflight
    return (
        sorted(inf.internal), st.packet_id, inf.send_quota, inf.maximum_send_quota
    )


@pytest.mark.parametrize("seed", range(6))
def test_begin_answers_as_the_three_calls_did(seed):
    """Random sessions (ids in flight, cursor, quota, its maximum) at a
    small id space, so that walks wrap and run out: the same answer, the
    same map, cursor and quota after every delivery of a run."""
    rng = random.Random(seed)
    seen = set()
    for _ in range(60):
        maximum = rng.randint(1, 8)
        cap = rng.randint(1, 10)
        a = session(rng, maximum)
        b = ClientState(0, 16)
        for pid in a.inflight.internal:
            b.inflight.set(entry(pid))
        b.packet_id = a.packet_id
        b.inflight.reset_send_quota(a.inflight.maximum_send_quota)
        b.inflight.send_quota = a.inflight.send_quota
        for _ in range(rng.randint(1, 5)):
            pa, pb = entry(), entry()
            got = as_oracle(a.inflight.begin(a, pa, cap, maximum), pa)
            want = three_calls(b, pb, cap, maximum)
            assert got == want
            assert snapshot(a) == snapshot(b)
            if isinstance(want, int) or isinstance(want, tuple):
                assert a.inflight.internal[pa.packet_id] is pa
            seen.add(want if isinstance(want, str) else type(want).__name__)
    # every answer was reached
    assert seen == {"full", "exhausted", "tuple", "int"}


def test_the_walk_is_next_packet_ids():
    """``next_id`` is the walk ``Client.next_packet_id`` runs (it calls
    it), ``begin`` the same walk under its own hold."""
    inf = Inflight()
    for pid in (1, 2, 4):
        inf.set(entry(pid))
    assert inf.next_id(0, 5) == 3
    assert inf.next_id(3, 5) == 5
    assert inf.next_id(5, 5) == 3  # wrapped past the maximum
    inf.set(entry(3))
    inf.set(entry(5))
    assert inf.next_id(2, 5) == -1  # every id in flight


@pytest.mark.parametrize(
    "quota, maximum_quota, want",
    [
        (2, 2, 1),  # written: the quota is spent by one
        (0, 2, BEGIN_PARKED),  # stored, waits for quota
        (0, 0, 1),  # no flow control: written, nothing spent
    ],
    ids=["spend", "park", "unlimited"],
)
def test_the_send_quota(quota, maximum_quota, want):
    st = ClientState(0, 16)
    st.inflight.reset_send_quota(maximum_quota)
    st.inflight.send_quota = quota
    pk = entry()
    assert st.inflight.begin(st, pk, 100, 0xFFFF) == want
    assert st.inflight.internal == {1: pk} and pk.packet_id == 1
    assert st.inflight.send_quota == max(quota - 1, 0)
    # the caller marks a parked entry: begin leaves its expiry alone
    assert pk.expiry == 0


def test_refusals_store_nothing():
    st = ClientState(0, 16)
    st.inflight.set(entry(1))
    st.packet_id = 1
    pk = entry()
    assert st.inflight.begin(st, pk, 1, 0xFFFF) == BEGIN_FULL
    assert st.inflight.begin(st, pk, 5, 1) == BEGIN_EXHAUSTED
    assert list(st.inflight.internal) == [1]
    assert st.packet_id == 1 and pk.packet_id == 0


def test_one_acquisition_a_begin():
    """Under an enabled lock plane a begin takes the session's inflight
    lock once; the three calls it replaced took it three times at least
    (one more for each id the walk passed over)."""
    DEFAULT_PLANE.arm()
    try:
        stats = DEFAULT_PLANE.stats("inflight")
        st = ClientState(0, 16)
        st.inflight.set(entry(1))  # the walk passes one id over
        before = stats.acquisitions
        for _ in range(10):
            assert st.inflight.begin(st, entry(), 100, 0xFFFF) > 0
        assert stats.acquisitions - before == 10
        old = ClientState(0, 16)
        old.inflight.set(entry(1))
        before = stats.acquisitions
        three_calls(old, entry(), 100, 0xFFFF)
        assert stats.acquisitions - before == 4  # len, get, get, set
    finally:
        DEFAULT_PLANE.disarm()


def twin(a):
    """A second session holding what ``a`` holds."""
    b = ClientState(0, 16)
    for pid in a.inflight.internal:
        b.inflight.set(entry(pid))
    b.packet_id = a.packet_id
    b.inflight.reset_send_quota(a.inflight.maximum_send_quota)
    b.inflight.send_quota = a.inflight.send_quota
    return b


@pytest.mark.parametrize("seed", range(6))
def test_begin_run_answers_as_begin_did_an_entry(seed):
    """Random sessions and runs of one to twelve entries at a small id
    space: the run's answers, ids, map, cursor and quota are those of
    ``begin`` called once an entry, in order."""
    rng = random.Random(1000 + seed)
    seen = set()
    for _ in range(60):
        maximum = rng.randint(1, 8)
        cap = rng.randint(1, 10)
        a = session(rng, maximum)
        b = twin(a)
        run = [entry() for _ in range(rng.randint(1, 12))]
        singles = [entry() for _ in run]
        got = a.inflight.begin_run(a, run, cap, maximum)
        want = [b.inflight.begin(b, pk, cap, maximum) for pk in singles]
        assert got == want
        assert snapshot(a) == snapshot(b)
        for code, pk, single in zip(got, run, singles):
            if code >= BEGIN_PARKED:
                assert a.inflight.internal[pk.packet_id] is pk
                assert pk.packet_id == single.packet_id
            else:
                assert pk.packet_id == 0
            seen.add(code if code < 0 else "id")
    assert seen == {BEGIN_FULL, BEGIN_EXHAUSTED, BEGIN_PARKED, "id"}


@pytest.mark.parametrize(
    "held, cursor, want",
    [
        ((), 0, [1, 2, 3]),
        ((2,), 0, [1, 3, 4]),  # the walk passes a held id over
        ((), 4, [5, 1, 2]),  # and wraps past the maximum
        ((1, 2, 3, 4), 0, [5, BEGIN_EXHAUSTED, BEGIN_EXHAUSTED]),
    ],
    ids=["fresh", "held", "wrap", "exhausted"],
)
def test_begin_run_walks_ids_in_order(held, cursor, want):
    st = ClientState(0, 16)
    for pid in held:
        st.inflight.set(entry(pid))
    st.packet_id = cursor
    assert st.inflight.begin_run(st, [entry() for _ in want], 100, 5) == want


def test_an_empty_run_moves_nothing():
    st = ClientState(0, 16)
    st.packet_id = 7
    due, owner = set(), object()
    st.inflight.watch(due, owner)
    assert st.inflight.begin_run(st, [], 100, 0xFFFF) == []
    assert st.packet_id == 7 and not due


def test_a_run_puts_its_session_in_the_due_set():
    """The session is in the set of sessions holding entries once the run
    stores one, as after a ``begin``; a run that stores none leaves it
    out."""
    st = ClientState(0, 16)
    due, owner = set(), object()
    st.inflight.watch(due, owner)
    assert st.inflight.begin_run(st, [entry()], 0, 0xFFFF) == [BEGIN_FULL]
    assert not due
    assert st.inflight.begin_run(st, [entry(), entry()], 100, 0xFFFF) == [1, 2]
    assert due == {owner}


def test_one_acquisition_a_run():
    """Under an enabled lock plane a run of forty takes the session's
    inflight lock once; forty begins take it forty times."""
    DEFAULT_PLANE.arm()
    try:
        stats = DEFAULT_PLANE.stats("inflight")
        st = ClientState(0, 16)
        st.inflight.set(entry(1))
        before = stats.acquisitions
        assert st.inflight.begin_run(st, [entry() for _ in range(40)], 100, 0xFFFF) == list(range(2, 42))
        assert stats.acquisitions - before == 1
        before = stats.acquisitions
        for _ in range(40):
            st.inflight.begin(st, entry(), 100, 0xFFFF)
        assert stats.acquisitions - before == 40
    finally:
        DEFAULT_PLANE.disarm()
