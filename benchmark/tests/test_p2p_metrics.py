"""What ``p2p-50k.paced`` brought to the benchmark: the two housekeeping
readers over slices made by hand (what each divides or picks, and nothing,
None and never 0, from a program whose snapshots lack the counts or spans,
or from a slice that held no tick), and the deployment's plan: a pure
function of the seed, one subscriber a publisher on a topic of its own,
and the offline ``$SYS/#`` row."""

import itertools
import json
import os

import pytest

import program_spans
import reference
from deployments import p2p
from layer_metrics import housekeep_stall_max_ms, housekeep_visits_per_tick

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "configs", "p2p-50k.json"), encoding="utf-8") as f:
    CONFIG = json.load(f)
PARAMS, REHEARSE = CONFIG["params"], CONFIG["rehearse_params"]
MS = 1_000_000


class Slice:
    def __init__(self, a, b):
        self.a, self.b, self.batches = a, b, []


def span(name, t0_ms, dur_ms, visits=3):
    return (name, t0_ms * MS, (t0_ms + dur_ms) * MS, {"visits": visits})


# three ticks in the slice, 27 sessions looked at; the $SYS publish is the
# longest stretch and is not a housekeeping pass
A = {"housekeep_ticks": 40, "housekeep_visits": 500}
B = {
    "housekeep_ticks": 43, "housekeep_visits": 527,
    "host_spans": [
        span("mqtt/housekeep", 100, 0.25), span("mqtt/sys.publish", 101, 12.0),
        span("mqtt/housekeep", 1100, 1.5), span("mqtt/housekeep", 2100, 0.5),
    ],
}


@pytest.mark.parametrize(
    "a, b, visits, stall",
    [
        (A, B, 9.0, 1.5),
        # a parent commit: no counts, no spans
        ({}, {}, None, None),
        # a slice that held no tick
        (A, {**A, "host_spans": [span("mqtt/sys.publish", 5, 9.0)]}, None, None),
        ({**A}, {**A, "host_spans": []}, None, None),
    ],
    ids=["three_ticks", "parent", "sys_only", "no_tick"],
)
def test_what_the_readers_read(monkeypatch, a, b, visits, stall):
    monkeypatch.setattr(program_spans, "load", lambda: Slice(a, b))
    ctx = {"metric": "", "trace": None}
    got_visits = housekeep_visits_per_tick.read(ctx)
    got_stall = housekeep_stall_max_ms.read(ctx)
    assert got_visits == visits if visits is None else got_visits == pytest.approx(visits)
    assert got_stall == stall if stall is None else got_stall == pytest.approx(stall)


def test_no_slice_reads_nothing(monkeypatch):
    monkeypatch.setattr(program_spans, "load", lambda: None)
    for reader in (housekeep_visits_per_tick, housekeep_stall_max_ms):
        assert reader.read({"metric": "", "trace": None}) is None


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 7, 4_000_000_001])
def test_the_plan_is_the_seeds_and_pairs_one_to_one(seed):
    plan = p2p.plan(PARAMS, seed, None)
    assert plan == p2p.plan(PARAMS, seed, None)
    subs, live, pubs = plan["subscriptions"], plan["live"], plan["publishers"]
    tag = p2p._tag(seed)
    assert len(subs) == 50_000 + 1 and live == list(range(5_000))
    assert len(pubs) == 5_000 == len(set(pubs))
    for k in (0, 1, 4_999, 49_999):
        assert subs[k] == (f"s{k}-{tag}", f"p2p/{tag}/{k}", 1)
    assert subs[-1] == ("ops-dashboard", "$SYS/#", 0)
    assert not {c for c, _f, _q in subs} & set(pubs)
    full = reference.FilterSet(subs)
    for k in (0, 3, 4_999):
        topic = next(p2p.topics(PARAMS, seed, k))
        assert pubs[k] == f"p{k}-{tag}"
        # publisher k's topic is heard by subscriber k and by nobody else
        assert dict(full.matches(topic)) == {f"s{k}-{tag}": 1}
        stream = list(itertools.islice(p2p.topics(PARAMS, seed, k), 5))
        assert stream == [topic] * 5


def test_two_seeds_name_two_fleets():
    a, b = (p2p.plan(REHEARSE, s, None) for s in (11, 12))
    assert not {f for _c, f, _q in a["subscriptions"][:-1]} & {
        f for _c, f, _q in b["subscriptions"][:-1]
    }


def test_the_rehearse_size_and_the_connections_it_takes():
    plan = p2p.plan(REHEARSE, 5, 100)
    assert len(plan["subscriptions"]) == 501 and len(plan["publishers"]) == 50
    with pytest.raises(ValueError):
        p2p.plan(REHEARSE, 5, 32)
