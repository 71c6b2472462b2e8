"""The telemetry fleet: BASELINE.json configs[1].

A copy of ``bench.cfg2_subscriptions`` / ``cfg2_topic`` (same draws in
the same order), kept here because later PRs may change ``bench.py``
and may not change the yardstick. Everything comes from the seed.
"""

from __future__ import annotations

import random

NAMES = ("region", "device", "metric")


def _vocab(params):
    return [[f"{name}{i}" for i in range(params["vocabulary"])] for name in NAMES]


def plan(params: dict, seed: int, connections) -> dict:
    """``subscriptions``: every ``(client, filter, qos)`` of the fleet in
    load order. ``live``: the rows whose client is a real connection —
    the first ``live_wildcard`` rows holding a ``+`` and the first
    ``live_exact`` holding none; every other client is offline.
    ``publishers``: client ids of the publishing connections, which
    subscribe to nothing (BASELINE states no publisher count: the traffic
    mix gives it)."""
    rng = random.Random(seed)
    v0, v1, v2 = _vocab(params)
    subs = []
    for i in range(params["subscriptions"]):
        parts = [rng.choice(v0), rng.choice(v1), rng.choice(v2)]
        if rng.random() < params["plus_share"]:
            parts[rng.randrange(3)] = "+"
        subs.append((f"cl{i}", "/".join(parts), i % 3))
    wild = [i for i, s in enumerate(subs) if "+" in s[1]][: params["live_wildcard"]]
    exact = [i for i, s in enumerate(subs) if "+" not in s[1]][: params["live_exact"]]
    return {
        "subscriptions": subs,
        "live": sorted(wild + exact),
        "publishers": [f"pub{k}" for k in range(int(connections))],
    }


def topics(params: dict, seed: int, publisher: int):
    """Publisher ``publisher``'s endless topic stream: uniform over the
    vocabulary (BASELINE states no popularity), its own generator so any
    process can replay it from the seed."""
    rng = random.Random((seed << 12) + publisher + 1)
    v0, v1, v2 = _vocab(params)
    choice = rng.choice
    while True:
        yield f"{choice(v0)}/{choice(v1)}/{choice(v2)}"
