"""The deep fleet: BASELINE.json configs[2] — 8-level filters, 5% of
them cut at a uniform depth 1-7 and ended in ``#``.

The vocabulary and the cut are ``bench.build_cfg3``'s (git
``12c7918:bench.py:212-235``): ``t0..t999`` at the top level,
``s0..s29`` below it. Its draw ORDER is not kept: row ``i``'s filter is
a pure function of ``(seed, i)``, so a publisher (a generator process
that never holds the fleet) can name a subscribed path from the seed and
a row number alone. The distribution is the same.

Pure Python; imports neither ``jax`` nor ``mqtt_tpu``.
"""

from __future__ import annotations

import random

_M = (1 << 64) - 1
_GOLDEN = 0x9E3779B97F4A7C15


def _mix(z: int) -> int:
    """splitmix64's finalizer."""
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _M
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _M
    return z ^ (z >> 31)


def _vocab(params: dict) -> tuple:
    top = [f"t{i}" for i in range(params["top_vocabulary"])]
    below = [f"s{i}" for i in range(params["level_vocabulary"])]
    return top, below


def _row_filter(params: dict, top: list, below: list, state: int, i: int) -> str:
    """Row ``i`` of the fleet whose seed hashed to ``state``: two words
    of a splitmix64 stream, the first spent on the levels, the second on
    whether the filter is cut and where."""
    z = _mix((state + (2 * i + 1) * _GOLDEN) & _M)
    n_top, n_below = len(top), len(below)
    parts = [top[z % n_top]]
    z //= n_top
    for _ in range(params["levels"] - 1):
        parts.append(below[z % n_below])
        z //= n_below
    z = _mix((state + (2 * i + 2) * _GOLDEN) & _M)
    if z % 1_000_000 < params["hash_share"] * 1_000_000:
        depth = 1 + (z // 1_000_000) % (params["levels"] - 1)
        parts[depth:] = ["#"]
    return "/".join(parts)


def row_filter(params: dict, seed: int, i: int) -> str:
    """The filter of subscription row ``i``: a function of ``(seed, i)``."""
    top, below = _vocab(params)
    return _row_filter(params, top, below, _mix(seed & _M), i)


def plan(params: dict, seed: int, connections) -> dict:
    """``subscriptions``: every ``(client, filter, qos)`` of the fleet in
    load order, one client ``cl<i>`` a row, QoS ``i % 3``. ``live``: the
    rows whose client is a real connection — the first ``live_hash`` rows
    that end in ``#`` and the first ``live_exact`` that do not; every
    other client is offline. ``publishers``: client ids of the publishing
    connections, which subscribe to nothing."""
    top, below = _vocab(params)
    state = _mix(seed & _M)
    subs = [
        (f"cl{i}", _row_filter(params, top, below, state, i), i % 3)
        for i in range(params["subscriptions"])
    ]
    wild = [i for i, s in enumerate(subs) if s[1][-1] == "#"][: params["live_hash"]]
    exact = [i for i, s in enumerate(subs) if s[1][-1] != "#"][: params["live_exact"]]
    return {
        "subscriptions": subs,
        "live": sorted(wild + exact),
        "publishers": [f"pub{k}" for k in range(int(connections))],
    }


def pool(params: dict, seed: int, publisher: int) -> list:
    """The fixed set of topics publisher ``publisher`` publishes for (a
    gateway's devices), drawn once from the seed. Every topic has all
    ``levels`` levels. Of each ``1 / subscribed_share`` pool topics one is
    a SUBSCRIBED path — the filter of a uniformly drawn row, a ``#``
    replaced by uniform levels — and the others are uniform over the
    vocabulary, as ``build_cfg3``'s ``topic_gen``."""
    rng = random.Random((seed << 12) + publisher + 1)
    top, below = _vocab(params)
    state = _mix(seed & _M)
    levels = params["levels"]
    every = round(1 / params["subscribed_share"])
    choice = rng.choice
    out = []
    for j in range(params["topics_per_publisher"]):
        if j % every == every - 1:
            row = rng.randrange(params["subscriptions"])
            parts = _row_filter(params, top, below, state, row).split("/")
            if parts[-1] == "#":
                parts.pop()
                parts += [choice(below) for _ in range(levels - len(parts))]
        else:
            parts = [choice(top)] + [choice(below) for _ in range(levels - 1)]
        out.append("/".join(parts))
    return out


def topics(params: dict, seed: int, publisher: int):
    """Publisher ``publisher``'s endless topic stream: uniform over its
    pool, its own generator so any process can replay it from the seed."""
    mine = pool(params, seed, publisher)
    choice = random.Random((seed << 12) + publisher + 0x800).choice
    while True:
        yield choice(mine)
