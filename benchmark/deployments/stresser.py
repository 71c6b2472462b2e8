"""mqtt-stresser's shape: N clients, each subscribed to its own exact
topic, each publishing to it and reading its own messages back on the
same connection (mochi-mqtt README, "Performance Benchmarks").

One more subscription is assumed, and no client is connected for it: an
operator's ``$SYS/#`` (see ``configs/stresser-100.json``). The seed names
the clients, so two seeds hash to different table rows.
"""

from __future__ import annotations


def _client(seed: int, k: int) -> str:
    return f"w{seed % 1000003}-{k}"


def plan(params: dict, seed: int, connections) -> dict:
    n = params["clients"]
    if connections not in (None, n):
        raise ValueError(f"this deployment has {n} publishers, not {connections}")
    clients = [_client(seed, k) for k in range(n)]
    subs = [(cid, f"stress/{cid}", 0) for cid in clients]
    subs += [(cid, flt, 0) for cid, flt in params["offline_subscriptions"]]
    return {"subscriptions": subs, "live": list(range(n)), "publishers": clients}


def topics(params: dict, seed: int, publisher: int):
    topic = f"stress/{_client(seed, publisher)}"
    while True:
        yield topic
