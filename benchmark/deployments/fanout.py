"""The broadcast deployment: EMQ's Open MQTT Benchmark Suite, enterprise
scenario ``fanout-5-1000-5-250K`` — a few topics, every subscriber live
and hearing all of them.

``publishers`` publishers, one topic each (``<root>/t<k>``);
``subscribers`` subscribers, every one a TCP connection, each holding the
one filter ``<root>/+`` at QoS 1 (the source's five exact subscriptions
a subscriber: see ``configs/fanout-5-1000.json``, ``assumed``). The seed
names the root and the clients, so two seeds hash to different table
rows.

Pure Python; imports neither ``jax`` nor ``mqtt_tpu``.
"""

from __future__ import annotations

_M = (1 << 64) - 1


def _tag(seed: int) -> str:
    """Six hex digits of the seed's splitmix64 finalizer."""
    z = (seed + 0x9E3779B97F4A7C15) & _M
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _M
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _M
    return f"{(z ^ (z >> 31)) & 0xFFFFFF:06x}"


def _root(seed: int) -> str:
    return "feed-" + _tag(seed)


def plan(params: dict, seed: int, connections) -> dict:
    """``subscriptions``: one ``(client, "<root>/+", 1)`` a subscriber, in
    load order; ``live``: every row; ``publishers``: the publishing
    connections, which subscribe to nothing."""
    n_pub = params["publishers"]
    if connections not in (None, n_pub):
        raise ValueError(f"this deployment has {n_pub} publishers, not {connections}")
    if params["topics"] != n_pub:
        raise ValueError("one topic a publisher: topics must equal publishers")
    tag = _tag(seed)
    flt = _root(seed) + "/+"
    subs = [(f"sub-{tag}-{i}", flt, 1) for i in range(params["subscribers"])]
    return {
        "subscriptions": subs,
        "live": list(range(len(subs))),
        "publishers": [f"pub-{tag}-{k}" for k in range(n_pub)],
    }


def topics(params: dict, seed: int, publisher: int):
    """Publisher ``publisher`` publishes to its own topic, and no other."""
    topic = f"{_root(seed)}/t{publisher}"
    while True:
        yield topic
