"""The point-to-point fleet: EMQ's Open MQTT Benchmark Suite,
point-to-point scenario — every device publisher on a topic of its own,
heard by one subscriber, its own backend, and by nobody else.

Pair ``k`` is publisher ``p<k>-<tag>`` and subscriber ``s<k>-<tag>`` on
``p2p/<tag>/<k>``, the subscription at QoS 1. The table holds every
pair's row (``pairs``); the first ``live_pairs`` rows are live subscriber
connections and their publishers are connections of their own. One more
row is an operator's offline ``$SYS/#`` (see ``configs/p2p-50k.json``).
The seed names the topics and the clients, so two seeds hash to
different table rows.

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


def _topic(tag: str, k: int) -> str:
    return f"p2p/{tag}/{k}"


def plan(params: dict, seed: int, connections) -> dict:
    """``subscriptions``: pair ``k``'s row ``(s<k>-<tag>, p2p/<tag>/<k>, 1)``
    for every pair in order, then the offline rows; ``live``: the first
    ``live_pairs`` rows; ``publishers``: ``p0-<tag>`` .. the last live
    pair's, which subscribe to nothing."""
    n, live = params["pairs"], params["live_pairs"]
    if not 0 < live <= n:
        raise ValueError(f"live_pairs must be in 1..{n}, not {live}")
    if connections not in (None, 2 * live):
        raise ValueError(f"this deployment has {2 * live} connections, not {connections}")
    tag = _tag(seed)
    subs = [(f"s{k}-{tag}", _topic(tag, k), 1) for k in range(n)]
    subs += [(cid, flt, 0) for cid, flt in params["offline_subscriptions"]]
    return {
        "subscriptions": subs,
        "live": list(range(live)),
        "publishers": [f"p{k}-{tag}" for k in range(live)],
    }


def topics(params: dict, seed: int, publisher: int):
    """Publisher ``publisher`` publishes to its own pair's topic, and no
    other."""
    topic = _topic(_tag(seed), publisher)
    while True:
        yield topic
