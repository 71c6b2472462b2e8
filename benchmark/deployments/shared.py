"""Shared subscriptions: BASELINE.json configs[3], "100k $share groups x
16 members, QoS1", as ``bench.build_cfg4`` realised it (git
``12c7918:bench.py:237-257``), served by MQTT 5.0 section 4.8.2's rule:
each matching message goes to ONE member of each matching group, of the
server's choice.

Group ``g`` is ``grp<g>-<tag>`` and holds ONE exact 3-level inner filter
over the ``telemetry-1m`` vocabulary (``region<i>/device<j>/metric<k>``),
drawn from the seed in ``build_cfg4``'s order (one ``Random(seed)``,
three choices a group). Its members are the clients ``g<g>m<m>-<tag>``,
each with one row ``$share/grp<g>-<tag>/<inner filter>`` at QoS 1, in
load order group by group. ``build_cfg4`` spelled the prefix ``$SHARE``;
the spec's spelling, which the reference knows, is ``$share``. After the
groups, the configuration's offline rows (an operator dashboard's
``$SYS/#``: without one wildcard the match plane answers every topic on
the host).

``live_groups`` groups, drawn from the seed, are fully live: every member
is a TCP connection with one SUBSCRIBE of its own row, so the check holds
such a group to "exactly one". The publishers are connections of their
own, ``pub-<tag>-<k>``, which subscribe to nothing. Publisher ``k``'s
stream names the inner filter of a live group, uniformly, except every
``cold_every``-th publish, which names the inner filter of a group drawn
uniformly from all of them (almost surely offline: "at most one").

Pure Python; imports neither ``jax`` nor ``mqtt_tpu``.
"""

from __future__ import annotations

import functools
import random

_M = (1 << 64) - 1
NAMES = ("region", "device", "metric")


def _tag(seed: int) -> str:
    """Six hex digits of the seed's splitmix64 finalizer."""
    z = (seed + 0x9E3779B97F4A7C15) & _M
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _M
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _M
    return f"{(z ^ (z >> 31)) & 0xFFFFFF:06x}"


@functools.lru_cache(maxsize=4)
def _inner(seed: int, groups: int, vocabulary: int) -> tuple:
    """Every group's inner filter, by group: ``build_cfg4``'s draws."""
    v0, v1, v2 = ([f"{n}{i}" for i in range(vocabulary)] for n in NAMES)
    choice = random.Random(seed).choice
    return tuple(f"{choice(v0)}/{choice(v1)}/{choice(v2)}" for _ in range(groups))


def live_groups(params: dict, seed: int) -> list:
    """The fully live groups, ascending."""
    rng = random.Random((seed << 12) + 0x5A4E)
    return sorted(rng.sample(range(params["groups"]), params["live_groups"]))


def plan(params: dict, seed: int, connections) -> dict:
    """``subscriptions``: every ``(client, filter, qos)`` in load order,
    group by group, then the offline rows; ``live``: the rows of the fully
    live groups' members, one a client; ``publishers``: the
    ``params["publishers"]`` publishing connections (the mix's
    ``connections`` is the full size's count, 32; the rehearse size has
    fewer, so the deployment's own count is the one used)."""
    tag = _tag(seed)
    members = params["members"]
    inner = _inner(seed, params["groups"], params["vocabulary"])
    subs = [
        (f"g{g}m{m}-{tag}", f"$share/grp{g}-{tag}/{flt}", 1)
        for g, flt in enumerate(inner)
        for m in range(members)
    ]
    subs += [(client, flt, 0) for client, flt in params["offline_subscriptions"]]
    live = [g * members + m for g in live_groups(params, seed) for m in range(members)]
    return {
        "subscriptions": subs,
        "live": live,
        "publishers": [f"pub-{tag}-{k}" for k in range(params["publishers"])],
    }


def topics(params: dict, seed: int, publisher: int):
    """Publisher ``publisher``'s endless stream, from ``(seed, publisher)``:
    a live group's inner filter, uniformly, but every ``cold_every``-th
    publish (the 4th, 8th, ...) a group's drawn from all of them."""
    inner = _inner(seed, params["groups"], params["vocabulary"])
    hot = [inner[g] for g in live_groups(params, seed)]
    every = params["cold_every"]
    rng = random.Random((seed << 12) + publisher + 1)
    choice, randrange = rng.choice, rng.randrange
    groups = len(inner)
    i = 0
    while True:
        i += 1
        yield inner[randrange(groups)] if i % every == 0 else choice(hot)
