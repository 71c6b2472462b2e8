"""The Sparkplug B plant: Eclipse Sparkplug Specification 3.0.0 — many
edge nodes that report, a few host applications that take everything.

Namespace ``spBv1.0/<group_id>/<message_type>/<edge_node_id>[/<device_id>]``
("Topics and Messages"). Who holds what ("Operational Behavior"), in
load order:

====================  =====================================  ===  =======
client                filter                                 QoS  live
====================  =====================================  ===  =======
the primary host,     ``spBv1.0/#``                          1    both
the historian
one line dashboard a  ``spBv1.0/<g>/#``                      0    all
group
one node application  ``spBv1.0/<g>/+/<n>/#``                0    all
a live node
the live edge nodes   ``spBv1.0/<g>/NCMD/<n>/#``             1    all
every other edge      NCMD ``/#``, DCMD ``/#``,              1    none
node                  ``spBv1.0/STATE/<primary host>``
a catalog service     ``spBv1.0/+/NBIRTH/#``, and the same   1    none
                      for ``DBIRTH`` and ``NDEATH``
====================  =====================================  ===  =======

Hosts come first: the control's cap keeps the LAST matched rows in load
order, so it has to cut them. A live client holds one row (the generator
sends one SUBSCRIBE a connection and a clean-session connect drops what
the bulk load gave its client id). The live nodes are spread evenly over
the groups: live node ``k`` is node ``k // groups`` of group
``k % groups``. The seed names groups, nodes and hosts, so two seeds
hash to different table rows; row ``i`` and every stream are functions
of the seed.

Pure Python; imports neither ``jax`` nor ``mqtt_tpu``.
"""

from __future__ import annotations

import random

_M = (1 << 64) - 1
NS = "spBv1.0"


def _tag(seed: int) -> str:
    """Six hex digits of the seed's splitmix64 finalizer."""
    z = (seed + 0x9E3779B97F4A7C15) & _M
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _M
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _M
    return f"{(z ^ (z >> 31)) & 0xFFFFFF:06x}"


def _group(tag: str, g: int) -> str:
    return f"L{g:02d}-{tag}"


def _node(tag: str, j: int) -> str:
    return f"EN{j:03d}-{tag}"


def _live_nodes(params: dict) -> list:
    """``(group, node)`` indices of live node ``k``, by ``k``."""
    groups = params["groups"]
    live = [(k % groups, k // groups) for k in range(params["live_nodes"])]
    if live and live[-1][1] >= params["nodes_per_group"]:
        raise ValueError("more live nodes than the groups hold")
    return live


def plan(params: dict, seed: int, connections) -> dict:
    """``subscriptions``: every ``(client, filter, qos)`` of the plant in
    load order (the module's table); ``live``: the rows whose client is a
    TCP connection; ``publishers``: the live edge nodes, by ``k``, then
    the primary host — each the same connection as its subscriber row."""
    live_nodes = _live_nodes(params)
    n_pub = len(live_nodes) + 1
    if connections not in (None, n_pub):
        raise ValueError(f"this deployment has {n_pub} publishers, not {connections}")
    tag = _tag(seed)
    groups, per_group = params["groups"], params["nodes_per_group"]
    primary = f"scada-{tag}"
    subs = [(primary, NS + "/#", 1), (f"hist-{tag}", NS + "/#", 1)]
    subs += [(f"dash-{tag}-{g}", f"{NS}/{_group(tag, g)}/#", 0) for g in range(groups)]
    subs += [
        (f"hmi-{tag}-{g}-{j}", f"{NS}/{_group(tag, g)}/+/{_node(tag, j)}/#", 0)
        for g, j in live_nodes
    ]
    nodes = [f"en-{tag}-{g}-{j}" for g, j in live_nodes]
    subs += [
        (client, f"{NS}/{_group(tag, g)}/NCMD/{_node(tag, j)}/#", 1)
        for client, (g, j) in zip(nodes, live_nodes)
    ]
    n_live = len(subs)
    state = f"{NS}/STATE/{primary}"
    is_live = set(live_nodes)
    for g in range(groups):
        group = _group(tag, g)
        for j in range(per_group):
            if (g, j) in is_live:
                continue
            client, node = f"en-{tag}-{g}-{j}", _node(tag, j)
            subs += [
                (client, f"{NS}/{group}/NCMD/{node}/#", 1),
                (client, f"{NS}/{group}/DCMD/{node}/#", 1),
                (client, state, 1),
            ]
    subs += [
        (f"catalog-{tag}", f"{NS}/+/{kind}/#", 1)
        for kind in ("NBIRTH", "DBIRTH", "NDEATH")
    ]
    return {
        "subscriptions": subs,
        "live": list(range(n_live)),
        "publishers": nodes + [primary],
    }


def topics(params: dict, seed: int, publisher: int):
    """Live node ``publisher``: its NBIRTH, one DBIRTH a device, then for
    ever one scan after another (NDATA, then DDATA for each device). The
    publisher after the last node is the primary host: it walks the live
    nodes in an order drawn from the seed with a rebirth request (NCMD),
    every fourth publish a DCMD to one of the node's devices."""
    tag = _tag(seed)
    live_nodes = _live_nodes(params)
    devices = params["devices_per_node"]
    if publisher == len(live_nodes):
        order = list(live_nodes)
        random.Random((seed << 12) + 0x5CADA).shuffle(order)
        i = 0
        while True:
            g, j = order[i % len(order)]
            head = f"{NS}/{_group(tag, g)}"
            if i % 4 == 3:
                yield f"{head}/DCMD/{_node(tag, j)}/D{(i // 4) % devices}"
            else:
                yield f"{head}/NCMD/{_node(tag, j)}"
            i += 1
    g, j = live_nodes[publisher]
    head, node = f"{NS}/{_group(tag, g)}", _node(tag, j)
    yield f"{head}/NBIRTH/{node}"
    for d in range(devices):
        yield f"{head}/DBIRTH/{node}/D{d}"
    scan = [f"{head}/NDATA/{node}"] + [
        f"{head}/DDATA/{node}/D{d}" for d in range(devices)
    ]
    while True:
        yield from scan
