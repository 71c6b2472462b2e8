"""``p2p-50k`` (EMQ's Open MQTT Benchmark Suite, point-to-point: each
publisher on a topic of its own, heard by one subscriber) at the rehearse
size on the CPU backend: the deployment's plan
(``benchmark/deployments/p2p.py``) served by a broker with the device
matcher over live sockets, one QoS1 frame a send, paced over the
publishers as the open loop sends them, and held to the plain reference
(``benchmark/reference.py``): every publish reaches its pair's subscriber
and nobody else, once, in order, at QoS 1, every QoS1 publish and
delivery acknowledged, nothing dropped. The configuration's control
(every 1,000th delivery lost) must come out as not correct. Answers and
counts, never a rate."""

import asyncio
import json
import os

import pytest

from mqtt_tpu import Options, staging
from mqtt_tpu.packets import CONNACK, PUBACK, PUBLISH, SUBACK, Subscription

from tests.test_batch_completion import load_benchmark_module
from tests.test_server import (
    Harness,
    connect_packet,
    pub_packet,
    read_wire_packet,
    sub_packet,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
reference = load_benchmark_module("reference")
p2p = load_benchmark_module("deployments/p2p")

with open(os.path.join(ROOT, "benchmark/configs/p2p-50k.json"), encoding="utf-8") as f:
    CONFIG = json.load(f)
REHEARSE = CONFIG["rehearse_params"]
ROUNDS = 24  # each live publisher's sends: 50 x 24 = 1,200 deliveries due


def serve(seed):
    """The rehearse plan (500 rows, 50 live pairs) in a broker with the
    device matcher, loaded by the restore's route as ``benchmark/run.py``
    loads it; every live subscriber connects over loopback TCP (v4,
    clean), subscribes to its row and acknowledges each QoS1 delivery;
    publish ``j`` goes out alone on publisher ``j % 50``, a round of the
    publishers at a time, as the open loop paces them."""
    plan = p2p.plan(REHEARSE, seed, None)
    subs, n_pub = plan["subscriptions"], len(plan["publishers"])
    streams = [p2p.topics(REHEARSE, seed, k) for k in range(n_pub)]
    sent = [(k, seq, next(streams[k]), 1) for seq in range(ROUNDS) for k in range(n_pub)]

    async def connect(port, cid, received, counts, row=None):
        r, w = await asyncio.open_connection("127.0.0.1", port)
        w.write(connect_packet(cid))
        assert (await read_wire_packet(r)).fixed_header.type == CONNACK
        if row is not None:
            _c, flt, qos = subs[row]
            w.write(sub_packet(1, [Subscription(filter=flt, qos=qos)]))
            ack = await read_wire_packet(r)
            assert ack.fixed_header.type == SUBACK and ack.reason_codes == bytes([qos])
        got = received.setdefault(cid, [])

        async def read():
            while True:
                pk = await read_wire_packet(r)
                if pk.fixed_header.type == PUBACK:
                    counts["pubacks"] += 1
                if pk.fixed_header.type != PUBLISH:
                    continue
                pub, seq = bytes(pk.payload).split(b":")
                got.append(reference.pack_delivery(
                    int(pub), int(seq), pk.fixed_header.qos,
                    int(pk.fixed_header.dup),
                    reference.topic_tag(pk.topic_name.encode()),
                ))
                if pk.fixed_header.qos:
                    w.write(b"\x40\x02" + pk.packet_id.to_bytes(2, "big"))

        return w, asyncio.ensure_future(read())

    async def scenario():
        from mqtt_tpu.listeners import Config as LConfig
        from mqtt_tpu.listeners.tcp import TCP

        h = Harness(Options(
            inline_client=True, device_matcher=True,
            matcher_opts={"background": False},
            matcher_stage_latency_budget_ms=0,
        ))
        srv = h.server
        srv.add_listener(TCP(LConfig(type="tcp", id="t", address="127.0.0.1:0")))
        await srv.serve()
        port = int(srv.listeners.get("t").address().rsplit(":", 1)[1])
        staging.bulk_register(
            srv.topics, ((c, Subscription(filter=f, qos=q)) for c, f, q in subs)
        )
        srv.matcher.flush()
        received: dict = {}
        counts = {"pubacks": 0}
        conns = [
            await connect(port, subs[row][0], received, counts, row)
            for row in plan["live"]
        ]
        pubs = [await connect(port, cid, {}, counts) for cid in plan["publishers"]]
        srv.matcher.flush()
        assert srv.topics.held == len(subs)
        stats = srv.matcher.stats
        topics0 = stats.topics
        for seq in range(ROUNDS):
            for k, (w, _task) in enumerate(pubs):
                w.write(pub_packet(sent[seq * n_pub + k][2], b"%d:%d" % (k, seq),
                                   qos=1, pid=seq + 1))
            await asyncio.sleep(0.005)
        stage = srv._stage
        for _ in range(600):
            if (
                counts["pubacks"] >= len(sent)
                and sum(map(len, received.values())) >= len(sent)
                and stage.pending_depth == 0 and stage.inflight_batches == 0
            ):
                break
            await asyncio.sleep(0.05)
        await asyncio.sleep(0.3)  # a surplus delivery would arrive now
        out = {
            "plan": plan, "sent": sent, "received": received,
            "pubacks": counts["pubacks"], "dropped": srv.info.messages_dropped,
            "topics": stats.topics - topics0, "host_fallbacks": stats.host_fallbacks,
            "inflight_held": {
                cl.id for cl in srv._due.inflight
            },
        }
        for w, task in conns + pubs:
            task.cancel()
            w.close()
        await srv.close()
        await h.shutdown()
        return out

    return asyncio.run(asyncio.wait_for(scenario(), timeout=120))


@pytest.fixture(scope="module", params=[13, 2**31 + 13])
def served(request):
    return serve(request.param)


def due_of(served):
    subs = served["plan"]["subscriptions"]
    live = reference.FilterSet(subs[row] for row in served["plan"]["live"])
    full = reference.FilterSet(subs)
    expected = reference.expected_deliveries(live, iter(served["sent"]))
    return live, full, expected


def test_every_publish_reaches_its_pair_once_in_order(served):
    live, _full, expected = due_of(served)
    assert sum(len(v) for by in expected.values() for v in by.values()) == 50 * ROUNDS
    # one subscriber a publisher, and one publisher a subscriber
    assert all(len(by) == 1 for by in expected.values()) and len(expected) == 50
    verdict = reference.compare_deliveries(expected, served["received"], {}, live.order)
    assert verdict["errors"] == 0, verdict
    assert served["pubacks"] == len(served["sent"])  # every QoS1 publish acknowledged
    assert served["dropped"] == 0
    # every delivery acknowledged: no session holds an in-flight entry
    assert served["inflight_held"] == set()


def test_the_device_matcher_answered_every_publish(served):
    assert served["topics"] >= len(served["sent"]) and served["host_fallbacks"] == 0


def test_the_control_comes_out_not_correct(served):
    live, full, expected = due_of(served)
    received, _plain, _shared = reference.control_answers(
        CONFIG["control"], live, full, expected, iter(served["sent"]), [], {}
    )
    verdict = reference.compare_deliveries(expected, received, {}, live.order)
    assert verdict["missing"] == len(served["sent"]) // 1000 >= 1
    assert verdict["errors"] > 0
