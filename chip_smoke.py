#!/usr/bin/env python3
"""Chip smoke: serve publishes from the accelerator at 1M subscriptions.

The quickest proof that the system still starts on the chip. ONE process:

1. **Served leg.** BASELINE.json config 2 (1,000,000 subscriptions,
   3-level topics, 10% ``+``; the stream ``cfg2_subscriptions`` draws
   from ``--seed``) is loaded into a
   ``Server(Options(device_matcher=True))`` with default options and a
   TCP listener on ``127.0.0.1:0``, by a route a user of the broker has:
   ``topics.subscribe_bulk`` (through the durable restore's own
   ``staging.bulk_register``) plus ``matcher.flush()``. A few hundred of
   those subscriptions are then taken over by real TCP connections —
   wildcard filters, and exact filters chosen among the ones the
   publishes will hit — and folded in by a second ``flush()``. Tens of
   thousands of publishes follow on config 2's topic distribution over
   several publisher connections, QoS0 with a QoS1 share. Each
   connection keeps at most one chunk in flight (it waits for the PUBACK
   of the chunk's last frame), so the stage never holds more than
   ``PUBLISHERS * CHUNK`` publishes: far below
   ``overload_stage_max_pending``, which makes any admission fallback a
   finding and not load. A ramp of single-connection bursts walks the
   batch-bucket ladder first, until a whole pass compiles nothing.
2. **Checks.** Every real subscriber received exactly what its FILTER
   says (not what the live trie says), through the scenario lab's
   delivery oracle; >= 1024 sampled topics agree device vs
   ``TopicsIndex.subscribers`` on the full Subscribers set
   (``canon``); the breaker, the staging fallbacks, the overlay
   and the rebuild thread are all quiet; >= 90% of publishes resolved
   from device results; nothing compiled in the second half of the
   publishes; both native modules are loaded; HBM in use covers the
   uploaded table.
3. **Kernel roll-call.** Every jitted entry point runs once on the chip
   at its owner's shapes against the host oracle that already exists.

Exits non-zero — and prints no result — unless
``jax.devices()[0].platform`` is ``tpu`` (``--expect-platform cpu`` is
for the tiny dry run and the tier-1 test). Standard output is two lines
of JSON. The first is the summary: every counter checked above, the
compile ledger, the cache and the native modules; seconds in it are
set-up times, not metrics, and it ends with ``"claim": null``. The LAST
is the verdict, exactly ``{"ok": ..., "device": {"platform", "kind",
"count"}}`` with the device as JAX reports it. Sets no ``JAX_PLATFORMS``
and no compile-cache path: the cache comes through the package
(``mqtt_tpu.ops.backend``).
"""

from __future__ import annotations

import argparse
import asyncio
import faulthandler
import json
import os
import random
import sys
import time
import traceback
import warnings

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from mqtt_tpu.packets import (  # noqa: E402
    CONNACK,
    CONNECT,
    PUBACK,
    PUBLISH,
    ConnectParams,
    FixedHeader,
    Packet,
    Subscription,
    encode_packet,
)

PUBLISHERS = 8
CHUNK = 32  # frames one publisher keeps in flight
QOS1_EVERY = 8  # the QoS1 share of the publishes
LADDER = (16, 32, 64, 128, 256)  # every batch bucket PUBLISHERS*CHUNK can reach
MAX_RAMP_PASSES = 6
REAL_SUBSCRIBERS = 300  # half wildcard, half exact
PARITY_SAMPLE = 1024
ROLLCALL_BATCH = 4096  # Options.matcher_stage_max_batch
DEVICE_SHARE_MIN = 0.90
WAIT_S = 300.0  # any single wait on the broker
HARD_TIMEOUT_S = 1150  # the contract's 1200 s, minus room to say why


def canon(s):
    """Order-free digest of a Subscribers set for parity checks."""
    return (
        {c: (sub.qos, tuple(sorted(sub.identifiers.items()))) for c, sub in s.subscriptions.items()},
        {f: set(m) for f, m in s.shared.items()},
        set(s.inline_subscriptions),
    )


_CFG2_VOCAB = tuple(
    [f"{name}{i}" for i in range(100)] for name in ("region", "device", "metric")
)


def cfg2_subscriptions(n_subs, rng):
    """BASELINE.json config 2's subscription stream as ``(client, filter,
    qos)``: 3-level filters over a 100^3 vocabulary, 10% with one level
    replaced by ``+``. The served leg loads this stream into a broker, so
    the draw order is part of the deployment."""
    v0, v1, v2 = _CFG2_VOCAB
    for i in range(n_subs):
        parts = [rng.choice(v0), rng.choice(v1), rng.choice(v2)]
        if rng.random() < 0.10:
            parts[rng.randrange(3)] = "+"
        yield f"cl{i}", "/".join(parts), i % 3


def cfg2_topic(rng) -> str:
    """One publish topic on config 2's (uniform) topic distribution."""
    v0, v1, v2 = _CFG2_VOCAB
    return f"{rng.choice(v0)}/{rng.choice(v1)}/{rng.choice(v2)}"


def build_cfg2(n_subs, rng):
    """3-level topics, 10% single-level + wildcards (north star)."""
    from mqtt_tpu.topics import TopicsIndex

    index = TopicsIndex()
    for client, flt, qos in cfg2_subscriptions(n_subs, rng):
        index.subscribe(client, Subscription(filter=flt, qos=qos))

    def topic_gen():
        return cfg2_topic(rng)

    return index, topic_gen


def filter_matches(flt: tuple, topic: tuple) -> bool:
    """MQTT filter semantics straight from the spec ([MQTT-4.7.1]), on
    pre-split levels — the delivery expectation's only authority."""
    for i, f in enumerate(flt):
        if f == "#":
            return True
        if i >= len(topic) or (f != "+" and f != topic[i]):
            return False
    return len(flt) == len(topic)


class Publisher:
    """One publishing connection: wire-true v4 frames out, PUBACKs
    counted in. ``send`` writes a chunk and returns once every QoS1
    frame sent so far is acknowledged; a chunk's LAST frame is always
    QoS1, and the broker reads a connection's next frames only after the
    previous ones resolved, so a connection never has more than one
    chunk in the stage."""

    def __init__(self, cid: str) -> None:
        self.cid = cid
        self.sent: list = []  # (seq, topic, qos) as actually sent
        self._pid = 0
        self._unacked = 0

    async def connect(self, port: int) -> "Publisher":
        self.reader, self.writer = await asyncio.open_connection("127.0.0.1", port)
        self.writer.write(
            encode_packet(
                Packet(
                    fixed_header=FixedHeader(type=CONNECT),
                    protocol_version=4,
                    connect=ConnectParams(
                        protocol_name=b"MQTT", clean=True, keepalive=600,
                        client_identifier=self.cid,
                    ),
                )
            )
        )
        ack = await asyncio.wait_for(self.reader.readexactly(4), 30)
        if ack[0] >> 4 != CONNACK or ack[3] != 0:
            raise RuntimeError(f"{self.cid}: CONNACK {ack.hex()}")
        return self

    async def send(self, chunk: list) -> None:
        """``chunk`` is ``[(seq, topic), ...]``; seq rides in the payload
        and keys the delivery oracle."""
        frames = bytearray()
        for i, (seq, topic) in enumerate(chunk):
            qos = int(i == len(chunk) - 1 or seq % QOS1_EVERY == 0)
            if qos:
                self._pid = self._pid % 65000 + 1
                self._unacked += 1
            frames += encode_packet(
                Packet(
                    fixed_header=FixedHeader(type=PUBLISH, qos=qos),
                    protocol_version=4,
                    topic_name=topic,
                    payload=str(seq).encode(),
                    packet_id=self._pid if qos else 0,
                )
            )
            self.sent.append((seq, topic, qos))
        self.writer.write(bytes(frames))
        while self._unacked:
            ack = await asyncio.wait_for(self.reader.readexactly(4), WAIT_S)
            if ack[0] >> 4 != PUBACK:
                raise RuntimeError(f"{self.cid}: expected PUBACK, got {ack.hex()}")
            self._unacked -= 1

    async def close(self) -> None:
        self.writer.close()


def cache_entries(path: str) -> int:
    try:
        return sum(1 for f in os.listdir(path) if f.endswith("-cache"))
    except FileNotFoundError:
        return 0


class Smoke:
    """One run: the summary being built and the failures found so far."""

    def __init__(self, args, device: dict) -> None:
        self.args = args
        self.rng = random.Random(args.seed)
        self.failures: list[str] = []
        self.topics_index = None  # the served trie, for the roll-call
        self.out: dict = {
            **device,
            "seed": args.seed,
            "subs": args.subs,
            "publishes": args.publishes,
        }

    def fail(self, msg: str) -> None:
        print(f"FAIL: {msg}", file=sys.stderr, flush=True)
        self.failures.append(msg)

    def note(self, msg: str) -> None:
        print(f"# {msg}", file=sys.stderr, flush=True)

    # -- the served leg ----------------------------------------------------

    async def served_leg(self) -> None:
        from mqtt_tpu.scenarios import DeliveryOracle, ScenarioBroker
        from mqtt_tpu.server import Options

        args, rng, out = self.args, self.rng, self.out
        subs = list(cfg2_subscriptions(args.subs, rng))
        publishes = [(seq, cfg2_topic(rng)) for seq in range(args.publishes)]
        real = self._pick_real_subscribers(subs, publishes)
        oracle = DeliveryOracle("chip_smoke")

        t0 = time.perf_counter()
        self.broker = await ScenarioBroker(Options(device_matcher=True)).start()
        out["server_start_s"] = round(time.perf_counter() - t0, 3)
        self.srv = srv = self.broker.server
        self.topics_index = srv.topics
        self.matcher, self.stage = srv.matcher, srv._stage
        self.stats = srv.matcher.stats
        self.conns: list = []
        try:
            self._load(subs)
            await self._attach_real_subscribers(real, oracle)
            pubs = await self._publish(publishes)
            self._expect(oracle, real, [s for p in pubs for s in p.sent])
            await self._check_deliveries(oracle)
            self._check_who_answered(len(publishes))
            await self._check_parity(real)
            self._check_memory()
        finally:
            for c in self.conns:
                await c.close()
            await self.broker.stop()

    def _load(self, subs: list) -> None:
        """The deployment's subscriptions, by the restore route."""
        from mqtt_tpu.staging import bulk_register

        out, stats = self.out, self.stats
        out["load_route"] = (
            "staging.bulk_register -> topics.subscribe_bulk, then matcher.flush()"
        )
        t0 = time.perf_counter()
        bulk_register(
            self.srv.topics,
            (
                (client, Subscription(filter=flt, qos=qos))
                for client, flt, qos in subs
            ),
        )
        out["load_s"] = round(time.perf_counter() - t0, 3)
        t0 = time.perf_counter()
        self.matcher.flush()
        out["flush_s"] = round(time.perf_counter() - t0, 3)
        out["build_s"] = round(stats.build_seconds, 3)
        out["upload_s"] = round(stats.upload_seconds, 3)
        out["rebuilds_during_load"] = stats.rebuilds
        # what the loaded trie costs (read, not gated)
        out["particles"] = self.srv.topics.particles
        out["particle_maps"] = self.srv.topics.particle_maps
        self.note(
            f"loaded {len(subs)} subs in {out['load_s']}s, flush "
            f"{out['flush_s']}s ({stats.rebuilds} rebuilds), "
            f"{out['particles']} particles, {out['particle_maps']} maps"
        )

    async def _attach_real_subscribers(self, real: list, oracle) -> None:
        """Real connections take over some of the subscriptions; their
        few hundred deltas go in by the fold path."""
        from mqtt_tpu.scenarios import ScenarioClient

        out = self.out
        for client, flt, qos in real:
            c = ScenarioClient(self.broker.port, client)
            c.on_publish = lambda _t, payload, pk, cid=client: oracle.deliver(
                (cid, int(payload), pk.fixed_header.qos)
            )
            await c.connect()
            await c.subscribe(flt, qos)
            self.conns.append(c)
        self.matcher.flush()
        out["real_subscribers"] = len(real)
        out["pending_deltas"] = self.matcher.pending_deltas
        if out["pending_deltas"]:
            self.fail(f"pending_deltas {out['pending_deltas']} after load")
        if self.stats.folds < 1:
            self.fail("the real subscribers did not fold into the index")

    async def _publish(self, publishes: list) -> list:
        """Ramp over the bucket ladder until a whole pass compiles
        nothing, then steady traffic in two halves; the ledger must not
        move in the second."""
        from mqtt_tpu.ops.devicestats import LEDGER

        out, stats, stage = self.out, self.stats, self.stage
        pubs = [
            await Publisher(f"smoke-pub{k}").connect(self.broker.port)
            for k in range(PUBLISHERS)
        ]
        self.conns.extend(pubs)
        self.base_topics = base = stats.topics
        sent = 0

        async def quiesce() -> None:
            deadline = time.monotonic() + WAIT_S
            while time.monotonic() < deadline and stage.alive():
                if (
                    stats.topics - base + stage.admission_fallbacks >= sent
                    and stage.pending_depth == 0
                    and stage.inflight_batches == 0
                ):
                    return
                await asyncio.sleep(0.01)
            raise TimeoutError(
                f"the stage never drained: sent {sent}, the matcher saw "
                f"{stats.topics - base}, admission fallbacks "
                f"{stage.admission_fallbacks}, alive {stage.alive()}"
            )

        half = len(publishes) // 2
        passes = 0
        while True:
            before = LEDGER.total()
            for n in LADDER:
                await pubs[0].send(publishes[sent : sent + n])
                sent += n
                await quiesce()
            passes += 1
            if LEDGER.total() == before:
                break
            if passes >= MAX_RAMP_PASSES or sent + sum(LADDER) > half:
                self.fail(
                    f"the ramp still compiled in pass {passes}: "
                    + LEDGER.attribution(before)
                )
                break
        out["ramp_passes"], out["ramp_publishes"] = passes, sent
        self.note(f"ramp settled after {passes} passes ({sent} publishes)")

        ledger_half = LEDGER.total()
        for end in (half, len(publishes)):
            batch = publishes[sent:end]
            await asyncio.gather(
                *(self._steady(p, batch[k::PUBLISHERS]) for k, p in enumerate(pubs))
            )
            sent = end
            await quiesce()
            if end == half:
                ledger_half = LEDGER.total()
        out["compiles_second_half"] = LEDGER.total() - ledger_half
        if out["compiles_second_half"]:
            self.fail(
                "compiled in the second half of the publishes: "
                + LEDGER.attribution(ledger_half)
            )
        out["served_compiles"] = LEDGER.counts()
        return pubs

    @staticmethod
    async def _steady(pub: Publisher, mine: list) -> None:
        for i in range(0, len(mine), CHUNK):
            await pub.send(mine[i : i + CHUNK])

    async def _check_deliveries(self, oracle) -> None:
        """Exactly what the filters say, no more."""
        deadline = time.monotonic() + 60
        while not oracle.complete() and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        await asyncio.sleep(0.5)  # let a surplus delivery show itself
        self.out["delivery"] = d = oracle.summary()
        if d["gaps"] or d["duplicates"]:
            self.fail(f"delivery mismatch: {d}")
        if not d["expected"]:
            self.fail("no delivery was expected: the leg checked nothing")

    def _check_who_answered(self, n_publishes: int) -> None:
        """The counters that say WHO answered the publishes."""
        out, stage, matcher = self.out, self.stage, self.matcher
        out["matcher"] = served = self.stats.as_dict()
        out["served_topics"] = self.stats.topics - self.base_topics
        out["device_share"] = round(1.0 - served["fallback_ratio"], 6)
        if out["served_topics"] != n_publishes:
            self.fail(
                f"{n_publishes} publishes sent, the matcher saw "
                f"{out['served_topics']}"
            )
        if out["device_share"] < DEVICE_SHARE_MIN:
            self.fail(f"device-resolved share {out['device_share']}")
        if served["host_fast"]:
            self.fail(f"host_fast {served['host_fast']}: the exact-map served")
        out["breaker"] = matcher.breaker_gauges()
        for key in ("trips", "failures", "fallback_batches", "wedged_workers"):
            if out["breaker"][key]:
                self.fail(f"breaker {key} = {out['breaker'][key]}")
        fallbacks = {
            k: int(c.value) for k, c in self.srv.telemetry.fallback.items()
        }
        out["staging"] = {
            "fallbacks": fallbacks,
            "peak_pending": stage.peak_pending,
            "max_pending": stage.max_pending,
            "compile_tainted_batches": stage.compile_tainted_batches,
        }
        for klass in ("admission", "issue_error", "resolve_error"):
            if fallbacks[klass]:
                self.fail(f"staging fallback {klass} = {fallbacks[klass]}")
        out["rebuild_errors"] = matcher.rebuild_errors
        if out["rebuild_errors"]:
            self.fail(f"{out['rebuild_errors']} background rebuild exception(s)")

    async def _check_parity(self, real: list) -> None:
        """Sampled parity on the FULL Subscribers set, device vs host:
        uniform topics plus topics the folded-in real filters reach."""
        rng = self.rng
        names = ("region", "device", "metric")
        sample = [cfg2_topic(rng) for _ in range(PARITY_SAMPLE)] + [
            "/".join(
                f"{names[i]}{rng.randrange(100)}" if lvl == "+" else lvl
                for i, lvl in enumerate(flt.split("/"))
            )
            for _c, flt, _q in real
        ]
        device = await asyncio.get_running_loop().run_in_executor(
            None, self.matcher.match_topics, sample
        )
        mismatched = [
            t
            for t, dev in zip(sample, device)
            if canon(dev) != canon(self.srv.topics.subscribers(t))
        ]
        self.out["parity"] = {
            "sampled": len(sample), "mismatches": len(mismatched),
        }
        if mismatched:
            self.fail(f"device != host on {mismatched[:5]}")

    @staticmethod
    def _pick_real_subscribers(subs: list, publishes: list) -> list:
        """Which of the deployment's subscriptions real connections hold:
        wildcard filters, and exact filters among those the publishes
        will hit (uniform topics over 100^3 would otherwise leave almost
        every exact subscriber silent, and the leg would check
        nothing)."""
        n = min(REAL_SUBSCRIBERS, len(subs) // 4) // 2
        published = {t for _seq, t in publishes}
        wild = [s for s in subs if "+" in s[1]][:n]
        exact = [s for s in subs if s[1] in published][:n]
        if len(exact) < n:  # tiny sizes: any exact filter will do
            taken = set(exact)
            rest = (s for s in subs if "+" not in s[1] and s not in taken)
            exact += [s for s, _ in zip(rest, range(n - len(exact)))]
        return wild + exact

    @staticmethod
    def _expect(oracle, real: list, sent: list) -> None:
        exact: dict = {}
        wild = []
        for client, flt, qos in real:
            if "+" in flt or "#" in flt:
                wild.append((client, tuple(flt.split("/")), qos))
            else:
                exact.setdefault(flt, []).append((client, qos))
        for seq, topic, qos in sent:
            for client, sub_qos in exact.get(topic, ()):
                oracle.expect((client, seq, min(qos, sub_qos)))
            levels = tuple(topic.split("/"))
            for client, flt, sub_qos in wild:
                if filter_matches(flt, levels):
                    oracle.expect((client, seq, min(qos, sub_qos)))

    def _check_memory(self) -> None:
        """The table is ON the device."""
        import jax

        out, stats = self.out, self.stats
        out["host_table_bytes"] = stats.table_bytes
        mem = jax.devices()[0].memory_stats()
        out["hbm_bytes_in_use"] = mem["bytes_in_use"] if mem else None
        out["hbm_peak_bytes_in_use"] = mem["peak_bytes_in_use"] if mem else None
        if mem is None:
            if out["platform"] != "cpu":  # CPU-jax has no memory_stats
                self.fail("the device reports no memory_stats()")
        elif mem["bytes_in_use"] < stats.table_bytes:
            self.fail(
                f"HBM in use {mem['bytes_in_use']} < uploaded table "
                f"{stats.table_bytes}"
            )

    # -- kernel roll-call --------------------------------------------------

    def roll_call(self) -> None:
        """Each jitted entry once at its owner's shapes vs its host
        oracle. A kernel the compiler refuses, or that disagrees, fails
        the run — by name."""
        from mqtt_tpu.ops.devicestats import LEDGER

        results: dict = {}
        for name, fn in (
            ("match_kernels", self._rc_match_kernels),
            ("predicates", self._rc_predicates),
            ("keystream", self._rc_keystream),
            ("retained_scan", self._rc_retained),
        ):
            before = LEDGER.counts()
            t0 = time.perf_counter()
            try:
                detail = fn()
            except Exception as e:
                traceback.print_exc()
                self.fail(f"roll-call {name}: {type(e).__name__}: {e}")
                detail = {"error": f"{type(e).__name__}: {e}"}
            after = LEDGER.counts()
            detail["seconds"] = round(time.perf_counter() - t0, 3)
            detail["compiled"] = {
                k: n - before.get(k, 0)
                for k, n in after.items()
                if n != before.get(k, 0)
            }
            results[name] = detail
            self.note(f"roll-call {name}: {detail}")
        self.out["roll_call"] = results

    def _rc_match_kernels(self) -> dict:
        """flat_match, flat_match_ranges, flat_match_packed,
        flat_match_compact and scatter_rows on a TpuMatcher over the
        served trie — the class the served snapshot is, at its width —
        vs ``TopicsIndex.subscribers``."""
        import jax.numpy as jnp
        import numpy as np

        from mqtt_tpu.ops.flat import flat_match
        from mqtt_tpu.ops.hashing import tokenize_topics
        from mqtt_tpu.ops.matcher import TpuMatcher, expand_sids
        from mqtt_tpu.topics import Subscribers

        rng = self.rng
        index = self.topics_index
        if index is None:  # the served leg died before it had a trie
            index, _gen = build_cfg2(self.args.subs, rng)
        tm = TpuMatcher(index)
        arrays, flat = tm.device_arrays, tm.index
        topics = [cfg2_topic(rng) for _ in range(ROLLCALL_BATCH)]
        host = [canon(index.subscribers(t)) for t in topics]
        tok = tokenize_topics(topics, flat.max_levels, flat.salt)
        tokens, too_deep = tok[:4], tok[4]
        detail: dict = {"batch": len(topics), "table": list(flat.table.shape)}

        def from_sids(kernel: str, sids_of) -> None:
            """``sids_of(i)``: topic i's sid list, None if host-routed."""
            bad = routed = 0
            for i, want in enumerate(host):
                sids = None if too_deep[i] else sids_of(i)
                if sids is None:
                    routed += 1
                elif canon(expand_sids(flat.subs, sids, Subscribers())) != want:
                    bad += 1
            detail[kernel] = {"mismatches": bad, "host_routed": routed}
            if bad or routed > len(host) // 10:
                self.fail(f"roll-call {kernel}: {detail[kernel]}")

        def from_matcher(kernel: str, batch: list, want: list) -> None:
            bad = sum(
                canon(got) != w for got, w in zip(tm.match_topics(batch), want)
            )
            detail[kernel] = {"mismatches": bad}
            if bad:
                self.fail(f"roll-call {kernel}: {bad} mismatches")

        ids, _totals, ovf = (
            np.asarray(a)
            for a in flat_match(
                *arrays, *(jnp.asarray(a) for a in tokens),
                max_levels=flat.max_levels, out_slots=tm.out_slots,
            )
        )
        from_sids(
            "flat_match",
            lambda i: None if ovf[i] else [int(s) for s in ids[i] if s >= 0],
        )
        starts, cnts, _totals, ovf_r = (
            np.asarray(a) for a in tm.match_tokens(*tokens)
        )
        from_sids(
            "flat_match_ranges",
            lambda i: None if ovf_r[i] else [
                s
                for s0, c in zip(starts[i].tolist(), cnts[i].tolist())
                for s in range(s0, s0 + c)
            ],
        )
        tm.compact = False  # the padded-ranges transfer
        from_matcher("flat_match_packed", topics, host)
        tm.compact, tm.compact_capacity = True, len(topics) * flat.window
        compacted = tm.stats.compact_batches
        from_matcher("flat_match_compact", topics, host)
        if tm.stats.compact_batches != compacted + 1:
            self.fail("roll-call flat_match_compact: the batch did not compact")

        # scatter_rows: fold new filters into the uploaded table in place.
        # A fold may legitimately ask for a rebuild instead (a new entry
        # landing in a full bucket, ~0.3% per filter at this load
        # factor): rebuild as the contract demands and fold another set
        for attempt in range(4):
            new = [f"region{i}/rollcall{attempt}/+" for i in range(8)]
            for i, flt in enumerate(new):
                index.subscribe(f"rollcall{i}", Subscription(filter=flt, qos=1))
            if tm.fold(set(new)):
                break
            tm.rebuild()
        else:
            self.fail("roll-call scatter_rows: no fold took the in-place path")
        reach = [flt.replace("+", f"metric{i}") for i, flt in enumerate(new)]
        from_matcher(
            "scatter_rows", reach, [canon(index.subscribers(t)) for t in reach]
        )
        return detail

    def _rc_predicates(self) -> dict:
        """rules_eval at the predicate plane's shape (one distinct rule per
        predicated subscription, scaled with --subs) vs
        ``eval_rule_host``; agg_reduce vs ``host_reduce_window``."""
        import numpy as np

        from mqtt_tpu.ops.predicates import (
            OP_MAX,
            OP_MEAN,
            OP_MIN,
            agg_reduce_batch,
        )
        from mqtt_tpu.predicates import (
            PredicateEngine,
            eval_rule_host,
            host_reduce_window,
        )

        rng = self.rng
        n_rules = max(1000, min(100_000, self.args.subs // 10))
        eng = PredicateEngine(oracle_sample=0)
        rules = [
            eng.register("$GT{v:%.9f}" % rng.random()) for _ in range(n_rules)
        ]
        bad = 0
        for rate in (0.1, 0.5, 0.9):
            payload = json.dumps({"v": rate}).encode()
            feats = [eng.features_for(payload) for _ in range(64)]
            issued = eng.eval_batch_async(feats)
            resolved = issued() if issued is not None else None
            if resolved is None:
                raise RuntimeError("rules_eval did not run on the device")
            row = resolved[0][0]
            for rule in rules:
                bit = bool((row[rule.idx >> 5] >> np.uint32(rule.idx & 31)) & 1)
                bad += bit != eval_rule_host(rule.spec, payload)
        detail = {"rules": n_rules, "rules_eval": {"mismatches": int(bad)}}

        windows = [
            (
                (OP_MEAN, OP_MAX, OP_MIN)[w % 3],
                [rng.uniform(-1e3, 1e3) for _ in range(128)],
            )
            for w in range(64)
        ]
        agg_bad = 0
        for got, (op, values) in zip(agg_reduce_batch(windows), windows):
            want = host_reduce_window(op, values)
            tol = 1e-5 * max(1.0, abs(want)) if op == OP_MEAN else 0.0
            agg_bad += abs(float(got) - want) > tol
        detail["agg_reduce"] = {"windows": len(windows), "mismatches": int(agg_bad)}
        if bad or agg_bad:
            self.fail(f"roll-call predicates: {detail}")
        return detail

    def _rc_keystream(self) -> dict:
        """The AES-128-CTR keystream kernel vs the host AES."""
        import numpy as np

        from mqtt_tpu.ops.recrypt import (
            ctr_counters,
            expand_key,
            host_keystream,
            keystream_async,
        )

        rng = self.rng
        table = np.stack([expand_key(rng.randbytes(16)) for _ in range(64)])
        kidx = np.array(
            [rng.randrange(len(table)) for _ in range(ROLLCALL_BATCH)],
            dtype=np.int32,
        )
        counters = ctr_counters(rng.randbytes(12), len(kidx))
        got = keystream_async(table, kidx, counters)()
        bad = int((got != host_keystream(table, kidx, counters)).any(axis=1).sum())
        if bad:
            self.fail(f"roll-call keystream: {bad} of {len(kidx)} blocks differ")
        return {"blocks": len(kidx), "keys": len(table), "mismatches": bad}

    def _rc_retained(self) -> dict:
        """The retained scan (the flat kernel run in reverse) vs the
        host walk ``TopicsIndex.messages``."""
        from mqtt_tpu.ops.retained import RetainedMatchEngine
        from mqtt_tpu.topics import TopicsIndex

        rng = self.rng
        n = max(1000, min(100_000, self.args.subs // 10))
        index = TopicsIndex()
        names = sorted({cfg2_topic(rng) for _ in range(n)})
        index.retain_bulk(
            [
                Packet(
                    fixed_header=FixedHeader(type=PUBLISH, retain=True),
                    topic_name=t,
                    payload=b"r",
                )
                for t in names
            ]
        )
        engine = RetainedMatchEngine(index, oracle_sample=0)
        engine.reseed()
        a, b, c = names[0].split("/")
        filters = [f"{a}/+/{c}", f"{a}/#", f"+/{b}/+", "#"]
        bad = 0
        for flt in filters:
            got = engine.match(flt)
            want = sorted(pk.topic_name for pk in index.messages(flt))
            bad += got is None or sorted(got) != want
        stats = engine.stats()
        if bad or stats["device_matches"] != len(filters):
            self.fail(f"roll-call retained scan: {bad} mismatches, {stats}")
        return {
            "corpus": len(names),
            "filters": len(filters),
            "mismatches": bad,
            "device_matches": stats["device_matches"],
        }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--subs", type=int, default=1_000_000)
    ap.add_argument("--publishes", type=int, default=30_000)
    ap.add_argument(
        "--expect-platform", default="tpu", choices=("tpu", "cpu"),
        help="cpu is for the tiny dry run and the tier-1 test only",
    )
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(HARD_TIMEOUT_S, exit=True)

    from mqtt_tpu import native
    from mqtt_tpu.ops.backend import device_summary, ensure_compile_cache

    device = device_summary()
    if device["platform"] != args.expect_platform:
        print(
            "chip_smoke: jax.devices()[0].platform is "
            f"{device['platform']!r}, expected {args.expect_platform!r}; "
            "this smoke proves the chip path and has no other mode",
            file=sys.stderr,
        )
        return 2

    import jax
    import jaxlib

    from mqtt_tpu.ops.devicestats import LEDGER

    smoke = Smoke(args, device)
    out = smoke.out
    out["versions"] = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        import libtpu

        out["versions"]["libtpu"] = libtpu.__version__
    except ImportError:
        out["versions"]["libtpu"] = None
    out["cache_dir"] = ensure_compile_cache()
    out["cache_entries_before"] = cache_entries(out["cache_dir"])
    out["native"] = native.status()
    for name, st in out["native"].items():
        if not st["loaded"]:
            smoke.fail(f"native module {name} not loaded")

    t_all = time.perf_counter()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        try:
            asyncio.run(smoke.served_leg())
        except Exception as e:
            traceback.print_exc()
            smoke.fail(f"served leg: {type(e).__name__}: {e}")
        smoke.roll_call()
    out["warnings"] = sorted(
        {str(w.message).split("\n")[0][:160] for w in caught}
    )
    events = LEDGER.events()
    out["compiles"] = [
        {"kernel": e["kernel"], "shape": e["shape_bucket"], "seconds": e["seconds"]}
        for e in events
    ]
    out["compile_s_total"] = round(sum(e["seconds"] for e in events), 3)
    table = out["roll_call"]["match_kernels"].get("table", ["?"])
    for kernel in ("flat_match_compact", "flat_match_packed"):
        if not any(
            e["kernel"] == kernel
            and e["shape_bucket"].startswith(f"{table[0]}x")
            for e in events
        ):
            smoke.fail(f"no {kernel} compile at the served table's width")
    out["cache_entries_after"] = cache_entries(out["cache_dir"])
    out["wall_s"] = round(time.perf_counter() - t_all, 3)
    out["failures"] = smoke.failures
    out["claim"] = None
    print(json.dumps(out), flush=True)
    verdict = {
        "ok": not smoke.failures,
        "device": {
            "platform": device["platform"],
            "kind": device["device_kind"],
            "count": device["n_devices"],
        },
    }
    print(json.dumps(verdict), flush=True)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
