#!/usr/bin/env python3
"""One run of one benchmark cell: load, warm, measure, check, print.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is ``<config>.<mix>`` in ``BENCHMARK.json``. This process holds
the chip and runs the broker — ``Server(Options(**broker_options))``
with one TCP listener on ``127.0.0.1:0`` — on its own event loop; the
load comes from ``generator.py`` children over loopback and never
touches JAX. The window drives the listener and nothing else. Set-up is
process start to the window's first instant. After the window: drain,
read the device's memory peak, ask the served matcher for a sample of
the published topics' whole subscriber sets, stop the broker, and only
then run the plain reference (``reference.py``) over everything the
sockets saw.

The last stdout line is the result object the driver reads. It fails —
non-zero, no result — unless JAX's first device is a TPU; ``--rehearse``
(CPU allowed, the config's ``rehearse_params``) is for the sandbox and
its numbers are never measurements.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from array import array  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import reference  # noqa: E402

WORK = os.path.join(HERE, ".work")  # traces; listed in .gitignore
WARM_S = 2.0  # one warm pass of the cell's own loop
MAX_WARM_PASSES = 8
TRACE_S = 3.0  # the traced slice, the window's last seconds
DRAIN_WAIT_S = 60.0  # an answer that comes late is late, not wrong
QUIET_S = 0.5
now_ns = time.monotonic_ns


def note(msg: str) -> None:
    print(f"# [{time.monotonic() - T_START:7.2f}s] {msg}", file=sys.stderr, flush=True)


def load_cell(workload: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload in cells:
        cell = cells[workload]
        config = next(c for c in manifest["configs"] if c["name"] == cell["config"])
        config_file = os.path.join(ROOT, config["file"])
    else:
        # not a cell of BENCHMARK.json: an experiment over files that are
        # there (a cell left out over a fault of the program, a new mix)
        name, _, mix_name = workload.rpartition(".")
        cell = {"name": workload, "config": name, "traffic": mix_name, "chips": 1,
                "experiment": True}
        config_file = os.path.join(HERE, "configs", name + ".json")
        if not os.path.isfile(config_file):
            raise SystemExit(f"no cell {workload!r}: {sorted(cells)}")
        note(f"{workload} is no cell of BENCHMARK.json: an experiment")
    with open(config_file, encoding="utf-8") as f:
        config_body = json.load(f)
    with open(
        os.path.join(HERE, "traffic", cell["traffic"] + ".json"), encoding="utf-8"
    ) as f:
        mix = json.load(f)

    def mine(metrics):
        return [
            m for m in metrics if cell["name"] in m.get("workloads", [cell["name"]])
        ]

    return {
        "cell": cell, "config": config_body, "mix": mix,
        "end_to_end": mine(manifest["end_to_end"]),
        "per_layer": mine(manifest["per_layer"]),
    }


class Child:
    """One generator process and its line protocol."""

    def __init__(self, index: int) -> None:
        self.index = index
        self.n_publishers = 0

    async def start(self, job: dict) -> None:
        self.proc = await asyncio.create_subprocess_exec(
            sys.executable, os.path.join(HERE, "generator.py"),
            stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
            limit=1 << 26,
        )
        self.n_publishers = len(job["publishers"])
        self.send(job)

    def send(self, obj: dict) -> None:
        self.proc.stdin.write(json.dumps(obj).encode() + b"\n")

    async def recv(self, wait_s: float = 300.0) -> tuple:
        line = await asyncio.wait_for(self.proc.stdout.readline(), wait_s)
        if not line:
            raise RuntimeError(f"generator {self.index} died")
        out = json.loads(line)
        blobs = [
            (name, await self.proc.stdout.readexactly(size))
            for name, size in out.pop("blobs", [])
        ]
        return out, blobs

    async def stop(self) -> None:
        if self.proc.returncode is None:
            self.proc.kill()
        await self.proc.wait()


class Run:
    def __init__(self, args, spec: dict) -> None:
        self.args, self.spec = args, spec
        self.mix, self.config = spec["mix"], spec["config"]
        self.params = self.config["rehearse_params" if args.rehearse else "params"]
        self.deployment = importlib.import_module(
            "deployments." + self.config["deployment"]
        )
        self.children: list = []
        self.trace = None

    # -- set-up ------------------------------------------------------------

    async def start_broker(self) -> None:
        from mqtt_tpu.hooks.auth import AllowHook
        from mqtt_tpu.listeners import Config as LConfig
        from mqtt_tpu.listeners.tcp import TCP
        from mqtt_tpu.server import Options, Server

        self.srv = srv = Server(Options(**self.config["broker_options"]))
        srv.add_hook(AllowHook())
        srv.add_listener(TCP(LConfig(type="tcp", id="bench", address="127.0.0.1:0")))
        await srv.serve()
        self.port = int(srv.listeners.get("bench").address().rsplit(":", 1)[1])
        self.matcher, self.stage, self.stats = srv.matcher, srv._stage, srv.matcher.stats

    def load(self) -> None:
        """The deployment's subscriptions, by the durable restore's route."""
        from mqtt_tpu.packets import Subscription
        from mqtt_tpu.staging import bulk_register

        t = time.monotonic()
        bulk_register(
            self.srv.topics,
            (
                (client, Subscription(filter=flt, qos=qos))
                for client, flt, qos in self.plan["subscriptions"]
            ),
        )
        t1 = time.monotonic()
        self.matcher.flush()
        note(
            f"loaded {len(self.plan['subscriptions'])} subscriptions in "
            f"{t1 - t:.1f}s, flush {time.monotonic() - t1:.1f}s, "
            f"{self.stats.rebuilds} rebuilds"
        )

    async def start_children(self) -> None:
        plan, n = self.plan, int(self.mix["generator_procs"])
        subs = plan["subscriptions"]
        jobs = [
            {
                "port": self.port, "seed": self.args.seed, "mix": self.mix,
                "deployment": self.config["deployment"], "params": self.params,
                "subscribers": [], "publishers": [],
            }
            for _ in range(n)
        ]
        home: dict = {}  # a client that publishes AND subscribes is one connection
        for i, row in enumerate(plan["live"]):
            client, flt, qos = subs[row]
            home[client] = i % n
            jobs[i % n]["subscribers"].append([row, client, flt, qos])
        for k, client in enumerate(plan["publishers"]):
            jobs[home.get(client, k % n)]["publishers"].append([k, client])
        self.children = [Child(i) for i in range(n)]
        await asyncio.gather(*(c.start(j) for c, j in zip(self.children, jobs)))
        ready = await asyncio.gather(*(c.recv() for c in self.children))
        self.matcher.flush()  # the live clients' subscriptions fold in
        note(f"{sum(r[0]['ready'] for r in ready)} live connections")

    async def quiesce(self) -> None:
        """Until the stage holds nothing (the smoke's wait)."""
        stage = self.stage
        deadline = time.monotonic() + DRAIN_WAIT_S
        quiet_since = None
        while time.monotonic() < deadline:
            if stage is None or (
                stage.pending_depth == 0 and stage.inflight_batches == 0
            ):
                quiet_since = quiet_since or time.monotonic()
                if time.monotonic() - quiet_since >= 0.05:
                    return
            else:
                quiet_since = None
            await asyncio.sleep(0.01)
        note("the stage did not drain in time")

    async def ask_all(self, cmd_for) -> list:
        for c in self.children:
            c.send(cmd_for(c))
        return await asyncio.gather(*(c.recv() for c in self.children))

    def run_cmd(self, seconds: float) -> tuple:
        t0 = now_ns() + 300_000_000
        cmd = {
            "cmd": "run", "t0_ns": t0, "seconds": seconds,
            "rate_per_s": self.mix.get(
                "rehearse_rate_per_s" if self.args.rehearse else "rate_per_s"
            ),
            "n_publishers": len(self.plan["publishers"]),
        }
        return t0, cmd

    async def warm(self) -> None:
        """Walk the batch buckets the cell can reach, then its own loop,
        until a whole pass compiles nothing."""
        from mqtt_tpu.ops.devicestats import LEDGER

        total = len(self.plan["publishers"])
        procs = len(self.children)
        for n_pass in range(1, MAX_WARM_PASSES + 1):
            before = LEDGER.total()
            for n in self.mix.get("warm_ladder", []):
                frames = -(-n // total)
                used = max(1, n // frames)
                await self.ask_all(
                    lambda c: {
                        "cmd": "burst", "frames": frames,
                        "publishers": min(c.n_publishers, -(-(used - c.index) // procs)),
                    }
                )
                await self.quiesce()
            _t0, cmd = self.run_cmd(WARM_S)
            await self.ask_all(lambda c: cmd)
            await self.quiesce()
            if LEDGER.total() == before:
                note(f"warm after {n_pass} passes, {before} first-signature calls")
                return
        note(f"still compiling after {MAX_WARM_PASSES} warm passes")

    # -- the window ----------------------------------------------------------

    def counters(self) -> dict:
        from mqtt_tpu.ops.devicestats import LEDGER

        s = self.stats
        tele = self.srv.telemetry
        gauges = self.matcher.breaker_gauges()
        return {
            "t_ns": now_ns(), "cpu_s": time.process_time(),
            "topics": s.topics, "batches": s.batches,
            "host_fallbacks": s.host_fallbacks, "host_fast": s.host_fast,
            "d2h_bytes": s.d2h_bytes, "overflows": s.overflows,
            "compact_batches": s.compact_batches,
            "compact_overflows": s.compact_overflows,
            "rebuilds": s.rebuilds, "folds": s.folds,
            "stage_fallbacks": sum(int(c.value) for c in tele.fallback.values())
            if tele is not None else 0,
            "messages_dropped": self.srv.info.messages_dropped,
            "breaker_fallback_topics": gauges.get("fallback_topics", 0),
            "breaker_trips": gauges.get("trips", 0),
            "compiles": LEDGER.total(),
        }

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}

    async def sleep_until(self, t_ns: int) -> None:
        await asyncio.sleep(max(0.0, (t_ns - now_ns()) / 1e9))

    async def window(self) -> None:
        seconds = float(self.args.seconds)
        t0, cmd = self.run_cmd(seconds)
        t1 = t0 + int(seconds * 1e9)
        self.setup_s = t0 / 1e9 - T_START
        for c in self.children:
            c.send(cmd)
        await self.sleep_until(t0)
        c0 = self.counters()
        if self.args.trace:
            await self.sleep_until(t1 - int(min(TRACE_S, seconds / 2) * 1e9))
            await self.traced_slice(t1)
        await self.sleep_until(t1)
        c1 = self.counters()
        self.window_counters = self.delta(c0, c1)
        self.window_s = (c1["t_ns"] - c0["t_ns"]) / 1e9
        self.replies = await asyncio.gather(*(c.recv() for c in self.children))
        note(f"window closed: {self.window_counters}")

    async def traced_slice(self, t1: int) -> None:
        import jax

        shutil.rmtree(WORK, ignore_errors=True)
        os.makedirs(WORK, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(WORK, profiler_options=opts)
        a = self.counters()
        await self.sleep_until(t1)
        b = self.counters()
        jax.profiler.stop_trace()
        self.trace = {
            "slice_s": (b["t_ns"] - a["t_ns"]) / 1e9, "counters": self.delta(a, b),
        }

    # -- after the window ------------------------------------------------------

    async def collect(self) -> None:
        """Everything the sockets saw, once nothing more is due."""
        await self.quiesce()
        finished = await self.ask_all(
            lambda c: {"cmd": "finish", "quiet_s": QUIET_S, "wait_s": DRAIN_WAIT_S}
        )
        self.received: dict = {}
        self.sent_qos: dict = {}
        self.malformed = self.qos1_sent = self.acks = 0
        for out, blobs in finished:
            blobs = iter(blobs)
            for sub in out["subscribers"]:
                arr = array("Q")
                arr.frombytes(next(blobs)[1])
                self.received[sub["client"]] = arr
                self.malformed += sub["malformed"]
            for pub in out["publishers"]:
                self.sent_qos[pub["publisher"]] = next(blobs)[1]
                self.qos1_sent += pub["qos1_sent"]
                self.acks += pub["acks"]

    def device_memory_peak(self) -> int:
        import jax

        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.devices()[: self.spec["cell"]["chips"]]
        ]
        return int(max(peaks))

    def sample_topics(self) -> list:
        """Topics the publishers sent, the first of each stream."""
        want = int(self.config.get("match_plane_sample") or 0)
        if not want:
            return []
        n_pub = len(self.plan["publishers"])
        each = -(-want // n_pub)
        out = []
        for k in range(n_pub):
            stream = self.deployment.topics(self.params, self.args.seed, k)
            out += [next(stream) for _ in range(min(each, len(self.sent_qos[k])))]
        return out[:want]

    async def match_plane_answers(self, topics: list) -> list:
        """The served matcher's whole answer for ``topics``: the object,
        the table and the compiled programs the window drove."""
        if not topics:
            return []
        results = await asyncio.get_running_loop().run_in_executor(
            None, self.matcher.match_topics, topics
        )
        answers = []
        for r in results:
            if hasattr(r, "materialize"):
                r = r.materialize()
            answers.append({c: sub.qos for c, sub in r.subscriptions.items()})
        return answers

    def sent(self):
        """Every publish every publisher sent, replayed from the seed."""
        for k, qos_bytes in sorted(self.sent_qos.items()):
            stream = self.deployment.topics(self.params, self.args.seed, k)
            for seq, qos in enumerate(qos_bytes):
                yield k, seq, next(stream), qos

    def check(self, sample: list, answers: list) -> dict:
        """The comparison that decides ``correct``. With ``--control`` the
        reference stands in the program's place with one stated guarantee
        broken, and must come out as not correct."""
        subs = self.plan["subscriptions"]
        live = reference.FilterSet(subs[row] for row in self.plan["live"])
        expected = reference.expected_deliveries(live, self.sent())
        received = {client: list(arr) for client, arr in self.received.items()}
        full = None
        control = self.config["control"] if self.args.control else {}
        if sample or control.get("fanout_cap") is not None:
            full = reference.FilterSet(subs)
        if control:
            received, answers = reference.control_answers(
                control, live, full, expected, self.sent(), sample
            )
        deliveries = reference.compare_deliveries(expected, received)
        n_expected = sum(len(v) for by in expected.values() for v in by.values())
        n_sent = sum(len(q) for q in self.sent_qos.values())
        unacked = self.qos1_sent - self.acks
        compared = {
            "socket_answer_errors": {
                "value": deliveries["errors"] + self.malformed + unacked, "limit": 0,
            },
        }
        if sample:
            sets = reference.compare_match_sets(full, sample, answers)
            compared["match_set_errors"] = {"value": sets["errors"], "limit": 0}
            note(f"match plane: {sets}")
        note(
            f"sockets: {n_sent} publishes, {n_expected} deliveries due, "
            f"{deliveries}, malformed {self.malformed}, QoS1 unacked {unacked}"
        )
        return {
            "compared": compared,
            "attempted": n_sent + n_expected,
            "failed": unacked + deliveries["missing"],
        }

    # -- metrics ----------------------------------------------------------------

    def end_to_end(self) -> dict:
        # the children count inside [t0, t0 + seconds) on the shared clock;
        # the parent's own snapshots lag when its loop is busy, so the
        # window's length is the one that was asked for, not window_s
        seconds = float(self.args.seconds)
        finished = sum(r[0]["finished"] for r in self.replies)
        delivered = sum(r[0]["delivered"] for r in self.replies)
        delays = self.window_samples("delays")
        values = {
            "publish_per_s": finished / seconds,
            "delivered_per_s": delivered / seconds,
            "setup_s": self.setup_s,
        }
        if len(delays) >= 100:
            cuts = statistics.quantiles(delays, n=100, method="inclusive")
            values["delay_p50_ms"] = cuts[49] / 1e6
            values["delay_p99_ms"] = cuts[98] / 1e6
            note(f"{len(delays)} delay samples, max {max(delays) / 1e6:.3f} ms")
        note(f"window {seconds:.3f}s (the parent's counters span {self.window_s:.3f}s): "
             f"{finished} publishes finished, {delivered} delivered")
        return values

    def window_samples(self, which: str) -> array:
        """The children's nanosecond samples of the window, merged."""
        merged = array("q")
        for _out, blobs in self.replies:
            for name, raw in blobs:
                if name == which:
                    merged.frombytes(raw)
        return merged

    def layer_context(self) -> dict:
        return {
            "seconds": float(self.args.seconds),
            "counters": self.window_counters,
            "generators": [r[0] for r in self.replies],
            "late_ns": self.window_samples("late"),
            "delays_ns": self.window_samples("delays"),
            "trace": self.trace,
            "index": self.index_shape,
            "device_kind": self.device["kind"],
        }

    def read_index_shape(self) -> dict:
        """P (patterns probed per topic) and L (levels) of the built
        index, for the roofline's byte count."""
        delta = getattr(self.matcher, "inner", self.matcher)
        snap = getattr(delta, "_snap", delta)
        flat = getattr(snap, "index", None)
        if flat is not None and hasattr(flat, "pat_depth"):
            return {
                "patterns": int(flat.pat_depth.shape[0]),
                "levels": int(flat.max_levels),
                "table_bytes": int(self.stats.table_bytes),
            }
        return {}


def per_layer_metrics(spec: dict, ctx: dict) -> dict:
    """Each per-layer metric is a reader of its own, found by name:
    ``layer_metrics/<name up to the first dot>.py`` with ``read(ctx)``;
    ``ctx["metric"]`` is the whole name, for a reader that serves several.
    A reader that finds nothing to read returns None and the metric is
    left out of the line."""
    out = {}
    for m in spec["per_layer"]:
        module = importlib.import_module("layer_metrics." + m["name"].split(".")[0])
        value = module.read({**ctx, "metric": m["name"]})
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


async def run_cell(args, spec: dict, device: dict, sabotage=None) -> dict:
    run = Run(args, spec)
    run.device = device
    run.plan = run.deployment.plan(
        run.params, args.seed, run.mix.get("connections")
    )
    note(f"plan: {len(run.plan['subscriptions'])} subscriptions, "
         f"{len(run.plan['live'])} live, {len(run.plan['publishers'])} publishers")
    await run.start_broker()
    try:
        if sabotage is not None:
            sabotage(run.srv)
        run.load()
        await run.start_children()
        await run.warm()
        await run.window()
        await run.collect()
        note("everything due has arrived")
        memory_peak = run.device_memory_peak()
        run.index_shape = run.read_index_shape()
        sample = run.sample_topics()
        answers = await run.match_plane_answers(sample)
        note(f"the match plane answered {len(sample)} sampled topics")
    finally:
        for c in run.children:
            await c.stop()
        await run.srv.close()
    note("broker and generators stopped")
    ctx = run.layer_context() if args.trace else None
    values = run.end_to_end()
    t = time.monotonic()
    verdict = run.check(sample, answers)
    note(f"reference took {time.monotonic() - t:.1f}s")

    device_block = {**device, "memory_peak_bytes": memory_peak}
    result = {
        "correct": all(c["value"] <= c["limit"] for c in verdict["compared"].values()),
        "attempted": verdict["attempted"],
        "failed": verdict["failed"],
    }
    if args.trace:
        import trace_reduce

        reduced = trace_reduce.reduce_dir(WORK)
        if args.keep_trace:
            shutil.copytree(WORK, args.keep_trace, dirs_exist_ok=True)
        shutil.rmtree(WORK, ignore_errors=True)
        ctx["trace"] = {**run.trace, **reduced}
        result["metrics"] = per_layer_metrics(spec, ctx)
        device_block["busy_s"] = reduced["busy_s"]
        device_block["window_s"] = run.trace["slice_s"]
        result["device"] = device_block
        result["breakdown"] = {
            "device_ops": reduced["device_ops"][:10],
            "idle_gaps": reduced["idle_gaps"][:10],
        }
    else:
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in spec["end_to_end"]
        }
        if spec["cell"].get("experiment"):  # whatever could be read
            result["metrics"] = {
                k: {"value": v, "unit": ""} for k, v in values.items() if v
            }
        result["device"] = device_block
    result["diagnostics"] = {
        "window_compiles": run.window_counters["compiles"],
        "window_counters": {
            k: v for k, v in run.window_counters.items() if k not in ("t_ns",)
        },
        "index": run.index_shape,
        "seed": args.seed, "rehearse": bool(args.rehearse),
        "control": bool(args.control),
    }
    result["compared"] = verdict["compared"]
    return result


def find_device(chips: int, rehearse: bool) -> dict:
    """The accelerator as JAX reports it, or no run: a measurement path
    that finds no chip fails, it does not fall back."""
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu" and not rehearse:
        raise SystemExit(
            f"benchmark: jax.devices()[0].platform is {d.platform!r}, not 'tpu'; "
            "no accelerator, no result (--rehearse is for the sandbox)"
        )
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, JAX has {len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def main(argv=None, sabotage=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU allowed, tiny deployment: never a measurement")
    ap.add_argument("--keep-trace", default="",
                    help="copy the traced slice's .xplane.pb into this directory")
    ap.add_argument("--control", action="store_true",
                    help="put the reference with one guarantee broken in the "
                    "program's place: must print correct false")
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)

    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    want = 4096 if hard == resource.RLIM_INFINITY else min(hard, 65536)
    if soft < want:
        resource.setrlimit(resource.RLIMIT_NOFILE, (want, hard))

    sys.path.insert(0, ROOT)
    try:
        import mqtt_tpu  # noqa: F401
    except ImportError as e:
        raise SystemExit(f"benchmark: the program is not in this checkout: {e}")
    device = find_device(spec["cell"]["chips"], args.rehearse)
    note(f"device {device}")
    result = asyncio.run(run_cell(args, spec, device, sabotage))
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    # Every child has been waited for and the line is out. Leave without
    # tearing down a million-subscription trie node by node: that took
    # 15 s of every run, which every later check would pay.
    sys.stderr.flush()
    os._exit(rc)
