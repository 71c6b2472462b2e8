#!/usr/bin/env python3
"""One load-generator process: wire-true MQTT v4 clients on loopback TCP.

Started by ``run.py`` as a child; never imports ``jax`` or ``mqtt_tpu``
(the parent holds the chip and runs the broker). The parent speaks to it
in JSON lines on stdin and reads JSON lines, each followed by the raw
blobs its ``"blobs"`` key sizes, from stdout.

Every connection is one MQTT client: a live subscriber, a publisher, or
both (the stresser's clients). What a traffic mix can ask for is one of
three loops, all parameters from the mix's data file:

``closed``    each publisher keeps one chunk in flight: the chunk's last
              frame is QoS1 and the next chunk follows its PUBACK.
``echo``      each publisher is its own subscriber: it writes a chunk and
              the next one once every message of it has come back.
``open``      publish ``j`` of the run is due at ``t0 + j / rate`` on
              publisher ``j % P``, whatever the broker does; its payload
              carries the DUE time.

One clock everywhere: ``time.monotonic_ns`` (CLOCK_MONOTONIC is the
machine's, so the parent's window and the children's stamps agree).
"""

from __future__ import annotations

import asyncio
import importlib
import json
import os
import struct
import sys
import time
from array import array

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from reference import pack_delivery, topic_tag  # noqa: E402

HEAD = struct.Struct(">IQq")  # publisher, seq, due (monotonic ns)
TICK_S = 0.001  # the open loop's send tick
WAIT_S = 120.0  # any single wait on the broker
now_ns = time.monotonic_ns


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _str(s: str) -> bytes:
    b = s.encode()
    return len(b).to_bytes(2, "big") + b


def connect_bytes(client: str) -> bytes:
    body = _str("MQTT") + bytes((4, 0x02)) + (600).to_bytes(2, "big") + _str(client)
    return b"\x10" + _varint(len(body)) + body


def subscribe_bytes(pid: int, flt: str, qos: int) -> bytes:
    body = pid.to_bytes(2, "big") + _str(flt) + bytes((qos,))
    return b"\x82" + _varint(len(body)) + body


class Conn:
    """One client connection and everything it sent and saw."""

    def __init__(self, client: str) -> None:
        self.client = client
        self.row = -1  # subscription row, -1: subscribes to nothing
        self.flt, self.sub_qos = "", 0
        self.publisher = -1  # publisher index, -1: publishes nothing
        self.received = array("Q")  # packed deliveries, arrival order
        self.delivered_in_window = self.delivered_total = 0
        self.malformed = 0
        self.sent_qos = bytearray()  # QoS of publish seq, by seq
        self.finished_in_window = 0
        self.qos1_sent = self.acks = 0
        self._pid = 0
        self._woke = asyncio.Event()  # set after every socket read
        self.topics = None
        self.reader_task = None
        self.last_rx_ns = 0

    async def open(self, port: int) -> None:
        self.reader, self.writer = await asyncio.open_connection("127.0.0.1", port)
        self.writer.write(connect_bytes(self.client))
        ack = await asyncio.wait_for(self.reader.readexactly(4), WAIT_S)
        if ack[0] >> 4 != 2 or ack[3] != 0:
            raise RuntimeError(f"{self.client}: CONNACK {ack.hex()}")
        if self.row >= 0:
            self.writer.write(subscribe_bytes(1, self.flt, self.sub_qos))
            ack = await asyncio.wait_for(self.reader.readexactly(5), WAIT_S)
            if ack[0] >> 4 != 9 or ack[4] != self.sub_qos:
                raise RuntimeError(f"{self.client}: SUBACK {ack.hex()}")

    # -- publishing --------------------------------------------------------

    def frames(self, n: int, g: "Generator", due_ns: int, last_qos1: bool) -> bytes:
        """The next ``n`` publishes as one buffer."""
        out = bytearray()
        every, filler, pub = g.qos1_every, g.filler, self.publisher
        seq = len(self.sent_qos)
        topics = self.topics
        for i in range(n):
            topic = next(topics).encode()
            qos = int(
                (every and seq % every == 0) or (last_qos1 and i == n - 1)
            )
            body_len = 2 + len(topic) + 2 * qos + HEAD.size + len(filler)
            out.append(0x30 | (qos << 1))
            out += _varint(body_len)
            out += len(topic).to_bytes(2, "big")
            out += topic
            if qos:
                self._pid = self._pid % 65000 + 1
                out += self._pid.to_bytes(2, "big")
                self.qos1_sent += 1
            out += HEAD.pack(pub, seq, due_ns)
            out += filler
            self.sent_qos.append(qos)
            seq += 1
        return bytes(out)

    async def send_chunk(self, n: int, g: "Generator") -> None:
        """Closed loop's unit: ``n`` frames, the last QoS1, then every
        PUBACK due."""
        self.writer.write(self.frames(n, g, now_ns(), True))
        while self.acks < self.qos1_sent:
            self._woke.clear()
            await asyncio.wait_for(self._woke.wait(), WAIT_S)

    # -- receiving ---------------------------------------------------------

    async def read_loop(self, g: "Generator") -> None:
        buf = bytearray()
        received_append = self.received.append
        read = self.reader.read
        while True:
            data = await read(262144)
            if not data:
                return
            t = now_ns()
            self.last_rx_ns = t
            in_window = g.t0 <= t < g.t1
            buf += data
            pos, n = 0, len(buf)
            acks = None
            while pos + 2 <= n:
                remaining = shift = 0
                vend = pos + 1
                while True:
                    if vend >= n:
                        vend = -1
                        break
                    b = buf[vend]
                    vend += 1
                    remaining |= (b & 0x7F) << shift
                    if not b & 0x80:
                        break
                    shift += 7
                    if shift > 21:
                        raise ValueError(f"{self.client}: malformed varint")
                if vend < 0 or vend + remaining > n:
                    break
                first, end = buf[pos], vend + remaining
                kind = first >> 4
                if kind == 3:  # PUBLISH
                    qos = (first >> 1) & 3
                    tl = (buf[vend] << 8) | buf[vend + 1]
                    at = vend + 2 + tl
                    tag = topic_tag(bytes(buf[vend + 2 : at]))
                    if qos:
                        if acks is None:
                            acks = bytearray()
                        acks += b"\x40\x02" + buf[at : at + 2]
                        at += 2
                    if end - at != g.payload_bytes or buf[end - 1] != 0x78:
                        self.malformed += 1
                    else:
                        pub, seq, due = HEAD.unpack_from(buf, at)
                        received_append(
                            pack_delivery(pub, seq, qos, (first >> 3) & 1, tag)
                        )
                        self.delivered_total += 1
                        if in_window:
                            self.delivered_in_window += 1
                            if g.delays is not None:
                                g.delays.append(t - due)
                elif kind == 4:  # PUBACK
                    self.acks += 1
                pos = end
            del buf[:pos]
            self._woke.set()
            if acks:
                self.writer.write(bytes(acks))


class Generator:
    def __init__(self, job: dict) -> None:
        self.job = job
        mix = job["mix"]
        self.mix = mix
        self.qos1_every = int(mix.get("qos1_every") or 0)
        self.payload_bytes = int(mix["payload_bytes"])
        if self.payload_bytes < HEAD.size + 1:
            raise ValueError(f"payload_bytes must be over {HEAD.size}")
        self.filler = b"x" * (self.payload_bytes - HEAD.size)
        self.t0 = self.t1 = 0
        self.delays = None
        self.late = array("q")
        self.conns: dict = {}
        deployment = importlib.import_module("deployments." + job["deployment"])
        for row, client, flt, qos in job["subscribers"]:
            c = self.conns.setdefault(client, Conn(client))
            c.row, c.flt, c.sub_qos = row, flt, qos
        for k, client in job["publishers"]:
            c = self.conns.setdefault(client, Conn(client))
            c.publisher = k
            c.topics = deployment.topics(job["params"], job["seed"], k)
        self.publishers = sorted(
            (c for c in self.conns.values() if c.publisher >= 0),
            key=lambda c: c.publisher,
        )

    async def connect(self) -> None:
        conns = list(self.conns.values())
        for i in range(0, len(conns), 50):
            await asyncio.gather(*(c.open(self.job["port"]) for c in conns[i : i + 50]))
        for c in conns:
            c.reader_task = asyncio.ensure_future(c.read_loop(self))

    # -- the three loops ----------------------------------------------------

    async def _closed(self, c: Conn, chunk: int) -> None:
        while now_ns() < self.t1:
            await c.send_chunk(chunk, self)
            if self.t0 <= now_ns() < self.t1:
                c.finished_in_window += chunk

    async def _echo(self, c: Conn, chunk: int) -> None:
        while now_ns() < self.t1:
            c.writer.write(c.frames(chunk, self, now_ns(), False))
            while c.delivered_total < len(c.sent_qos):
                c._woke.clear()
                await asyncio.wait_for(c._woke.wait(), WAIT_S)
            if self.t0 <= now_ns() < self.t1:
                c.finished_in_window += chunk

    async def _open(self, rate: float, n_publishers: int, seconds: float) -> None:
        """Publish ``j`` is due at ``t0 + j / rate`` on publisher
        ``j % n_publishers``; this process sends its own publishers'."""
        period = 1e9 / rate
        total = int(rate * seconds)
        nxt = {c.publisher: c.publisher for c in self.publishers}
        while True:
            t = now_ns()
            done = True
            for c in self.publishers:
                j = nxt[c.publisher]
                out = None
                while j < total:
                    due = self.t0 + int(j * period)
                    if due > t:
                        break
                    frame = c.frames(1, self, due, False)
                    out = frame if out is None else out + frame
                    self.late.append(t - due)
                    j += n_publishers
                    c.finished_in_window += 1
                nxt[c.publisher] = j
                if out is not None:
                    c.writer.write(out)
                done = done and j >= total
            if done:
                return
            await asyncio.sleep(TICK_S)

    async def run(self, cmd: dict) -> tuple:
        loop_kind = self.mix["loop"]
        self.t0, seconds = int(cmd["t0_ns"]), float(cmd["seconds"])
        self.t1 = self.t0 + int(seconds * 1e9)
        self.delays = array("q") if loop_kind == "open" else None
        self.late = array("q")
        for c in self.conns.values():
            c.delivered_in_window = c.finished_in_window = 0
        await asyncio.sleep(max(0.0, (self.t0 - now_ns()) / 1e9))
        cpu0 = time.process_time()
        if loop_kind == "open":
            await self._open(
                float(cmd["rate_per_s"]), int(cmd["n_publishers"]), seconds
            )
        else:
            loop = {"closed": self._closed, "echo": self._echo}[loop_kind]
            await asyncio.gather(
                *(loop(c, int(self.mix["chunk"])) for c in self.publishers)
            )
        await asyncio.sleep(max(0.0, (self.t1 - now_ns()) / 1e9))
        cpu_s = time.process_time() - cpu0
        subs = [c for c in self.conns.values() if c.row >= 0]
        out = {
            "finished": sum(c.finished_in_window for c in self.publishers),
            "delivered": sum(c.delivered_in_window for c in subs),
            "per_subscriber": {c.row: c.delivered_in_window for c in subs},
            "cpu_s": cpu_s,
            "blobs": [],
        }
        blobs = []
        for name, arr in (("delays", self.delays), ("late", self.late)):
            if arr is not None:
                out["blobs"].append([name, len(arr) * arr.itemsize])
                blobs.append(arr.tobytes())
        return out, blobs

    async def burst(self, cmd: dict) -> dict:
        """Warm-up's unit: the first ``publishers`` of this process each
        send ``frames`` frames and wait for the PUBACK of the last."""
        who = self.publishers[: int(cmd["publishers"])]
        await asyncio.gather(*(c.send_chunk(int(cmd["frames"]), self) for c in who))
        return {"done": len(who)}

    async def finish(self, cmd: dict) -> tuple:
        """Wait for what is still due — every PUBACK, then the sockets
        quiet for ``quiet_s`` — at most ``wait_s``; late is late, not
        wrong. Then hand everything seen to the parent."""
        quiet_ns = int(float(cmd["quiet_s"]) * 1e9)
        deadline = now_ns() + int(float(cmd["wait_s"]) * 1e9)
        conns = list(self.conns.values())
        while now_ns() < deadline:
            last = max(c.last_rx_ns for c in conns)
            acked = all(c.acks >= c.qos1_sent for c in conns)
            if acked and now_ns() - last >= quiet_ns:
                break
            await asyncio.sleep(0.05)
        out = {"subscribers": [], "publishers": [], "blobs": []}
        blobs = []
        for c in conns:
            if c.row >= 0:
                out["subscribers"].append(
                    {"row": c.row, "client": c.client, "malformed": c.malformed}
                )
                out["blobs"].append(["received", len(c.received) * 8])
                blobs.append(c.received.tobytes())
        for c in self.publishers:
            out["publishers"].append(
                {"publisher": c.publisher, "qos1_sent": c.qos1_sent, "acks": c.acks}
            )
            out["blobs"].append(["sent_qos", len(c.sent_qos)])
            blobs.append(bytes(c.sent_qos))
        return out, blobs


def reply(obj: dict, blobs=()) -> None:
    out = sys.stdout.buffer
    out.write(json.dumps(obj).encode() + b"\n")
    for b in blobs:
        out.write(b)
    out.flush()


async def main() -> None:
    loop = asyncio.get_running_loop()
    stdin = asyncio.StreamReader(limit=1 << 26)
    await loop.connect_read_pipe(
        lambda: asyncio.StreamReaderProtocol(stdin), sys.stdin
    )
    gen = Generator(json.loads(await stdin.readline()))
    await gen.connect()
    reply({"ready": len(gen.conns)})
    while True:
        line = await stdin.readline()
        if not line:
            return
        cmd = json.loads(line)
        if cmd["cmd"] == "burst":
            reply(await gen.burst(cmd))
        elif cmd["cmd"] == "run":
            reply(*await gen.run(cmd))
        elif cmd["cmd"] == "finish":
            # the connections stay up until the parent ends this process:
            # a client that leaves takes its subscription with it
            reply(*await gen.finish(cmd))
        else:
            raise ValueError(f"unknown command {cmd!r}")


if __name__ == "__main__":
    asyncio.run(main())
