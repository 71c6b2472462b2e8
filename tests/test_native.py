"""Differential tests: native C core vs the pure-Python reference paths.

The native library is optional; when it can't be built these tests skip
(except the pure-Python fallback cases, which always run).
"""

import hashlib
import random
import sys

import numpy as np
import pytest

from mqtt_tpu import native
from mqtt_tpu.native import (
    Frame,
    _frame_scan_py,
    _varint_decode_py,
    _varint_encode_py,
    frame_scan,
    hash_token_native,
    tokenize_topics_native,
    utf8_valid,
    varint_decode,
    varint_encode,
)
from mqtt_tpu.ops.hashing import tokenize_topics_py
from tests.tpackets import CASES

needs_native = pytest.mark.skipif(
    not native.available(), reason="native library unavailable"
)


@needs_native
class TestBlake2b:
    def test_matches_hashlib(self):
        rng = random.Random(11)
        for _ in range(500):
            n = rng.randrange(0, 300)
            tok = bytes(rng.randrange(256) for _ in range(n))
            salt = rng.randrange(1 << 63)
            want = int.from_bytes(
                hashlib.blake2b(
                    tok, digest_size=8, salt=salt.to_bytes(8, "little")
                ).digest(),
                "little",
            )
            assert hash_token_native(tok, salt) == want

    def test_multiblock_boundaries(self):
        for n in (0, 1, 127, 128, 129, 255, 256, 257, 1024):
            tok = bytes(range(256)) * 5
            tok = tok[:n]
            want = int.from_bytes(
                hashlib.blake2b(
                    tok, digest_size=8, salt=(0).to_bytes(8, "little")
                ).digest(),
                "little",
            )
            assert hash_token_native(tok, 0) == want


@needs_native
class TestTokenize:
    def test_matches_python(self):
        rng = random.Random(12)
        words = ["a", "bb", "sensor", "+", "#", "$SYS", "x" * 40, "", "日本語"]
        topics = ["", "/", "//", "a//b/", "$SYS/broker/load"]
        for _ in range(300):
            topics.append(
                "/".join(rng.choice(words) for _ in range(rng.randrange(1, 12)))
            )
        for salt in (0, 7, 123456789):
            py = tokenize_topics_py(topics, 8, salt)
            nat = tokenize_topics_native(topics, 8, salt)
            for a, b in zip(py, nat):
                assert np.array_equal(a, b)

    def test_empty_batch(self):
        nat = tokenize_topics_native([], 4, 0)
        assert nat[0].shape == (0, 4)


class TestVarint:
    @pytest.mark.parametrize(
        "value,encoded",
        [
            (0, b"\x00"),
            (127, b"\x7f"),
            (128, b"\x80\x01"),
            (16383, b"\xff\x7f"),
            (16384, b"\x80\x80\x01"),
            (2097151, b"\xff\xff\x7f"),
            (2097152, b"\x80\x80\x80\x01"),
            (268435455, b"\xff\xff\xff\x7f"),
        ],
    )
    def test_roundtrip(self, value, encoded):
        assert varint_encode(value) == encoded
        assert _varint_encode_py(value) == encoded
        assert varint_decode(encoded) == (value, len(encoded))
        assert _varint_decode_py(encoded) == (value, len(encoded))

    def test_incomplete(self):
        assert varint_decode(b"\x80")[1] == 0
        assert _varint_decode_py(b"\x80")[1] == 0

    def test_overflow(self):
        with pytest.raises(ValueError):
            varint_decode(b"\xff\xff\xff\xff")
        with pytest.raises(ValueError):
            _varint_decode_py(b"\xff\xff\xff\xff")
        with pytest.raises(ValueError):
            varint_encode(268435456)

    def test_differential_random(self):
        rng = random.Random(13)
        for _ in range(300):
            v = rng.randrange(268435456)
            e = varint_encode(v)
            assert e == _varint_encode_py(v)
            assert varint_decode(e) == _varint_decode_py(e) == (v, len(e))


class TestFrameScan:
    def _scan_both(self, buf, **kw):
        got = frame_scan(buf, **kw)
        py = _frame_scan_py(buf, kw.get("max_frames", 1024), kw.get("max_packet_size", 0))
        assert [
            (f.first_byte, f.body_offset, f.remaining) for f in got[0]
        ] == [(f.first_byte, f.body_offset, f.remaining) for f in py[0]]
        assert got[1:] == py[1:]
        return got

    def test_golden_catalogue_stream(self):
        """Concatenate all well-formed golden packets and re-find each one."""
        good = [c for c in CASES if c.raw and c.decode_err is None and c.fail_first is None]
        buf = b"".join(c.raw for c in good)
        frames, consumed, err = self._scan_both(buf)
        assert err == 0
        assert consumed == len(buf)
        assert len(frames) == len(good)
        pos = 0
        for f, c in zip(frames, good):
            assert f.first_byte == c.raw[0]  # first byte of this packet
            # body = raw minus fixed header (first byte + varint length)
            header_len = len(c.raw) - f.remaining
            assert f.body_offset == pos + header_len
            assert buf[f.body_offset : f.body_offset + f.remaining] == c.raw[header_len:]
            pos += len(c.raw)

    def test_partial_tail(self):
        pk = bytes.fromhex("30080003612f62706179")  # publish a/b "pay" (8 body bytes)
        frames, consumed, err = self._scan_both(pk + pk[:4])
        assert err == 0 and len(frames) == 1 and consumed == len(pk)

    def test_reserved_type_scans_as_frame(self):
        # type 0 with zero flags passes header validation; the decoder
        # dispatch is what rejects it (matching FixedHeader.decode)
        frames, consumed, err = self._scan_both(b"\x00\x00")
        assert err == 0 and len(frames) == 1

    def test_malformed_header_flags(self):
        # PINGREQ with nonzero flags violates [MQTT-3.12.1-1]
        frames, consumed, err = self._scan_both(b"\xc1\x00")
        assert err == -1 and consumed == 0

    def test_malformed_second_packet(self):
        pk = bytes.fromhex("c000")  # PINGREQ
        bad = b"\x63\x00"  # PUBLISH with QoS 3
        frames, consumed, err = self._scan_both(pk + bad)
        # the complete PINGREQ before the error is still returned
        assert err == -1 and consumed == len(pk) and len(frames) == 1

    def test_max_packet_size(self):
        pk = bytes.fromhex("30080003612f62706179")
        frames, consumed, err = self._scan_both(pk, max_packet_size=5)
        assert err == -2 and consumed == 0
        frames, consumed, err = self._scan_both(pk, max_packet_size=100)
        assert err == 0 and len(frames) == 1

    def test_max_frames(self):
        ping = bytes.fromhex("c000")
        frames, consumed, err = self._scan_both(ping * 10, max_frames=3)
        assert err == 0 and len(frames) == 3 and consumed == 6

    def test_incomplete_varint(self):
        frames, consumed, err = self._scan_both(b"\x30\xff")
        assert err == 0 and frames == [] and consumed == 0

    def test_bytearray_input_zero_copy_path(self):
        # the client read loop passes its mutable bytearray buffer
        pk = bytearray(bytes.fromhex("c000") * 3)
        frames, consumed, err = frame_scan(pk)
        assert err == 0 and len(frames) == 3 and consumed == 6
        del pk[:consumed]  # must not raise BufferError (no live exports)
        assert len(pk) == 0

    def test_empty_buffer(self):
        frames, consumed, err = self._scan_both(b"")
        assert err == 0 and frames == [] and consumed == 0

    def test_dup_without_qos_rejected(self):
        # PUBLISH DUP=1 QoS=0 violates [MQTT-3.3.1-2]
        frames, consumed, err = self._scan_both(b"\x38\x00")
        assert err == -1

    @pytest.mark.parametrize("kind", [bytes, bytearray, memoryview])
    def test_one_scans_results_do_not_outlive_it_in_the_next(self, kind):
        """The output arrays are kept from call to call (one set a
        thread): a later scan, of fewer frames or asked for more than the
        arrays hold, leaves an earlier scan's frames as they were."""
        ping, pk = bytes.fromhex("c000"), bytes.fromhex("30080003612f62706179")
        wrap = (lambda b: memoryview(bytearray(b))) if kind is memoryview else kind
        first, _, _ = self._scan_both(wrap(pk * 5), max_frames=8)
        second, consumed, err = self._scan_both(wrap(ping * 3), max_frames=8)
        third, _, _ = self._scan_both(wrap(ping * 40 + pk), max_frames=64)  # grows
        assert [(f.first_byte, f.remaining) for f in first] == [(0x30, 8)] * 5
        assert [f.body_offset for f in first] == [2, 12, 22, 32, 42]
        assert [(f.first_byte, f.body_offset) for f in second] == [(0xC0, 2), (0xC0, 4), (0xC0, 6)]
        assert (consumed, err) == (6, 0) and len(third) == 41 and third[-1].remaining == 8

    def test_threads_scan_side_by_side(self):
        """Each thread has its own output arrays: scans on two event
        loops' threads at once do not write into each other's."""
        import threading

        streams = [bytes.fromhex("c000") * 200, bytes.fromhex("30080003612f62706179") * 200]
        want = [_frame_scan_py(s, 256, 0) for s in streams]
        wrong = []

        def scan(k):
            for _ in range(300):
                got = frame_scan(bytearray(streams[k]), max_frames=256)
                if [(f.first_byte, f.body_offset, f.remaining) for f in got[0]] != [
                    (f.first_byte, f.body_offset, f.remaining) for f in want[k][0]
                ] or got[1:] != want[k][1:]:
                    wrong.append(k)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=scan, args=(k % 2,), daemon=True) for k in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads) and not wrong


class TestUtf8:
    @pytest.mark.parametrize(
        "data,ok",
        [
            (b"plain", True),
            ("日本語".encode(), True),
            (b"with\x00nul", False),  # [MQTT-1.5.4-2]
            (b"\xc0\xaf", False),  # overlong '/'
            (b"\xed\xa0\x80", False),  # surrogate
            (b"\xf4\x90\x80\x80", False),  # > U+10FFFF
            (b"\xff", False),
            (b"\xe2\x82", False),  # truncated
            ("\U0010ffff".encode(), True),
            (b"", True),
        ],
    )
    def test_cases(self, data, ok):
        assert utf8_valid(data) is ok
        # python fallback path agreement
        py_ok = b"\x00" not in data
        if py_ok:
            try:
                data.decode("utf-8", "strict")
            except UnicodeDecodeError:
                py_ok = False
        assert py_ok is ok


@needs_native
def test_matcher_pipeline_uses_native(monkeypatch):
    """tokenize_topics (the matcher input path) must agree with the
    pure-Python reference even when served by the native core."""
    from mqtt_tpu.ops.hashing import tokenize_topics

    topics = ["a/b/c", "$share/g/t", "", "x/+/#"]
    nat = tokenize_topics(topics, 4, 3)
    py = tokenize_topics_py(topics, 4, 3)
    for a, b in zip(nat, py):
        assert np.array_equal(a, b)


# -- C materializer (accelmod.c) differential tests -------------------------

needs_accel = pytest.mark.skipif(
    native.accel() is None, reason="accel extension unavailable"
)


def _random_snaps(rng, n_entries, window):
    """Snapshot tuples shaped like ops/flat builds them: clients first,
    then shared members, then inline subscriptions, all within window."""
    from mqtt_tpu.packets import Subscription
    from mqtt_tpu.topics import InlineSubscription

    snaps = []
    for e in range(n_entries):
        n_cli = rng.randint(0, 4)
        n_shr = rng.randint(0, 2)
        n_inl = rng.randint(0, 2)
        cli = tuple(
            (
                f"cl{e}_{i}" if rng.random() < 0.8 else "dup",  # force merges
                Subscription(
                    filter=f"f/{e}/{i}",
                    qos=rng.randint(0, 2),
                    identifier=rng.choice([0, 0, 5, 9]),
                    identifiers={f"prev/{e}": 3} if rng.random() < 0.3 else None,
                    no_local=rng.random() < 0.2,
                ),
            )
            for i in range(n_cli)
        )
        shr = tuple(
            (
                f"m{e}_{i}",
                Subscription(filter=f"$SHARE/g{i % 2}/f/{e}", qos=1),
            )
            for i in range(n_shr)
        )
        inl = tuple(
            InlineSubscription(
                filter=f"f/{e}", identifier=e * 10 + i + 1, handler=lambda *a: None
            )
            for i in range(n_inl)
        )
        snaps.append((cli, shr, inl))
    return snaps


def _canon(s):
    return (
        {
            c: (
                sub.qos,
                sub.no_local,
                sub.filter,
                tuple(sorted((sub.identifiers or {}).items())),
            )
            for c, sub in s.subscriptions.items()
        },
        {f: set(m) for f, m in s.shared.items()},
        set(s.inline_subscriptions),
    )


@needs_accel
class TestResolveBatch:
    def _packed(self, rng, n_topics, P, snaps, window):
        """Random VALID range rows: counts never exceed the entry's actual
        snapshot population (the device meta word guarantees this in
        production — counts are derived from the snapshot lengths)."""
        totals = [sum(len(part) for part in s) for s in snaps]
        packed = np.zeros((n_topics, 2 * P + 2), dtype=np.int32)
        for i in range(n_topics):
            if rng.random() < 0.1:
                packed[i, 2 * P + 1] = 1  # overflow row
                continue
            for p in range(P):
                if rng.random() < 0.5:
                    e = rng.randrange(len(snaps))
                    if not totals[e]:
                        continue
                    lo = rng.randrange(totals[e])  # the $-mask's lo offset
                    packed[i, p] = e * window + lo
                    packed[i, P + p] = rng.randint(0, totals[e] - lo)
        return packed

    def _python_reference(self, packed, P, snaps, window):
        """expand_sids over a _LazySubTable built from the same snaps."""
        from mqtt_tpu.ops.flat import _LazySubTable
        from mqtt_tpu.ops.matcher import expand_sids
        from mqtt_tpu.topics import Subscribers

        table = _LazySubTable(window, list(snaps), len(snaps) * window)
        results = []
        for row in packed.tolist():
            if row[2 * P + 1]:
                results.append(None)
                continue
            sids = []
            for p in range(P):
                c = row[P + p]
                if c:
                    sids.extend(range(row[p], row[p] + c))
            results.append(expand_sids(table, sids, Subscribers()))
        return results

    def test_differential_random(self):
        from mqtt_tpu.topics import Subscribers

        acc = native.accel()
        rng = random.Random(11)
        window, P, n_entries = 8, 3, 64
        snaps = _random_snaps(rng, n_entries, window)
        packed = self._packed(rng, 512, P, snaps, window)
        res_c, ovf = acc.resolve_batch(packed, 512, P, snaps, window, Subscribers)
        res_py = self._python_reference(packed, P, snaps, window)
        assert len(res_c) == len(res_py) == 512
        assert [i for i, r in enumerate(res_py) if r is None] == list(ovf)
        for a, b in zip(res_c, res_py):
            assert (a is None) == (b is None)
            if a is not None:
                assert _canon(a) == _canon(b)

    def test_identifiers_shared_and_extended(self):
        """A stored identifiers map is mutated by the copy when
        identifier > 0 — Subscription.merge semantics, which the Python
        and C paths must share exactly."""
        from mqtt_tpu.packets import Subscription
        from mqtt_tpu.topics import Subscribers

        acc = native.accel()
        stored = Subscription(filter="a/b", qos=1, identifier=7, identifiers={"x": 1})
        snaps = [(( ("c1", stored), ), (), ())]
        packed = np.zeros((1, 2 * 1 + 2), dtype=np.int32)
        packed[0, 0] = 0
        packed[0, 1] = 1
        res, ovf = acc.resolve_batch(packed, 1, 1, snaps, 4, Subscribers)
        got = res[0].subscriptions["c1"]
        assert got is not stored  # fresh copy
        assert got.identifiers is stored.identifiers  # the SHARED map
        assert stored.identifiers == {"x": 1, "a/b": 7}  # extended in place

    def test_out_of_range_sids_skipped(self):
        from mqtt_tpu.topics import Subscribers

        acc = native.accel()
        snaps = _random_snaps(random.Random(1), 2, 4)
        packed = np.zeros((1, 4), dtype=np.int32)
        packed[0, 0] = 4 * 100  # ordinal way past the table
        packed[0, 1] = 3
        res, ovf = acc.resolve_batch(packed, 1, 1, snaps, 4, Subscribers)
        assert not ovf
        assert not res[0].subscriptions

    def test_dict_class_fallback(self):
        """Subclasses without a usable slots layout route through the
        Python self_merged_copy / merge methods — same values."""
        from mqtt_tpu.packets import Subscription
        from mqtt_tpu.topics import Subscribers

        class DictSub(Subscription):
            pass  # plain subclass: instances carry a __dict__

        acc = native.accel()
        stored = DictSub(filter="q/w", qos=2, identifier=3)
        snaps = [((("c1", stored),), (), ())]
        packed = np.zeros((1, 4), dtype=np.int32)
        packed[0, 1] = 1
        res, _ = acc.resolve_batch(packed, 1, 1, snaps, 8, Subscribers)
        got = res[0].subscriptions["c1"]
        assert type(got) is DictSub
        assert (got.qos, got.identifiers) == (2, {"q/w": 3})

    def test_expand_sids_list_matches_expand_sids(self):
        from mqtt_tpu.ops.flat import _LazySubTable
        from mqtt_tpu.ops.matcher import expand_sids
        from mqtt_tpu.topics import Subscribers

        acc = native.accel()
        rng = random.Random(3)
        window = 8
        snaps = _random_snaps(rng, 32, window)
        table = _LazySubTable(window, list(snaps), len(snaps) * window)
        # only slots the snapshots actually populate (production sids are
        # bounded by the per-entry counts)
        valid = [
            e * window + k
            for e, s in enumerate(snaps)
            for k in range(sum(len(part) for part in s))
        ]
        sids = sorted(rng.sample(valid, min(64, len(valid))))
        a = acc.expand_sids_list(sids, snaps, window, Subscribers())
        b = expand_sids(table, list(sids), Subscribers())
        assert _canon(a) == _canon(b)


@needs_accel
def test_expand_snap_matches_python():
    from mqtt_tpu.ops.matcher import TpuMatcher
    from mqtt_tpu.topics import Subscribers

    acc = native.accel()
    rng = random.Random(5)
    for snap in _random_snaps(rng, 24, 8):
        cli, shr, inl = snap
        # a real trie node keys clients uniquely (the subscriptions map);
        # drop the generator's forced-dup entries for this single-node case
        seen, uniq = set(), []
        for client, sub in cli:
            if client not in seen:
                seen.add(client)
                uniq.append((client, sub))
        snap = (tuple(uniq), shr, inl)
        a = acc.expand_snap(snap, Subscribers)
        b = TpuMatcher._expand_snap(snap)
        assert _canon(a) == _canon(b)
    # empty snapshot
    empty = acc.expand_snap(((), (), ()), Subscribers)
    assert not empty.subscriptions and not empty.shared


class TestArtifactNaming:
    """The loader opens only the name computed from the source on disk
    (ISSUE 21): a digest of the C source plus the build flags. A binary
    built from other bytes has another name and is never looked at;
    mtimes — which a copy of the tree does not preserve — decide
    nothing."""

    def test_name_carries_the_source_digest(self, tmp_path, monkeypatch):
        monkeypatch.delenv("MQTT_TPU_NATIVE_CFLAGS", raising=False)
        src = tmp_path / "m.c"
        src.write_bytes(b"int f(void) { return 1; }\n")
        d1 = native.source_digest(str(src))
        p1 = native._so_path("libmqtt_native", str(src))
        assert p1.endswith(f"-{d1}.so") and len(d1) == 12
        # same bytes, any mtime: same name
        import os

        os.utime(src, (1, 1))
        assert native._so_path("libmqtt_native", str(src)) == p1
        # other bytes: another name, so the old binary cannot load
        src.write_bytes(b"int f(void) { return 2; }\n")
        assert native.source_digest(str(src)) != d1
        assert native._so_path("libmqtt_native", str(src)) != p1

    def test_flags_change_the_name_and_mark_it_throwaway(
        self, tmp_path, monkeypatch
    ):
        src = tmp_path / "m.c"
        src.write_bytes(b"int f(void) { return 1; }\n")
        monkeypatch.delenv("MQTT_TPU_NATIVE_CFLAGS", raising=False)
        plain = native._so_path("mqtt_accel", str(src))
        monkeypatch.setenv("MQTT_TPU_NATIVE_CFLAGS", "-fsanitize=address")
        san = native._so_path("mqtt_accel", str(src))
        assert san != plain
        # tools/c_gate.sh sweeps sanitized artifacts by this mark
        assert f"-x{native.source_digest(str(src))}.so" in san

    @needs_native
    def test_loaded_modules_report_their_digest(self):
        st = native.status()
        assert st["lib"]["loaded"] and st["accel"]["loaded"]
        assert st["lib"]["digest"] == native.source_digest(native._SRC)
        assert st["accel"]["digest"] == native.source_digest(
            native._ACCEL_SRC
        )

    @needs_native
    def test_stale_binary_under_the_current_name_is_impossible(self):
        """Only artifacts whose name carries the digest of the sources
        on disk exist for the loader."""
        import os

        here = os.path.dirname(native.__file__)
        want = {
            os.path.basename(native._so_path("libmqtt_native", native._SRC)),
            os.path.basename(native._so_path("mqtt_accel", native._ACCEL_SRC)),
        }
        assert want <= {f for f in os.listdir(here) if f.endswith(".so")}
