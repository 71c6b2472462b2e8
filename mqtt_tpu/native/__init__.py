"""ctypes bindings for the native host data-plane core (mqtt_native.c).

The shared library is compiled on demand from the checked-in C source
(cached next to it under a name keyed on a digest of that source plus
the build flags, so a stale binary cannot load) and loaded via ctypes; every
entry point has a pure-Python fallback, so the package works — just
slower — when no C toolchain is present. ``lib()`` returns the loaded
library or ``None``.

Wired into the package hot paths:

- ``tokenize_topics_native`` — batch topic→hash arrays (ops/hashing.py
  picks it up when available; bit-identical to the Python path, which the
  differential tests in tests/test_native.py enforce)
- ``frame_scan`` + ``varint_decode`` — bulk packet framing in the client
  read loop (clients.Client.read)

``hash_token_native`` / ``varint_encode`` / ``utf8_valid`` expose the
remaining C entry points; their fallbacks delegate to packets/codec.py so
there is a single Python source of truth for those rules.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import sys
import tempfile
import threading
from typing import Optional

import numpy as np

_log = logging.getLogger("mqtt_tpu.native")
_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "mqtt_native.c")
_ACCEL_SRC = os.path.join(_HERE, "accelmod.c")
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False
_ACCEL = None
_ACCEL_TRIED = False
# did THIS process compile the artifact (vs load one already on disk)
_LIB_BUILT = False
_ACCEL_BUILT = False

# Per-scan frame cap: bounds the output arrays while the read loop keeps
# rescanning until the buffer is drained, so it is not a throughput cap.
MAX_FRAMES_PER_SCAN = 256


def _extra_cflags() -> list[str]:
    """Extra build flags from ``MQTT_TPU_NATIVE_CFLAGS`` — the sanitizer
    leg (tools/c_gate.sh --san, CI) builds both native modules with
    ``-fsanitize=address,undefined`` this way and runs the native test
    suite under ASAN/UBSAN."""
    flags = os.environ.get("MQTT_TPU_NATIVE_CFLAGS", "")
    return flags.split() if flags else []


def source_digest(src: str) -> str:
    """Digest of one C source PLUS the extra build flags — the part of
    an artifact's name that makes a stale or differently-built binary
    unloadable: the loader only ever opens the name computed from the
    source on disk, so a ``.so`` built from other bytes (an old
    checkout's, a sanitized build's) is simply never looked at. File
    mtimes decide nothing: a copy of the tree does not preserve them."""
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update(b"\0" + " ".join(_extra_cflags()).encode())
    return h.hexdigest()[:12]


def _so_path(stem: str, src: str) -> str:
    tag = f"{sys.implementation.cache_tag}-{os.uname().machine}"
    # flag-modified (sanitized) builds are throwaway: the ``x`` marks
    # them for tools/c_gate.sh to sweep
    mark = "x" if _extra_cflags() else ""
    return os.path.join(
        _HERE, f"{stem}-{tag}-{mark}{source_digest(src)}.so"
    )


def _compile(src: str, so: str, extra: list[str]) -> bool:
    """Compile ``src`` → ``so``. Returns False (and logs, loudly: the
    Python fallbacks are correct but an order of magnitude slower, so a
    broker that lost its C core must say so) on failure."""
    for cc in (os.environ.get("CC"), "cc", "gcc", "clang"):
        if not cc:
            continue
        # build to a temp file then atomically rename, so concurrent
        # processes never load a half-written library
        # brokerlint: ok=R14 single-flight first-call build: the lock exists to serialize this compile; never on a frame path
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=_HERE)
        os.close(fd)
        try:
            cmd = [cc, "-O3", "-shared", "-fPIC", *_extra_cflags(), *extra,
                   "-o", tmp, src]
            # brokerlint: ok=R14 the compile is the whole point of the lock (single-flight build)
            r = subprocess.run(cmd, capture_output=True, timeout=120)
            if r.returncode == 0:
                # brokerlint: ok=R14 atomic publish of the built library, still under the single-flight build lock
                os.replace(tmp, so)
                return True
            _log.warning(
                "native build of %s with %s failed: %s",
                os.path.basename(src), cc, r.stderr.decode(errors="replace"),
            )
        except (OSError, subprocess.SubprocessError) as e:
            _log.warning(
                "native build of %s with %s failed: %s",
                os.path.basename(src), cc, e,
            )
        finally:
            if os.path.exists(tmp):
                # brokerlint: ok=R14 temp-file cleanup on the single-flight build path
                os.unlink(tmp)
    return False


def lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, building it on first use; None if
    unavailable (no toolchain / unsupported platform)."""
    global _LIB, _TRIED, _LIB_BUILT
    if _LIB is not None or _TRIED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        if os.environ.get("MQTT_TPU_NO_NATIVE"):
            return None
        if sys.byteorder != "little":
            # the C hashing assumes little-endian loads; on big-endian hosts
            # its hashes would silently disagree with the host-side oracle
            _log.warning("native core disabled: big-endian host")
            return None
        so = _so_path("libmqtt_native", _SRC)
        try:
            if not os.path.exists(so):
                if not _compile(_SRC, so, []):
                    return None
                _LIB_BUILT = True
            cdll = ctypes.CDLL(so)
        except OSError as e:
            _log.warning("native library unavailable: %s", e)
            return None
        _declare(cdll)
        _LIB = cdll
        return _LIB


def _declare(l: ctypes.CDLL) -> None:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    l.mqtt_hash_token.restype = ctypes.c_uint64
    l.mqtt_hash_token.argtypes = [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint64]
    l.mqtt_tokenize_topics.restype = None
    l.mqtt_tokenize_topics.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64, ctypes.c_int64, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_int32), u8p, u8p,
    ]
    l.mqtt_varint_decode.restype = ctypes.c_int
    l.mqtt_varint_decode.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.POINTER(ctypes.c_uint32)
    ]
    l.mqtt_varint_encode.restype = ctypes.c_int
    l.mqtt_varint_encode.argtypes = [ctypes.c_uint32, u8p]
    l.mqtt_fh_validate.restype = ctypes.c_int
    l.mqtt_fh_validate.argtypes = [ctypes.c_uint8]
    l.mqtt_frame_scan.restype = ctypes.c_int64
    l.mqtt_frame_scan.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_int64), u8p, ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32),
    ]
    l.mqtt_utf8_valid.restype = ctypes.c_int
    l.mqtt_utf8_valid.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    l.mqtt_fan_flush.restype = ctypes.c_int64
    l.mqtt_fan_flush.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64, ctypes.c_char_p,
        ctypes.c_int64, ctypes.c_int64, ctypes.POINTER(ctypes.c_uint16),
        ctypes.POINTER(ctypes.c_int64),
    ]
    l.mqtt_frame_scan_multi.restype = None
    l.mqtt_frame_scan_multi.argtypes = [
        ctypes.c_int64, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64, ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_int64), u8p, ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32),
    ]
    l.mqtt_assemble_frames.restype = None
    l.mqtt_assemble_frames.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, u8p, ctypes.c_int64, u8p,
        ctypes.c_int64, u8p, ctypes.c_int64, ctypes.c_int64, u8p,
    ]


def available() -> bool:
    return lib() is not None


def accel():
    """The C materializer extension module, building it on first use;
    None when unavailable. Unlike mqtt_native.c (plain C via ctypes) it
    builds Python result objects, so it compiles against the CPython
    headers and loads as a real extension module. Every caller keeps the
    pure-Python path as fallback and source of truth."""
    global _ACCEL, _ACCEL_TRIED, _ACCEL_BUILT
    if _ACCEL is not None or _ACCEL_TRIED:
        return _ACCEL
    with _LOCK:
        if _ACCEL is not None or _ACCEL_TRIED:
            return _ACCEL
        _ACCEL_TRIED = True
        if os.environ.get("MQTT_TPU_NO_NATIVE"):
            return None
        so = _so_path("mqtt_accel", _ACCEL_SRC)
        try:
            if not os.path.exists(so):
                import sysconfig

                include = sysconfig.get_paths()["include"]
                if not _compile(_ACCEL_SRC, so, [f"-I{include}"]):
                    return None
                _ACCEL_BUILT = True
            import importlib.machinery
            import importlib.util

            loader = importlib.machinery.ExtensionFileLoader("mqtt_accel", so)
            spec = importlib.util.spec_from_file_location(
                "mqtt_accel", so, loader=loader
            )
            mod = importlib.util.module_from_spec(spec)
            loader.exec_module(mod)
            _ACCEL = mod
        except (OSError, ImportError) as e:
            _log.warning("accel module unavailable: %s", e)
            return None
        return _ACCEL


def status() -> dict:
    """Whether each native module is loaded, whether THIS process had to
    build it, and the source digest its artifact is keyed on — what
    chip_smoke.py reports and requires (both modules loaded)."""
    return {
        "lib": {
            "loaded": lib() is not None,
            "built": _LIB_BUILT,
            "digest": source_digest(_SRC),
        },
        "accel": {
            "loaded": accel() is not None,
            "built": _ACCEL_BUILT,
            "digest": source_digest(_ACCEL_SRC),
        },
    }


# -- high-level wrappers ----------------------------------------------------


def hash_token_native(token: bytes, salt: int = 0) -> Optional[int]:
    """8-byte blake2b of one token; None when the library is unavailable."""
    l = lib()
    if l is None:
        return None
    return l.mqtt_hash_token(token, len(token), salt)


def tokenize_topics_native(topics: list[str], max_levels: int, salt: int = 0):
    """Native batch tokenization with the exact output contract of
    ops/hashing.tokenize_topics; None when the library is unavailable."""
    l = lib()
    if l is None:
        return None
    n = len(topics)
    encoded = [t.encode("utf-8") for t in topics]
    offsets = np.zeros(n + 1, dtype=np.int64)
    for i, e in enumerate(encoded):
        offsets[i + 1] = offsets[i] + len(e)
    buf = b"".join(encoded)
    tok1 = np.zeros((n, max_levels), dtype=np.uint32)
    tok2 = np.zeros((n, max_levels), dtype=np.uint32)
    lengths = np.zeros(n, dtype=np.int32)
    is_dollar = np.zeros(n, dtype=np.uint8)
    overflow = np.zeros(n, dtype=np.uint8)
    if n:
        l.mqtt_tokenize_topics(
            buf,
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            n, max_levels, salt,
            tok1.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            tok2.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            is_dollar.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            overflow.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        )
    return tok1, tok2, lengths, is_dollar.astype(bool), overflow.astype(bool)


def varint_decode(buf: bytes) -> tuple[int, int]:
    """Returns (value, bytes_consumed); consumed 0 = need more bytes.
    Raises ValueError on a malformed integer."""
    l = lib()
    if l is None:
        return _varint_decode_py(buf)
    value = ctypes.c_uint32()
    r = l.mqtt_varint_decode(buf, len(buf), ctypes.byref(value))
    if r < 0:
        raise ValueError("malformed variable byte integer")
    return value.value, r


def _varint_decode_py(buf: bytes) -> tuple[int, int]:
    value = 0
    shift = 0
    for i, b in enumerate(buf[:4]):
        value |= (b & 0x7F) << shift
        if value > 268435455:
            raise ValueError("malformed variable byte integer")
        if not b & 0x80:
            return value, i + 1
        shift += 7
    if len(buf) >= 4:
        raise ValueError("malformed variable byte integer")
    return 0, 0


def varint_encode(value: int) -> bytes:
    l = lib()
    if l is None:
        return _varint_encode_py(value)
    out = (ctypes.c_uint8 * 4)()
    n = l.mqtt_varint_encode(value, out)
    if n < 0:
        raise ValueError("value exceeds maximum variable byte integer")
    return bytes(out[:n])


def _varint_encode_py(value: int) -> bytes:
    from ..packets.codec import encode_length

    if value > 268435455:
        raise ValueError("value exceeds maximum variable byte integer")
    out = bytearray()
    encode_length(out, value)
    return bytes(out)


def utf8_valid(data: bytes) -> bool:
    """Strict UTF-8 incl. the MQTT NUL rejection [MQTT-1.5.4-2]."""
    l = lib()
    if l is None:
        from ..packets.codec import valid_utf8

        return valid_utf8(data)
    return bool(l.mqtt_utf8_valid(data, len(data)))


class Frame:
    """One complete packet located by frame_scan."""

    __slots__ = ("first_byte", "body_offset", "remaining")

    def __init__(self, first_byte: int, body_offset: int, remaining: int):
        self.first_byte = first_byte
        self.body_offset = body_offset
        self.remaining = remaining


# frame_scan's output arrays, one set a thread (the scan is synchronous
# and every event loop runs in one thread): made once, where every call
# used to allocate three numpy arrays and five ctypes objects
_scan_out = threading.local()


def _scan_scratch(max_frames: int) -> tuple:
    out = getattr(_scan_out, "held", None)
    if out is None or out[0] < max_frames:
        consumed, err = ctypes.c_int64(), ctypes.c_int32()
        out = _scan_out.held = (
            max_frames,
            (ctypes.c_int64 * max_frames)(),
            (ctypes.c_uint8 * max_frames)(),
            (ctypes.c_uint32 * max_frames)(),
            consumed, err, ctypes.byref(consumed), ctypes.byref(err),
        )
    return out


def frame_scan(
    buf: bytes, max_frames: int = 1024, max_packet_size: int = 0
) -> tuple[list[Frame], int, int]:
    """Split a raw read buffer into complete MQTT packets.

    Returns ``(frames, consumed, err)``. ``frames`` holds every complete
    packet found before any error (the caller still processes them).
    ``err``: 0 ok, -1 malformed header/varint, -2 packet-too-large; on
    error ``consumed`` points at the offending packet's first byte.
    """
    l = lib()
    if l is None:
        return _frame_scan_py(buf, max_frames, max_packet_size)
    size = len(buf)
    if not size:
        return [], 0, 0
    _, body_offsets, first_bytes, remainings, consumed, err, consumed_ref, err_ref = (
        _scan_scratch(max_frames)
    )
    if isinstance(buf, (bytearray, memoryview)):
        # zero-copy view of the mutable read buffer
        holder = ctypes.c_char.from_buffer(buf)
        ptr = ctypes.addressof(holder)
    else:
        holder = buf
        ptr = ctypes.cast(ctypes.c_char_p(buf), ctypes.c_void_p).value
    try:
        n = l.mqtt_frame_scan(
            ptr, size, max_frames, max_packet_size,
            body_offsets, first_bytes, remainings, consumed_ref, err_ref,
        )
    finally:
        # release the from_buffer export DETERMINISTICALLY: anything that
        # pins this frame past return (a debugger, or anything else
        # holding sys._current_frames() references) would keep the export
        # alive and make the caller's `del rbuf[:consumed]` raise
        # BufferError("Existing exports of data") mid-read-loop
        del holder
    frames = list(map(Frame, first_bytes[:n], body_offsets[:n], remainings[:n]))
    return frames, consumed.value, err.value


_FH_FLAG_OK = {  # type → required flags; PUBLISH checked separately.
    # type 0 (reserved) with zero flags passes here — the decoder dispatch
    # rejects it with NoValidPacketAvailable, matching FixedHeader.decode.
    6: 0x02, 8: 0x02, 10: 0x02,
    0: 0, 1: 0, 2: 0, 4: 0, 5: 0, 7: 0, 9: 0, 11: 0, 12: 0, 13: 0, 14: 0, 15: 0,
}


def _fh_validate_py(b: int) -> bool:
    type_ = b >> 4
    flags = b & 0x0F
    if type_ == 3:
        qos = (flags >> 1) & 0x03
        return qos < 3 and not (flags & 0x08 and qos == 0)
    want = _FH_FLAG_OK.get(type_)
    return want is not None and flags == want


def fan_flush(
    fds, frame: bytes, id_offset: int = -1, ids=None
):
    """Write one encoded PUBLISH variant frame to many ready sockets in
    a single GIL-released native call (server._fan_out batched path).

    ``fds`` is a sequence of socket fds whose transports the caller
    verified idle; ``id_offset``/``ids`` patch per-target 2-byte packet
    ids via writev iovecs for QoS>0 variants (no per-target copies).
    Returns an int64 array of per-target results — bytes written, or
    ``-errno`` — or None when the native library is unavailable (the
    caller keeps the per-target transport path)."""
    l = lib()
    if l is None:
        return None
    n = len(fds)
    fds_arr = np.asarray(fds, dtype=np.int32)
    sent = np.zeros(n, dtype=np.int64)
    if ids is None:
        ids_arr = np.zeros(0, dtype=np.uint16)
        id_offset = -1
    else:
        ids_arr = np.asarray(ids, dtype=np.uint16)
    if n:
        l.mqtt_fan_flush(
            fds_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            n, frame, len(frame), id_offset,
            ids_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
            sent.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        )
    return sent


def frame_scan_multi(
    bufs: list, max_frames: int = 256, max_packet_size: int = 0
) -> "Optional[list[tuple[list[Frame], int, int]]]":
    """Scan K read buffers in ONE native call — the read-side decode
    batched across ready sockets (the coalesced read path). Returns one
    ``(frames, consumed, err)`` tuple per buffer with frame_scan's exact
    contract, or None when the native library is unavailable."""
    l = lib()
    if l is None:
        return None
    k = len(bufs)
    if k == 0:
        return []
    holders: list = []
    ptrs = (ctypes.c_void_p * k)()
    lens = np.zeros(k, dtype=np.int64)
    for i, buf in enumerate(bufs):
        lens[i] = len(buf)
        if isinstance(buf, (bytearray, memoryview)):
            # NOTE: the export must live ONLY in `holders` — a loop
            # local binding would survive the finally below and, with
            # this frame pinned past return (a debugger's
            # sys._current_frames() references), keep the
            # LAST buffer exported while its read loop resumes and
            # `del rbuf[:consumed]` raises BufferError — the exact
            # frame_scan hazard, multiplied by the shard fabric's
            # default-on per-shard ScanGate
            if len(buf):
                holders.append((ctypes.c_char * len(buf)).from_buffer(buf))
                ptrs[i] = ctypes.addressof(holders[-1])
            else:
                holders.append(b"")
                ptrs[i] = None
        else:
            holders.append(buf)
            ptrs[i] = (
                ctypes.cast(ctypes.c_char_p(buf), ctypes.c_void_p).value
                if buf
                else None
            )
    body_offsets = np.zeros(k * max_frames, dtype=np.int64)
    first_bytes = np.zeros(k * max_frames, dtype=np.uint8)
    remainings = np.zeros(k * max_frames, dtype=np.uint32)
    counts = np.zeros(k, dtype=np.int64)
    consumed = np.zeros(k, dtype=np.int64)
    errs = np.zeros(k, dtype=np.int32)
    try:
        l.mqtt_frame_scan_multi(
            k, ptrs,
            lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            max_frames, max_packet_size,
            body_offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            first_bytes.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            remainings.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            consumed.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            errs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
    finally:
        # deterministic release of the from_buffer exports (the same
        # BufferError hazard frame_scan documents): clear IN PLACE so
        # the exports die even while something pins this frame
        holders.clear()
        del holders
    out = []
    for i in range(k):
        base = i * max_frames
        frames = [
            Frame(
                int(first_bytes[base + j]),
                int(body_offsets[base + j]),
                int(remainings[base + j]),
            )
            for j in range(int(counts[i]))
        ]
        out.append((frames, int(consumed[i]), int(errs[i])))
    return out


def assemble_frames(head: bytes, nonces, keystreams, plaintext: bytes):
    """Assemble N per-subscriber encrypted PUBLISH frames — head ||
    nonce_i || (plaintext XOR keystream_i) — in one GIL-released native
    pass (the re-encrypt fan-out's encode-once path). ``nonces`` is
    uint8 [N, nonce_len], ``keystreams`` uint8 [N, >= len(plaintext)].
    Returns a uint8 array [N, frame_len], or None when the native
    library is unavailable (callers keep the numpy path)."""
    l = lib()
    if l is None:
        return None
    nonces = np.ascontiguousarray(nonces, dtype=np.uint8)
    keystreams = np.ascontiguousarray(keystreams, dtype=np.uint8)
    n, nonce_len = nonces.shape
    pt_len = len(plaintext)
    ks_stride = keystreams.shape[1] if keystreams.ndim == 2 else 0
    if n and pt_len > ks_stride:
        return None  # keystream rows too short: let the caller's path run
    out = np.empty((n, len(head) + nonce_len + pt_len), dtype=np.uint8)
    if n:
        pt = np.frombuffer(plaintext, dtype=np.uint8)
        u8 = ctypes.POINTER(ctypes.c_uint8)
        l.mqtt_assemble_frames(
            head, len(head),
            nonces.ctypes.data_as(u8), nonce_len,
            keystreams.ctypes.data_as(u8), ks_stride,
            pt.ctypes.data_as(u8), pt_len,
            n, out.ctypes.data_as(u8),
        )
    return out


def _frame_scan_py(
    buf: bytes, max_frames: int, max_packet_size: int
) -> tuple[list[Frame], int, int]:
    frames: list[Frame] = []
    pos = 0
    n = len(buf)
    while len(frames) < max_frames and pos < n:
        if not _fh_validate_py(buf[pos]):
            return frames, pos, -1
        if pos + 1 >= n:
            break
        try:
            remaining, vb = _varint_decode_py(buf[pos + 1 :])
        except ValueError:
            return frames, pos, -1
        if vb == 0:
            break
        if max_packet_size and remaining + 1 > max_packet_size:
            return frames, pos, -2
        if pos + 1 + vb + remaining > n:
            break
        frames.append(Frame(buf[pos], pos + 1 + vb, remaining))
        pos += 1 + vb + remaining
    return frames, pos, 0
