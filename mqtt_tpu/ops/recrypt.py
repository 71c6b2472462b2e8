"""Device kernel for batched per-subscriber payload re-encryption
(ROADMAP item 6; MQT-TZ, arxiv 2007.12442).

MQT-TZ hardens a broker by decrypting each publish once with the
publisher's key and re-encrypting it per subscriber inside a TEE — a
mass per-(publish, subscriber) crypto transform with exactly the batch
shape the staged device matcher was built for. This module supplies the
transform itself: AES-128-CTR keystream generation, vectorized over
blocks, with identical math on two independent paths:

- ``host_keystream``: a vectorized numpy implementation — the
  differential oracle and the breaker degradation target
  (mqtt_tpu.tenancy.RecryptEngine wires it exactly like the matcher and
  predicate engines wire their host walks).
- ``keystream_async``: the jax device kernel — one fused dispatch
  evaluates every counter block of every (publish, subscriber) job in a
  fan-out tick, so re-encrypting to N subscribers is one dispatch, not
  N crypto calls. Per-block round keys are gathered on device from a
  dense key table (176 bytes per distinct KEY transfers, 16 bytes per
  BLOCK), and shapes are power-of-two bucketed so fan-out churn reuses
  a handful of jitted executables.

CTR framing (SP 800-38A): the counter block for block ``i`` of a
message is ``nonce(12 bytes) || BE32(i)``; the wire payload of an
encrypted publish is ``nonce || ciphertext``. Keystream bytes XOR the
payload HOST-side (numpy releases the GIL for large buffers) — only
keystream generation rides the device.

The AES tables are generated at import from the GF(2^8) definition
(no 256-entry literals to mistype); tests pin the whole construction to
the FIPS-197 appendix C.1 block vector and the SP 800-38A F.5.1 CTR
vectors, and the engine's sampled oracle cross-checks device against
host on live traffic.
"""

from __future__ import annotations

from typing import Callable

import numpy as np

from .flat import _bucket, _LazyJit

#: bytes per AES block / per keystream row
BLOCK = 16
#: wire nonce prefix of an encrypted payload (counter block = nonce || BE32(i))
NONCE_BYTES = 12
#: AES-128 rounds (round keys are [11, 16])
ROUNDS = 10


def _build_sbox() -> np.ndarray:
    """The AES S-box, generated from the field definition (multiplicative
    inverse in GF(2^8) followed by the affine transform) instead of a
    transcribed table."""
    sbox = [0] * 256
    p = q = 1
    while True:
        # p walks the multiplicative group via generator 3; q tracks 1/p
        p = p ^ ((p << 1) & 0xFF) ^ (0x1B if p & 0x80 else 0)
        q ^= (q << 1) & 0xFF
        q ^= (q << 2) & 0xFF
        q ^= (q << 4) & 0xFF
        if q & 0x80:
            q ^= 0x09
        q &= 0xFF
        affine = (
            q
            ^ ((q << 1) | (q >> 7))
            ^ ((q << 2) | (q >> 6))
            ^ ((q << 3) | (q >> 5))
            ^ ((q << 4) | (q >> 4))
        ) & 0xFF
        sbox[p] = affine ^ 0x63
        if p == 1:
            break
    sbox[0] = 0x63
    return np.array(sbox, dtype=np.uint8)


SBOX = _build_sbox()

# ShiftRows as a flat permutation over the column-major state layout
# (state[4c + r]): row r rotates left by r, so out[4c+r] = in[4((c+r)%4)+r]
SHIFT_ROWS = np.array(
    [4 * (((i // 4) + (i % 4)) % 4) + (i % 4) for i in range(16)],
    dtype=np.int32,
)


def expand_key(key: bytes) -> np.ndarray:
    """FIPS-197 AES-128 key expansion: 16-byte key -> uint8 [11, 16]
    round keys (flat, same byte order as the state/counter blocks)."""
    if len(key) != 16:
        raise ValueError(f"AES-128 key must be 16 bytes, got {len(key)}")
    w = [list(key[4 * i : 4 * i + 4]) for i in range(4)]
    rcon = 1
    for i in range(4, 44):
        t = list(w[i - 1])
        if i % 4 == 0:
            t = t[1:] + t[:1]  # RotWord
            t = [int(SBOX[b]) for b in t]  # SubWord
            t[0] ^= rcon
            rcon = ((rcon << 1) ^ 0x1B) & 0xFF if rcon & 0x80 else rcon << 1
        w.append([a ^ b for a, b in zip(w[i - 4], t)])
    return np.array(w, dtype=np.uint8).reshape(ROUNDS + 1, 16)


def _xt_np(v: np.ndarray) -> np.ndarray:
    """GF(2^8) doubling (xtime) on uint8 arrays."""
    return ((v << 1) ^ (0x1B * (v >> 7))).astype(np.uint8)


def _mix_columns_np(s: np.ndarray) -> np.ndarray:
    """MixColumns over flat [N, 16] column-major states (numpy)."""
    c = s.reshape(-1, 4, 4)  # [N, column, row]
    a0, a1, a2, a3 = c[:, :, 0], c[:, :, 1], c[:, :, 2], c[:, :, 3]
    x0, x1, x2, x3 = _xt_np(a0), _xt_np(a1), _xt_np(a2), _xt_np(a3)
    out = np.empty_like(c)
    out[:, :, 0] = x0 ^ x1 ^ a1 ^ a2 ^ a3
    out[:, :, 1] = a0 ^ x1 ^ x2 ^ a2 ^ a3
    out[:, :, 2] = a0 ^ a1 ^ x2 ^ x3 ^ a3
    out[:, :, 3] = x0 ^ a0 ^ a1 ^ a2 ^ x3
    return out.reshape(-1, 16)


def aes_encrypt_blocks_ref(
    round_keys: np.ndarray, blocks: np.ndarray
) -> np.ndarray:
    """Reference numpy AES-128 in the textbook S-box/ShiftRows/
    MixColumns formulation over ``blocks`` uint8 [N, 16] with per-block
    ``round_keys`` uint8 [N, 11, 16]. Structurally the same math as the
    device kernel; kept as the third, slowest implementation (client
    helpers + tests pin all three to the FIPS vectors)."""
    s = (blocks ^ round_keys[:, 0]).astype(np.uint8)
    for rnd in range(1, ROUNDS):
        s = SBOX[s]
        s = s[:, SHIFT_ROWS]
        s = _mix_columns_np(s)
        s ^= round_keys[:, rnd]
    s = SBOX[s]
    s = s[:, SHIFT_ROWS]
    return (s ^ round_keys[:, ROUNDS]).astype(np.uint8)


def _build_ttables() -> tuple:
    """The four fused SubBytes+ShiftRows+MixColumns lookup tables in the
    native-endian uint32 word packing ``_as_words`` produces (byte k of
    a word is flat state position 4c+k): T0..T3 are the per-input-row
    column contributions of the classic T-table formulation."""
    s = SBOX.astype(np.uint32)
    s2 = ((s << 1) ^ (0x1B * (s >> 7))) & 0xFF
    s3 = s2 ^ s
    pack = lambda b0, b1, b2, b3: (  # noqa: E731 - local packing helper
        b0 | (b1 << 8) | (b2 << 16) | (b3 << 24)
    ).astype(np.uint32)
    t0 = pack(s2, s, s, s3)
    t1 = pack(s3, s2, s, s)
    t2 = pack(s, s3, s2, s)
    t3 = pack(s, s, s3, s2)
    return t0, t1, t2, t3


_T0, _T1, _T2, _T3 = _build_ttables()


def _as_words(a: np.ndarray) -> np.ndarray:
    """Flat uint8 [..., 16] state -> native uint32 [..., 4] words (one
    word per state column; byte k of a word is row k of the column)."""
    return np.ascontiguousarray(a).view(np.uint32).reshape(*a.shape[:-1], 4)


def aes_encrypt_blocks(round_keys: np.ndarray, blocks: np.ndarray) -> np.ndarray:
    """Vectorized numpy AES-128 over ``blocks`` uint8 [N, 16] with
    per-block ``round_keys`` uint8 [N, 11, 16] — the HOST path and the
    device kernel's differential oracle, in the fused T-table
    formulation (word-wide lookups, ~3x the byte-wise reference's
    throughput and a genuinely independent derivation for the oracle
    to disagree with)."""
    rkw = _as_words(round_keys)  # [N, 11, 4]
    w = _as_words(blocks) ^ rkw[:, 0]  # [N, 4]
    # per round, each table gathers ONCE over all four output columns:
    # output column c takes T_k[byte_k of column (c+k) % 4], so T_k's
    # index matrix is the byte-k plane of the state rotated left by k
    # columns — four [N, 4] takes and four XORs per round
    r1, r2, r3 = (1, 2, 3, 0), (2, 3, 0, 1), (3, 0, 1, 2)
    for rnd in range(1, ROUNDS):
        b = w.view(np.uint8).reshape(-1, 4, 4)  # [N, column, byte-pos]
        w = (
            np.take(_T0, b[:, :, 0])
            ^ np.take(_T1, b[:, r1, 1])
            ^ np.take(_T2, b[:, r2, 2])
            ^ np.take(_T3, b[:, r3, 3])
            ^ rkw[:, rnd]
        )
    # final round: SubBytes + ShiftRows + AddRoundKey (no MixColumns)
    s = np.ascontiguousarray(w).view(np.uint8).reshape(-1, BLOCK)
    s = SBOX[s]
    s = s[:, SHIFT_ROWS]
    return (s ^ round_keys[:, ROUNDS]).astype(np.uint8)


def host_keystream(
    key_table: np.ndarray, kidx: np.ndarray, counters: np.ndarray
) -> np.ndarray:
    """The vectorized-host keystream: gather each block's round keys from
    the dense ``key_table`` uint8 [T, 11, 16] by ``kidx`` int32 [N] and
    encrypt the ``counters`` uint8 [N, 16]."""
    if len(kidx) == 0:
        return np.zeros((0, BLOCK), dtype=np.uint8)
    return aes_encrypt_blocks(key_table[kidx], counters)


def keystream_core(key_table, kidx, counters):
    """The device kernel: identical AES math to :func:`aes_encrypt_blocks`
    expressed in jax ops — S-box lookups via ``take``, ShiftRows as a
    static gather, MixColumns via uint8 xtime arithmetic. Unrolled 10
    rounds; one fused dispatch per staged batch / fan-out tick."""
    import jax.numpy as jnp

    sbox = jnp.asarray(SBOX)
    shift = jnp.asarray(SHIFT_ROWS)
    rk = jnp.take(key_table, kidx, axis=0)  # [N, 11, 16]

    def xt(v):
        return (v << 1) ^ (jnp.uint8(0x1B) * (v >> 7))

    def mix(s):
        c = s.reshape(-1, 4, 4)
        a0, a1, a2, a3 = c[:, :, 0], c[:, :, 1], c[:, :, 2], c[:, :, 3]
        x0, x1, x2, x3 = xt(a0), xt(a1), xt(a2), xt(a3)
        out = jnp.stack(
            [
                x0 ^ x1 ^ a1 ^ a2 ^ a3,
                a0 ^ x1 ^ x2 ^ a2 ^ a3,
                a0 ^ a1 ^ x2 ^ x3 ^ a3,
                x0 ^ a0 ^ a1 ^ a2 ^ x3,
            ],
            axis=2,
        )
        return out.reshape(-1, 16)

    s = counters ^ rk[:, 0]
    for rnd in range(1, ROUNDS):
        s = jnp.take(sbox, s.astype(jnp.int32))
        s = jnp.take(s, shift, axis=1)
        s = mix(s)
        s = s ^ rk[:, rnd]
    s = jnp.take(sbox, s.astype(jnp.int32))
    s = jnp.take(s, shift, axis=1)
    return s ^ rk[:, ROUNDS]


def _jit_keystream():
    import jax

    return jax.jit(keystream_core)


keystream = _LazyJit(_jit_keystream, kernel="keystream")


def ctr_counters(nonce: bytes, n_blocks: int, start: int = 0) -> np.ndarray:
    """Counter blocks ``nonce || BE32(start + i)`` as uint8 [n, 16]."""
    out = np.zeros((n_blocks, BLOCK), dtype=np.uint8)
    if n_blocks == 0:
        return out
    out[:, :NONCE_BYTES] = np.frombuffer(nonce[:NONCE_BYTES], dtype=np.uint8)
    ctr = (start + np.arange(n_blocks, dtype=np.uint32)).astype(">u4")
    out[:, NONCE_BYTES:] = ctr.view(np.uint8).reshape(n_blocks, 4)
    return out


def xor_into(data: bytes, ks_rows: np.ndarray) -> bytes:
    """XOR ``data`` against the flattened keystream rows (truncated to
    the data length) — the CTR en/decrypt step, applied host-side."""
    if not data:
        return b""
    flat = ks_rows.reshape(-1)[: len(data)]
    return (np.frombuffer(data, dtype=np.uint8) ^ flat).tobytes()


def keystream_async(
    key_table: np.ndarray, kidx: np.ndarray, counters: np.ndarray
) -> Callable[[], np.ndarray]:
    """Dispatch one fused keystream batch on the device; returns a
    zero-arg resolver yielding uint8 [N, 16] keystream rows.

    The block axis is power-of-two bucketed (padding rows use key 0 /
    zero counters — don't-care work, sliced off at resolve) so fan-out
    width churn reuses a handful of jitted executables; the key table
    ships at its true size (one executable per distinct key-count
    bucket would thrash — the table is tiny and `take` is shape-agnostic
    in the block axis only)."""
    import jax.numpy as jnp

    n = len(kidx)
    pad_n = _bucket(max(1, n), minimum=16)
    if pad_n != n:
        kidx = np.concatenate(
            [kidx, np.zeros(pad_n - n, dtype=np.int32)]
        )
        counters = np.vstack(
            [counters, np.zeros((pad_n - n, BLOCK), dtype=np.uint8)]
        )
    rows_dev = keystream(
        jnp.asarray(key_table), jnp.asarray(kidx), jnp.asarray(counters)
    )
    # overlap the D2H with the rest of the staged batch (the topic
    # matcher and predicate kernels do the same)
    rows_dev.copy_to_host_async()

    def resolve() -> np.ndarray:
        # brokerlint: ok=R15 the blessed resolve seam: ONE batched D2H after copy_to_host_async
        return np.asarray(rows_dev)[:n]

    return resolve
