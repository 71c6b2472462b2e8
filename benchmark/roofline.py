"""What the match needs to move, and the least time the chip could take.

The flat match is a gather: for each topic, each of the index's P probe
patterns hashes the topic and reads ONE bucket row of the table. It does
next to no arithmetic, so the bound is HBM bandwidth. Bytes are counted
per topic actually matched — not per padded batch row — so the figure is
the same whatever implements the kernel.
"""

from __future__ import annotations

import json
import os

ROW_BYTES = 64  # one bucket row: 4 entries of 4 int32


def match_bytes(topics: int, patterns: int, levels: int) -> int:
    """Per topic: P row gathers from the table, the token row up
    (two hashes a level, the length and the $-flag, int32 each), and the
    hit ranges down (a start and a count per pattern, the total and the
    overflow flag)."""
    up = (2 * levels + 2) * 4
    down = (2 * patterns + 2) * 4
    return topics * (patterns * ROW_BYTES + up + down)


def peak(device_kind: str) -> dict:
    with open(
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json"),
        encoding="utf-8",
    ) as f:
        peaks = json.load(f)
    if device_kind not in peaks or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to peaks.json")
    return peaks[device_kind]


def least_seconds(topics: int, patterns: int, levels: int, device_kind: str) -> float:
    return match_bytes(topics, patterns, levels) / peak(device_kind)["hbm_bytes_per_s"]
