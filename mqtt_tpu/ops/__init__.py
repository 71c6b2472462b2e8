"""TPU device plane: the batched wildcard topic matcher.

This package lifts the reference's hot loop — ``TopicsIndex.Subscribers()``
(reference topics.go:583-628), the wildcard trie walk executed once per
PUBLISH — onto the TPU as a multi-probe flat-hash join:

- ``flat``     — compiles the host trie into a device-resident flat hash
                 table keyed by whole-path hashes; the jitted match kernel
- ``hashing``  — host-side topic-level tokenization and dual u32 hashing
- ``matcher``  — the broker-facing ``TpuMatcher`` (drop-in for
                 ``TopicsIndex.subscribers``)
- ``delta``    — ``DeltaMatcher``: snapshot + host delta overlay +
                 background rebuild, for live brokers under churn

The host trie in ``mqtt_tpu.topics`` remains the bit-identical oracle and
the fallback path (saturation and over-deep routes, in-flight delta windows).
"""

from .delta import DeltaMatcher
from .flat import (
    FlatIndex,
    KIND_CLIENT,
    KIND_INLINE,
    KIND_SHARED,
    SubEntry,
    build_flat_index,
    flat_match_core,
)
from .hashing import hash_token, tokenize_topics
from .matcher import MatcherStats, TpuMatcher, expand_sids

__all__ = [
    "DeltaMatcher",
    "FlatIndex",
    "KIND_CLIENT",
    "KIND_INLINE",
    "KIND_SHARED",
    "MatcherStats",
    "SubEntry",
    "TpuMatcher",
    "build_flat_index",
    "expand_sids",
    "flat_match_core",
    "hash_token",
    "tokenize_topics",
]
