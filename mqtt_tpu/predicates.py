"""MQTT+ payload-predicate subscriptions (ROADMAP item 4; arxiv 1810.00773).

An MQTT+ client appends an in-broker payload filter to a standard
SUBSCRIBE filter — ``sensors/+/temp$GT{25.0}``, ``alerts/#$CONTAINS{alarm}``
— or an aggregation window — ``sensors/+/temp$MEAN{temp:10}`` — and the
broker delivers only the publishes whose payload satisfies the predicate
(TD-MQTT-style transparent syntax, arxiv 2406.02731: the extension rides
unmodified SUBSCRIBE packets; a broker without it would treat the filter
as literal).

The expensive part — evaluating predicates over very large subscription
populations per publish — is exactly the shape the device matcher was
built for, so the subsystem splits host/device the same way the topic
matcher does:

- :func:`mqtt_tpu.topics.split_predicate_suffix` strips the suffix at
  SUBSCRIBE time; the trie only ever sees the base filter (retained
  matching, $SHARE parsing, and SUBACK validation are byte-identical to
  a plain subscription).
- :class:`PredicateEngine` interns each distinct suffix into a
  :class:`CompiledRule` (op-code, field slot, float32 threshold,
  contains-bit) and compiles the live rule set into the vectorized
  device rule table (:mod:`mqtt_tpu.ops.predicates`), rebuilt lazily on
  registry generation bumps — the same snapshot discipline as the CSR
  trie.
- Per publish the HOST extracts payload features once — a float32
  vector over the registered field slots plus a contains-bitmask over
  the registered substrings — and the staging loop
  (:mod:`mqtt_tpu.staging`) ships the feature batch to the device
  alongside the tokenized topics: rule evaluation rides the SAME staged
  batch as topic matching, and fan-out receives the already-filtered
  subscriber set.
- The host interpreter (:func:`eval_rule_host`) is both the
  differential oracle (sampled device decisions are re-derived from the
  raw payload and compared bit-for-bit) and the degradation target: a
  :class:`~mqtt_tpu.resilience.CircuitBreaker` (the PR 1 pattern) trips
  device evaluation onto the host path on repeated failures and probes
  it back closed.

Skip-to-pass semantics: a numeric predicate whose field is missing, not
numeric, or whose payload is not JSON evaluates to PASS — the predicate
is a refinement, never a reason to silently drop telemetry a plain
subscription would have delivered. Thresholds and extracted values are
coerced to float32 on BOTH paths so host and device agree bit-for-bit.

Aggregation windows (``$MEAN{field:N}`` / ``$MAX`` / ``$MIN``) withhold
raw delivery and accumulate the extracted value per (rule, subscriber);
every Nth matched sample emits one synthesized publish carrying the
aggregate — the window rides the staging batch clock (emission happens
during the fan-out that completed the window), no extra timers.
"""

from __future__ import annotations

import json
import logging
import math
import zlib
from dataclasses import dataclass
from typing import Any, Callable, Optional

import numpy as np

from .topics import (
    PREDICATE_AGG_OPS,
    PREDICATE_COMPOUND_OPS,
    PREDICATE_NUMERIC_OPS,
    Subscribers,
    split_predicate_suffix,
    split_predicate_tokens,
)
from .utils.locked import InstrumentedLock

_log = logging.getLogger("mqtt_tpu.predicates")

# op codes shared with the device kernel (mqtt_tpu.ops.predicates)
OP_NONE = 0
OP_GT = 1
OP_GTE = 2
OP_LT = 3
OP_LTE = 4
OP_EQ = 5
OP_NE = 6
OP_CONTAINS = 7
# aggregation ops are host-only (stateful windows never run on device)
OP_MEAN = 8
OP_MAX = 9
OP_MIN = 10
# string equality ($EQS{field:literal}): device path rides the
# host-computed bitmask exactly like CONTAINS — the host interns the
# (field, literal) pair and sets the verdict bit once per publish
OP_EQS = 11
# compound ops ($AND{...}/$OR{...}): the CHILDREN compile to ordinary
# device rows; the boolean combine happens host-side from the child bits
OP_AND = 12
OP_OR = 13

_OP_CODES = {
    "GT": OP_GT,
    "GTE": OP_GTE,
    "LT": OP_LT,
    "LTE": OP_LTE,
    "EQ": OP_EQ,
    "NE": OP_NE,
    "CONTAINS": OP_CONTAINS,
    "MEAN": OP_MEAN,
    "MAX": OP_MAX,
    "MIN": OP_MIN,
    "EQS": OP_EQS,
    "AND": OP_AND,
    "OR": OP_OR,
}
_AGG_CODES = {OP_MEAN, OP_MAX, OP_MIN}
_COMPOUND_CODES = {OP_AND, OP_OR}


@dataclass(frozen=True)
class PredicateSpec:
    """One parsed predicate: the semantic form of a ``$OP{arg}`` suffix."""

    op: int  # OP_* code
    field: str = ""  # JSON field name; "" = whole payload as the number
    value: float = 0.0  # comparison threshold (numeric ops)
    text: bytes = b""  # substring (CONTAINS) / literal utf-8 (EQS)
    window: int = 0  # sample count per emission (aggregation ops)
    children: tuple = ()  # member specs (AND/OR compounds only)

    @property
    def is_agg(self) -> bool:
        return self.op in _AGG_CODES

    @property
    def is_compound(self) -> bool:
        return self.op in _COMPOUND_CODES


def predicate_digest(suffix: str) -> int:
    """The 32-bit interning digest of one predicate suffix — the key the
    mesh edge summaries carry (mqtt_tpu.cluster predicate push-down) and
    receivers cache compiled specs under. CRC32 over the literal suffix
    text: deterministic across processes (two workers must agree on the
    digest of the same interned rule), and a collision only merges two
    rules' cache slots — the suffix itself always travels beside the
    digest, so evaluation never trusts the digest alone."""
    return zlib.crc32(suffix.encode("utf-8", "surrogatepass"))


def compile_suffix(suffix: str) -> PredicateSpec:
    """Compile a validated ``$OP{arg}`` suffix (as returned by
    ``split_predicate_suffix``) into its spec. Raises ValueError on
    malformed input — callers pass only pre-validated suffixes."""
    if not suffix.startswith("$") or not suffix.endswith("}"):
        raise ValueError(f"not a predicate suffix: {suffix!r}")
    op_name, _, arg = suffix[1:-1].partition("{")
    code = _OP_CODES.get(op_name)
    if code is None:
        raise ValueError(f"unknown predicate op: {op_name!r}")
    if op_name in PREDICATE_COMPOUND_OPS:
        tokens = split_predicate_tokens(arg)
        if not tokens:
            raise ValueError(f"malformed compound predicate: {suffix!r}")
        children = tuple(compile_suffix(t) for t in tokens)
        return PredicateSpec(op=code, children=children)
    if code == OP_CONTAINS:
        if not arg:
            raise ValueError("empty $CONTAINS argument")
        return PredicateSpec(op=code, text=arg.encode("utf-8"))
    if code == OP_EQS:
        field_part, sep, literal = arg.partition(":")
        if not sep:
            raise ValueError(f"malformed $EQS argument: {arg!r}")
        return PredicateSpec(
            op=code, field=field_part, text=literal.encode("utf-8")
        )
    field_part, _, num = arg.rpartition(":")
    if op_name in PREDICATE_AGG_OPS:
        window = int(num)
        if window < 1:
            raise ValueError(f"aggregation window must be >= 1: {suffix!r}")
        return PredicateSpec(op=code, field=field_part, window=window)
    if op_name not in PREDICATE_NUMERIC_OPS:  # pragma: no cover - map is total
        raise ValueError(f"unhandled predicate op: {op_name!r}")
    value = float(num)
    if math.isnan(value):
        raise ValueError("nan threshold")
    return PredicateSpec(op=code, field=field_part, value=value)


# -- payload feature extraction (once per publish, on the host) ------------


def payload_number(payload: bytes, field: str, doc: Any = None) -> float:
    """Extract the numeric feature ``field`` from a payload; NaN when the
    payload has no such number (skip-to-pass upstream). ``field=""``
    reads the whole payload as one number. A dotted field
    (``battery.level``) traverses nested JSON objects — unless the
    payload carries the dotted string as a FLAT key, which wins (a
    pre-nested-paths deployment whose devices publish literal dotted
    keys keeps its exact semantics). ``doc`` is an optional pre-parsed
    JSON document (or any non-dict marker) so a publish with several
    field rules parses its payload once."""
    if field == "":
        try:
            return float(payload)
        except ValueError:
            return math.nan
    if doc is None:
        try:
            doc = json.loads(payload)
        except (ValueError, UnicodeDecodeError):
            doc = _NOT_JSON
    if not isinstance(doc, dict):
        return math.nan
    v = doc.get(field)
    if v is None and "." in field and field not in doc:
        # nested path (ISSUE 12 satellite / PR 8 residual): walk the
        # dotted segments through nested objects; any non-object hop or
        # missing key is NaN (skip-to-pass, like a missing flat field)
        v = doc
        for seg in field.split("."):
            if not isinstance(v, dict):
                v = None
                break
            v = v.get(seg)
    # bool is an int subclass: True > 0.5 would be a surprising predicate
    if isinstance(v, (int, float)) and not isinstance(v, bool):
        return float(v)
    return math.nan


_NOT_JSON = object()  # sentinel: payload parsed and found not-a-JSON-object


def payload_string(payload: bytes, field: str, doc: Any = None) -> Optional[str]:
    """Extract the STRING feature ``field`` from a JSON payload; None
    when the payload has no such string (skip-to-pass upstream). Same
    flat-key-wins dotted traversal as :func:`payload_number`."""
    if doc is None:
        try:
            doc = json.loads(payload)
        except (ValueError, UnicodeDecodeError):
            doc = _NOT_JSON
    if not isinstance(doc, dict):
        return None
    v = doc.get(field)
    if v is None and "." in field and field not in doc:
        v = doc
        for seg in field.split("."):
            if not isinstance(v, dict):
                v = None
                break
            v = v.get(seg)
    return v if isinstance(v, str) else None


def eval_equals(payload: bytes, field: str, text: bytes, doc: Any = None) -> bool:
    """The $EQS verdict — shared by the host interpreter AND the feature
    extractor (the device gathers the host-computed bit, so both paths
    are this function by construction). ``field=""`` compares the whole
    payload bytes; a missing or non-string field skips to PASS."""
    if field == "":
        return payload == text
    v = payload_string(payload, field, doc)
    if v is None:
        return True  # skip-to-pass: the predicate does not apply
    return v.encode("utf-8") == text


def eval_rule_host(spec: PredicateSpec, payload: bytes, doc: Any = None) -> bool:
    """The host predicate interpreter — the differential oracle for the
    device kernel and the degradation path when the breaker is open.
    Numeric comparisons coerce both sides to float32 so the verdict is
    bit-identical to the device's. Compounds recurse over their member
    specs (one JSON parse shared across every child)."""
    if spec.children:
        if doc is None and any(c.field for c in spec.children):
            try:
                doc = json.loads(payload)
            except (ValueError, UnicodeDecodeError):
                doc = _NOT_JSON
        verdicts = (eval_rule_host(c, payload, doc) for c in spec.children)
        return all(verdicts) if spec.op == OP_AND else any(verdicts)
    if spec.op == OP_CONTAINS:
        return spec.text in payload
    if spec.op == OP_EQS:
        return eval_equals(payload, spec.field, spec.text, doc)
    v = payload_number(payload, spec.field, doc)
    if math.isnan(v):
        return True  # skip-to-pass: the predicate does not apply
    v32 = np.float32(v)
    t32 = np.float32(spec.value)
    if spec.op == OP_GT:
        return bool(v32 > t32)
    if spec.op == OP_GTE:
        return bool(v32 >= t32)
    if spec.op == OP_LT:
        return bool(v32 < t32)
    if spec.op == OP_LTE:
        return bool(v32 <= t32)
    if spec.op == OP_EQ:
        return bool(v32 == t32)
    return bool(v32 != t32)  # OP_NE (agg ops never reach the interpreter)


class PublishFeatures:
    """One publish's extracted payload features — the per-publish carrier
    through the staging pipeline. Built on the event loop by
    ``PredicateEngine.features_for``; the stage batches the vectors to
    the device and attaches the resolved pass-bit row back here, so the
    fan-out path's ``apply`` finds the device verdicts without any
    side-channel."""

    __slots__ = ("payload", "fvec", "cmask", "version", "device_row", "row_gen")

    def __init__(
        self,
        payload: bytes,
        fvec: np.ndarray,
        cmask: np.ndarray,
        version: int,
    ) -> None:
        self.payload = payload
        self.fvec = fvec  # float32 [n_slots]
        self.cmask = cmask  # uint32 [n_contains_words]
        self.version = version  # registry generation the vectors match
        self.device_row: Optional[np.ndarray] = None  # uint32 pass bits
        self.row_gen = -1  # device-table generation of device_row


@dataclass
class CompiledRule:
    """One interned predicate: spec + registry bookkeeping + its dense
    index in the current device rule table (-1 = host-only: aggregation
    rules, and rules past ``max_rules``).

    ``idx`` is only meaningful paired with ``idx_gen`` — the table
    generation it was assigned at. A pass-bit row decodes through
    ``idx`` only when the row's generation equals ``idx_gen``, so a
    rebuild racing an in-flight publish can never mis-decode (the
    rebuild invalidates ``idx_gen`` BEFORE moving ``idx``)."""

    spec: PredicateSpec
    slot: int = -1  # field slot in the feature vector (-1: CONTAINS/EQS)
    cbit: int = -1  # verdict bitmask bit (-1: numeric/agg/compound)
    refs: int = 0  # live subscriptions referencing this rule
    idx: int = -1  # dense row in the device table (valid per idx_gen)
    idx_gen: int = -1  # table generation idx belongs to
    device: bool = True  # eligible for the device table at all
    children: tuple = ()  # member suffixes (compounds; refcounted rules)


class _AggWindow:
    """One (rule, subscriber) aggregation accumulator.

    Small windows accumulate in O(1) state (running total / best —
    reducing them on device would cost more dispatch than it saves).
    LARGE windows (``PredicateEngine.device_agg_min_window``) BUFFER the
    raw samples instead: completed buffers from one fan-out tick reduce
    in ONE fused device dispatch (ops/predicates.agg_reduce), and only
    the aggregates come back — the PR 8 carried-over residual."""

    __slots__ = ("count", "total", "best", "values")

    def __init__(self, buffered: bool = False) -> None:
        self.count = 0
        self.total = 0.0
        self.best = math.nan
        self.values: Optional[list[float]] = [] if buffered else None

    def add(self, op: int, v: float) -> None:
        self.count += 1
        if self.values is not None:
            self.values.append(v)
            return
        self.total += v
        if math.isnan(self.best):
            self.best = v
        elif op == OP_MAX:
            self.best = max(self.best, v)
        elif op == OP_MIN:
            self.best = min(self.best, v)

    def emit(self, op: int) -> float:
        # unbuffered windows only: buffered completions drain through
        # take_values() into the fused device/host reduction instead
        assert self.values is None
        value = self.total / self.count if op == OP_MEAN else self.best
        self.count = 0
        self.total = 0.0
        self.best = math.nan
        return value

    def take_values(self) -> list[float]:
        """Drain the buffered samples (buffered windows only)."""
        assert self.values is not None
        vals = self.values
        self.values = []
        self.count = 0
        return vals


def host_reduce_window(op: int, values: list[float]) -> float:
    """The host window reduction — the differential oracle for the
    device ``agg_reduce`` kernel and the degradation path when it is
    unavailable. MAX/MIN reduce over float32-coerced samples (the
    device's dtype; float32 rounding is monotone, so the coerced
    reduction picks the same element the device does — host fallback
    and device path stay bit-identical). MEAN accumulates in float64
    (the device reduces in float32 — the sampled oracle compares with
    a relative tolerance)."""
    if op == OP_MEAN:
        return sum(values) / len(values)
    vals32 = [float(np.float32(v)) for v in values]
    return max(vals32) if op == OP_MAX else min(vals32)


class PredicateEngine:
    """The broker's predicate plane: suffix registry, feature extraction,
    device-batch evaluation with breaker degradation, result-set
    filtering, aggregation windows, and the sampled differential oracle.

    Registry mutation (subscribe/unsubscribe) takes ``_lock``; the
    publish path reads interned rules without it (dict reads are atomic
    and a racing mutation only flips a publish between the device and
    host paths — both bit-identical)."""

    def __init__(
        self,
        max_rules: int = 1 << 20,
        oracle_sample: int = 64,
        breaker=None,
        registry=None,
        device_agg_min_window: int = 32,
    ) -> None:
        self.max_rules = max(1, max_rules)
        self.oracle_sample = max(0, oracle_sample)
        # aggregation windows at least this wide buffer raw samples and
        # reduce on device in one fused dispatch per fan-out tick
        # (ops/predicates.agg_reduce); smaller windows keep the O(1)
        # host accumulator. <= 0 disables device reductions entirely.
        self.device_agg_min_window = device_agg_min_window
        # the device dispatch engages only when one fan-out tick
        # completed at least this many windows (the mass-fan-out shape
        # the reduction is for): the samples are host-resident, so a
        # single window's round trip would only add link latency —
        # the host reduction serves it in microseconds
        self.device_agg_min_batch = 4
        self._lock = InstrumentedLock("predicate_rules")
        self._rules: dict[str, CompiledRule] = {}
        self._fields: dict[str, int] = {}  # field name -> feature slot
        # the verdict bitmask is ONE shared bit space: CONTAINS interns
        # substrings, EQS interns (field, literal) pairs — bits are
        # allocated from the combined counter and stay monotonic until
        # the whole rule set drains (same discipline as field slots)
        self._contains: dict[bytes, int] = {}  # substring -> bitmask bit
        self._equals: dict[tuple[str, bytes], int] = {}  # (field, lit) -> bit
        self._gen = 0  # bumped on every registry mutation
        self._table_gen = -1  # generation the device table was built at
        # mqtt_tpu.ops.predicates.DeviceRuleEvaluator, built lazily on
        # the first predicated batch (Any: ops must stay import-light)
        self._evaluator: Optional[Any] = None
        self._device_enabled = True
        # degradation manager (the PR 1 ResilientMatcher pattern): device
        # eval failures trip evaluation onto the host interpreter; probes
        # re-admit the device once verified healthy
        if breaker is None:
            from .resilience import CircuitBreaker

            breaker = CircuitBreaker(failure_threshold=3)
        self.breaker = breaker
        # aggregation windows: (suffix, subscriber key) -> accumulator.
        # Touched only on the fan-out path (event loop), no lock needed.
        self._agg: dict[tuple[str, str], _AggWindow] = {}
        # counters ($SYS/broker/predicates/* + mqtt_tpu_predicate_*)
        self.device_evals = 0  # rule evaluations performed on device
        self.host_evals = 0  # rule evaluations by the host interpreter
        self.device_decisions = 0  # delivery verdicts taken from device bits
        self.filtered = 0  # deliveries suppressed by a failing predicate
        self.deliveries = 0  # predicated deliveries that passed
        self.agg_emits = 0  # synthesized aggregate publishes emitted
        self.agg_device_reductions = 0  # windows reduced on device
        self.oracle_checks = 0
        self.oracle_mismatches = 0
        self.device_batches = 0
        self.device_errors = 0
        self._apply_seq = 0  # oracle sampling clock (1-in-N publishes)
        if registry is not None:
            self._register_metrics(registry)

    # -- registry ----------------------------------------------------------

    @property
    def rule_count(self) -> int:
        return len(self._rules)

    @property
    def active(self) -> bool:
        """Any live rules at all? False keeps every publish path at one
        attribute read — the bit-identical pre-MQTT+ fast-out."""
        return bool(self._rules)

    @property
    def generation(self) -> int:
        return self._gen

    def parse_subscribe(self, filter: str) -> tuple[str, tuple]:
        """Split + register a SUBSCRIBE filter's predicate. Returns
        ``(base_filter, predicates)`` where ``predicates`` is the tuple
        to store on the Subscription (() = plain subscription)."""
        base, suffix = split_predicate_suffix(filter)
        if not suffix:
            return filter, ()
        self.register(suffix)
        return base, (suffix,)

    def register(self, suffix: str) -> CompiledRule:
        """Intern one predicate suffix (refcounted)."""
        with self._lock:
            return self._register_locked(suffix)

    def _register_locked(self, suffix: str) -> CompiledRule:
        rule = self._rules.get(suffix)
        if rule is not None:
            rule.refs += 1
            return rule
        spec = compile_suffix(suffix)
        rule = CompiledRule(spec=spec, refs=1)
        if spec.children:
            # compound: each member interns as its OWN (device-eligible)
            # rule holding one parent reference; the compound row never
            # enters the device table — _rule_passes combines the child
            # bits host-side, so the members still evaluate on device
            op_name, _, arg = suffix[1:-1].partition("{")
            tokens = split_predicate_tokens(arg)
            for t in tokens:
                self._register_locked(t)
            rule.children = tokens
        elif spec.op == OP_CONTAINS:
            bit = self._contains.get(spec.text)
            if bit is None:
                bit = self._contains[spec.text] = len(self._contains) + len(
                    self._equals
                )
            rule.cbit = bit
        elif spec.op == OP_EQS:
            key = (spec.field, spec.text)
            bit = self._equals.get(key)
            if bit is None:
                bit = self._equals[key] = len(self._contains) + len(
                    self._equals
                )
            rule.cbit = bit
        else:
            slot = self._fields.get(spec.field)
            if slot is None:
                slot = self._fields[spec.field] = len(self._fields)
            rule.slot = slot
        # aggregation is host-state, compounds are host-combined; rules
        # past the table cap stay host-interpreted (degraded, never
        # refused)
        rule.device = (
            not spec.is_agg
            and not spec.children
            and len(self._rules) < self.max_rules
        )
        self._rules[suffix] = rule
        self._gen += 1
        return rule

    def release(self, predicates: tuple) -> None:
        """Drop one reference per suffix (unsubscribe / replace)."""
        if not predicates:
            return
        with self._lock:
            for suffix in predicates:
                self._release_locked(suffix)
            if not self._rules:
                self._fields.clear()
                self._contains.clear()
                self._equals.clear()
                self._agg.clear()

    def _release_locked(self, suffix: str) -> None:
        rule = self._rules.get(suffix)
        if rule is None:
            return
        rule.refs -= 1
        if rule.refs <= 0:
            del self._rules[suffix]
            self._gen += 1
            # a dying compound drops its one reference on each member
            for child in rule.children:
                self._release_locked(child)
            # field slots / verdict bits are monotonic: vectors stay
            # index-stable across releases, and the widths only reset
            # when the whole rule set drains

    # -- feature extraction ------------------------------------------------

    def features_for(self, payload: bytes) -> PublishFeatures:
        """Extract one publish's payload features (parsed ONCE on the
        host): the float32 field vector + the contains bitmask, stamped
        with the registry generation the layout belongs to."""
        # list() snapshots: an embedder-thread subscribe growing the
        # registry mid-iteration must not tear this publish's extraction
        # (the gen stamp below keeps a raced row off the device anyway)
        gen = self._gen
        fields = list(self._fields.items())
        contains = list(self._contains.items())
        equals = list(self._equals.items())
        fvec = np.empty(max(1, len(fields)), dtype=np.float32)
        doc: Any = None
        if any(name != "" for name, _ in fields) or any(
            f != "" for (f, _t), _ in equals
        ):
            try:
                doc = json.loads(payload)
            except (ValueError, UnicodeDecodeError):
                doc = _NOT_JSON
        for name, slot in fields:
            if slot < fvec.shape[0]:
                fvec[slot] = np.float32(payload_number(payload, name, doc))
        n_bits = len(contains) + len(equals)
        mask = np.zeros(max(1, (n_bits + 31) // 32), dtype=np.uint32)
        for text, bit in contains:
            if text in payload and (bit >> 5) < mask.shape[0]:
                mask[bit >> 5] |= np.uint32(1 << (bit & 31))
        for (field, text), bit in equals:
            if (bit >> 5) < mask.shape[0] and eval_equals(
                payload, field, text, doc
            ):
                mask[bit >> 5] |= np.uint32(1 << (bit & 31))
        return PublishFeatures(payload, fvec, mask, gen)

    # -- device evaluation (rides the staged batch) ------------------------

    def set_device_enabled(self, enabled: bool) -> None:
        self._device_enabled = enabled

    def _device_rules(self) -> list[CompiledRule]:
        # list() snapshots atomically under the GIL: callers iterate
        # while an embedder-thread subscribe may mutate the dict
        return [r for r in list(self._rules.values()) if r.device]

    def _rebuild_evaluator(self) -> None:
        """(Re)compile the live rule set into the device table — dense
        rule indices are assigned here and stamped with the generation,
        so a pass-bit row can never be decoded against a different
        table's layout."""
        from .ops.predicates import DeviceRuleEvaluator

        gen = self._gen
        rules = self._device_rules()
        for i, rule in enumerate(rules):
            # invalidate-then-move: a concurrent publish decoding an
            # OLD pass-bit row reads (idx, idx_gen) without the lock;
            # clearing the gen first means it can never pair a new idx
            # with a stale generation check
            rule.idx_gen = -1
            rule.idx = i
        if self._evaluator is None:
            self._evaluator = DeviceRuleEvaluator()
        self._evaluator.rebuild(
            [r.spec for r in rules],
            [r.slot for r in rules],
            [r.cbit for r in rules],
            n_slots=max(1, len(self._fields)),
            n_cwords=max(
                1, (len(self._contains) + len(self._equals) + 31) // 32
            ),
        )
        self._table_gen = gen
        for rule in rules:
            rule.idx_gen = gen  # indices valid for this table generation

    def eval_batch_async(self, feats_list: list) -> Optional[Callable]:
        """Issue ONE device evaluation for a staged batch's features.
        Returns a zero-arg resolver yielding the packed pass-bit rows
        (``uint32 [B, ceil(R/32)]``) — or None when the device path is
        unavailable (no device rules, breaker open, import failure); the
        caller then leaves evaluation to the host interpreter at apply
        time. The resolver NEVER raises: failures are recorded on the
        breaker and surface as a None row set."""
        if not self._device_enabled or not any(
            f is not None for f in feats_list
        ):
            return None
        # work-existence checks run BEFORE the breaker gate: a batch with
        # no device-eligible rules or rows must neither consume the
        # half-open probe slot nor count as a verified probe
        if not any(r.device for r in list(self._rules.values())):
            return None
        gen_now = self._gen
        if not any(
            f is not None and f.version == gen_now for f in feats_list
        ):
            return None
        breaker = self.breaker
        probing = False
        if not breaker.allow():
            if not breaker.acquire_probe():
                return None  # degraded: host interpreter serves this batch
            probing = True
        try:
            with self._lock:
                if self._table_gen != self._gen:
                    self._rebuild_evaluator()
                evaluator = self._evaluator
                gen = self._table_gen
            if evaluator is None or evaluator.n_rules == 0:
                # every device rule was released between the pre-check
                # and the rebuild: not a device fault, nothing to probe
                if probing:
                    breaker.record_probe_failure("raced")
                return None
            n_slots, n_cwords = evaluator.n_slots, evaluator.n_cwords
            B = len(feats_list)
            F = np.zeros((B, n_slots), dtype=np.float32)
            M = np.zeros((B, n_cwords), dtype=np.uint32)
            eligible = []
            for i, f in enumerate(feats_list):
                # a feature row built against an older registry layout
                # (subscribe raced the batch) keeps its host path
                if f is None or f.version != gen:
                    continue
                F[i, : f.fvec.shape[0]] = f.fvec
                M[i, : f.cmask.shape[0]] = f.cmask
                eligible.append(i)
            if not eligible:
                # the registry moved between the pre-check and the
                # rebuild (raced subscribe): nothing device-decidable
                if probing:
                    breaker.record_probe_failure("raced")
                return None
            resolver = evaluator.eval_async(F, M)
        except Exception:
            _log.exception("predicate device eval issue failed; host path")
            self.device_errors += 1
            if probing:
                breaker.record_probe_failure("issue")
            else:
                breaker.record_failure("issue")
            return None

        n_rules = evaluator.n_rules

        def resolve() -> Optional[tuple]:
            try:
                rows = resolver()
            except Exception:
                _log.exception(
                    "predicate device eval resolve failed; host path"
                )
                self.device_errors += 1
                if probing:
                    self.breaker.record_probe_failure("resolve")
                else:
                    self.breaker.record_failure("resolve")
                return None
            if probing:
                self.breaker.record_probe_success()
            else:
                self.breaker.record_success()
            self.device_batches += 1
            self.device_evals += len(eligible) * n_rules
            return rows, eligible, gen

        return resolve

    def attach_rows(self, feats_list: list, resolved: Optional[tuple]) -> None:
        """Stamp resolved device pass-bit rows onto their feature
        carriers (called by the staging drain loop before futures
        complete)."""
        if resolved is None:
            return
        rows, eligible, gen = resolved
        for i in eligible:
            f = feats_list[i]
            if f is not None:
                f.device_row = rows[i]
                f.row_gen = gen

    # -- delivery filtering (the fan-out choke point) ----------------------

    def _doc(self, payload: bytes, memo: list) -> Any:
        """The publish's parsed JSON document, computed at most once per
        publish however many rules/subscribers consult it (the host
        path's analog of features_for's single parse)."""
        if memo[0] is None:
            try:
                memo[0] = json.loads(payload)
            except (ValueError, UnicodeDecodeError):
                memo[0] = _NOT_JSON
        return memo[0]

    def _rule_passes(
        self, rule: CompiledRule, payload: bytes, feats, oracle: bool, memo: list
    ) -> bool:
        spec = rule.spec
        if rule.children:
            # compound: combine the member verdicts — each member is its
            # own interned rule, so each rides the device pass-bit row
            # when one is attached (the compound itself has no table row)
            verdicts = []
            for sfx, cspec in zip(rule.children, spec.children):
                crule = self._rules.get(sfx)
                if crule is not None:
                    verdicts.append(
                        self._rule_passes(crule, payload, feats, oracle, memo)
                    )
                else:
                    # member released mid-flight (raced unsubscribe):
                    # evaluate its spec directly, same verdict either way
                    self.host_evals += 1
                    verdicts.append(
                        eval_rule_host(
                            cspec,
                            payload,
                            self._doc(payload, memo) if cspec.field else None,
                        )
                    )
            return all(verdicts) if spec.op == OP_AND else any(verdicts)
        # read idx BEFORE idx_gen: the rebuild path invalidates idx_gen
        # first, so a generation match here guarantees the idx we read
        # belongs to the row's table (see _rebuild_evaluator)
        idx = rule.idx
        if (
            feats is not None
            and feats.device_row is not None
            and idx >= 0
            and rule.idx_gen == feats.row_gen
        ):
            bit = bool((feats.device_row[idx >> 5] >> np.uint32(idx & 31)) & 1)
            self.device_decisions += 1
            if oracle:
                self.oracle_checks += 1
                want = eval_rule_host(
                    spec,
                    payload,
                    self._doc(payload, memo) if spec.field else None,
                )
                if want != bit:
                    self.oracle_mismatches += 1
                    _log.warning(
                        "predicate oracle mismatch: device=%s host=%s "
                        "op=%d field=%r value=%r payload[:64]=%r",
                        bit,
                        want,
                        spec.op,
                        spec.field,
                        spec.value,
                        payload[:64],
                    )
                    return want  # the host interpreter is ground truth
            return bit
        self.host_evals += 1
        return eval_rule_host(
            spec, payload, self._doc(payload, memo) if spec.field else None
        )

    def _decide(
        self,
        predicates: tuple,
        payload: bytes,
        feats,
        agg_key: str,
        oracle: bool,
        memo: list,
    ) -> tuple[bool, list, list]:
        """One subscriber's verdict: ``(deliver_raw, emissions,
        pending)`` where emissions are (suffix, value) aggregate
        completions and pending are ``(op, values)`` BUFFERED window
        completions the caller reduces on device (one fused dispatch for
        every window the fan-out tick completed). OR semantics across
        the subscriber's predicates; aggregation rules withhold raw
        delivery and accumulate instead."""
        deliver = False
        saw_filter = False
        emissions: list = []
        pending: list = []
        for suffix in predicates:
            rule = self._rules.get(suffix)
            if rule is None:
                # released mid-flight (unsubscribe raced the walk):
                # fail open, exactly like an unpredicated subscription
                deliver = True
                saw_filter = True
                continue
            spec = rule.spec
            if spec.is_agg:
                v = payload_number(
                    payload,
                    spec.field,
                    self._doc(payload, memo) if spec.field else None,
                )
                if not math.isnan(v):
                    win = self._agg.get((suffix, agg_key))
                    if win is None:
                        buffered = (
                            self.device_agg_min_window > 0
                            and spec.window >= self.device_agg_min_window
                            and self._device_enabled
                        )
                        win = self._agg[(suffix, agg_key)] = _AggWindow(
                            buffered
                        )
                    win.add(spec.op, v)
                    if win.count >= spec.window:
                        if win.values is not None:
                            pending.append((spec.op, win.take_values()))
                        else:
                            emissions.append((suffix, win.emit(spec.op)))
                continue
            saw_filter = True
            if not deliver and self._rule_passes(
                rule, payload, feats, oracle, memo
            ):
                deliver = True
        # an aggregation-only subscription receives ONLY synthesized
        # aggregates; mixed subscriptions deliver raw when a filter passes
        return deliver if saw_filter else False, emissions, pending

    def apply(
        self, subs: Subscribers, payload: bytes, feats=None
    ) -> tuple[Subscribers, list]:
        """Filter one publish's matched subscriber set in place and
        collect aggregate emissions. Returns ``(subs, emissions)`` with
        emissions as ``(kind, target, sub, payload_bytes)`` tuples the
        fan-out delivers after the raw pass (kind "client": target is a
        client id; kind "inline": target is the InlineSubscription).

        Unpredicated subscriptions are untouched — when no rules are
        live the caller skips this entirely (``active``), keeping the
        pre-MQTT+ path bit-identical."""
        self._apply_seq += 1
        oracle = (
            self.oracle_sample > 0
            and self._apply_seq % self.oracle_sample == 0
        )
        memo: list = [None]  # one JSON parse per publish on the host path
        emissions: list = []
        # buffered large-window completions collected across EVERY
        # subscriber this publish matched, reduced in ONE fused device
        # dispatch after the walk (ops/predicates.agg_reduce)
        agg_pending: list = []
        drop: list = []
        for cid, sub in subs.subscriptions.items():
            preds = sub.predicates
            if not preds:
                continue
            deliver, emits, pend = self._decide(
                preds, payload, feats, cid, oracle, memo
            )
            for _suffix, value in emits:
                emissions.append(("client", cid, sub, _format_agg(value)))
            for op, values in pend:
                agg_pending.append(("client", cid, sub, op, values))
            if deliver:
                self.deliveries += 1
            else:
                drop.append(cid)
        if drop:
            self.filtered += len(drop)
            for cid in drop:
                del subs.subscriptions[cid]
        # shared groups: drop failing members BEFORE group selection so a
        # passing member is picked when one exists
        if subs.shared:
            empty: list = []
            for gfilter, members in subs.shared.items():
                gdrop: list = []
                for cid, sub in members.items():
                    if not sub.predicates:
                        continue
                    deliver, emits, pend = self._decide(
                        sub.predicates,
                        payload,
                        feats,
                        "$share:" + gfilter,
                        oracle,
                        memo,
                    )
                    for _suffix, value in emits:
                        emissions.append(
                            ("client", cid, sub, _format_agg(value))
                        )
                    for op, values in pend:
                        agg_pending.append(("client", cid, sub, op, values))
                    if deliver:
                        self.deliveries += 1
                    else:
                        gdrop.append(cid)
                if gdrop:
                    self.filtered += len(gdrop)
                    for cid in gdrop:
                        del members[cid]
                if not members:
                    empty.append(gfilter)
            for gfilter in empty:
                del subs.shared[gfilter]
        if subs.inline_subscriptions:
            idrop: list = []
            for iid, isub in subs.inline_subscriptions.items():
                if not isub.predicates:
                    continue
                deliver, emits, pend = self._decide(
                    isub.predicates, payload, feats, f"$inline:{iid}", oracle, memo
                )
                for _suffix, value in emits:
                    emissions.append(("inline", isub, isub, _format_agg(value)))
                for op, values in pend:
                    agg_pending.append(("inline", isub, isub, op, values))
                if deliver:
                    self.deliveries += 1
                else:
                    idrop.append(iid)
            if idrop:
                self.filtered += len(idrop)
                for iid in idrop:
                    del subs.inline_subscriptions[iid]
        if agg_pending:
            self._flush_agg(agg_pending, emissions, oracle)
        if emissions:
            self.agg_emits += len(emissions)
        return subs, emissions

    def _flush_agg(
        self, agg_pending: list, emissions: list, oracle: bool
    ) -> None:
        """Reduce the buffered windows this fan-out tick completed in
        ONE fused device dispatch and append the synthesized emissions.
        Only the aggregates transfer back; the dispatch engages when the
        tick batched at least ``device_agg_min_batch`` windows AND the
        breaker admits the device (an open breaker serves every window
        from the host reduction silently — same never-drop posture as
        rule evaluation, never a per-tick failing dispatch)."""
        values_out = None
        if (
            len(agg_pending) >= max(1, self.device_agg_min_batch)
            and self._device_enabled
            and self.breaker.allow()
        ):
            try:
                from .ops.predicates import agg_reduce_batch

                values_out = agg_reduce_batch(
                    [(op, values) for _k, _t, _s, op, values in agg_pending]
                )
                self.breaker.record_success()
            except Exception:
                _log.exception("device window reduction failed; host path")
                self.device_errors += 1
                self.breaker.record_failure("agg")
                values_out = None
        if values_out is not None:
            self.agg_device_reductions += len(agg_pending)
            if oracle:
                # sampled differential: MAX/MIN must be bit-identical
                # (both sides reduce float32-coerced samples), MEAN
                # within float32 accumulation tolerance
                for got, (_k, _t, _s, op, values) in zip(
                    values_out, agg_pending
                ):
                    self.oracle_checks += 1
                    want = host_reduce_window(op, values)
                    tol = 1e-5 * max(1.0, abs(want)) if op == OP_MEAN else 0.0
                    if abs(float(got) - want) > tol:
                        self.oracle_mismatches += 1
                        _log.warning(
                            "window-reduction oracle mismatch: device=%r "
                            "host=%r op=%d n=%d",
                            float(got), want, op, len(values),
                        )
        for i, (kind, target, sub, op, values) in enumerate(agg_pending):
            if values_out is not None:
                value = float(values_out[i])
            else:
                value = host_reduce_window(op, values)
            emissions.append((kind, target, sub, _format_agg(value)))

    def passes_retained(self, sub, payload: bytes) -> bool:
        """Gate one retained message against a fresh subscription's
        predicates (the subscribe-time retained walk): filter rules
        apply; an aggregation-only subscription receives no retained
        messages (its deliveries are synthesized aggregates)."""
        preds = sub.predicates
        if not preds:
            return True
        deliver = False
        saw_filter = False
        memo: list = [None]  # one JSON parse per retained message
        for suffix in preds:
            rule = self._rules.get(suffix)
            if rule is None:
                return True
            spec = rule.spec
            if spec.is_agg:
                continue
            saw_filter = True
            self.host_evals += 1
            if eval_rule_host(
                spec, payload, self._doc(payload, memo) if spec.field else None
            ):
                deliver = True
        return deliver if saw_filter else False

    # -- observability -----------------------------------------------------

    def filtered_ratio(self) -> float:
        total = self.filtered + self.deliveries
        return self.filtered / total if total else 0.0

    def gauges(self) -> dict:
        """The $SYS/broker/predicates/* tree. Reads run off-lock: the
        list() snapshot is atomic under the GIL, so a racing subscribe
        can never tear the $SYS tick's iteration."""
        return {
            "rules": len(self._rules),
            "device_rules": sum(
                1 for r in list(self._rules.values()) if r.device
            ),
            "fields": len(self._fields),
            "contains": len(self._contains),
            "equals": len(self._equals),
            "device_evals": self.device_evals,
            "device_batches": self.device_batches,
            "device_decisions": self.device_decisions,
            "host_evals": self.host_evals,
            "filtered": self.filtered,
            "deliveries": self.deliveries,
            "filtered_ratio": round(self.filtered_ratio(), 6),
            "agg_emits": self.agg_emits,
            "agg_windows": len(self._agg),
            "agg_device_reductions": self.agg_device_reductions,
            "oracle_checks": self.oracle_checks,
            "oracle_mismatches": self.oracle_mismatches,
            "device_errors": self.device_errors,
            "breaker_state": self.breaker.state,
        }

    def _register_metrics(self, registry) -> None:
        """Prometheus families (mqtt_tpu.telemetry.MetricsRegistry)."""
        registry.gauge(
            "mqtt_tpu_predicate_rules",
            "Live interned payload-predicate rules",
            fn=lambda: len(self._rules),
        )
        for name, attr in (
            ("mqtt_tpu_predicate_evals_total", "device_evals"),
            ("mqtt_tpu_predicate_host_evals_total", "host_evals"),
            ("mqtt_tpu_predicate_filtered_total", "filtered"),
            ("mqtt_tpu_predicate_deliveries_total", "deliveries"),
            ("mqtt_tpu_predicate_agg_emits_total", "agg_emits"),
            (
                "mqtt_tpu_predicate_agg_device_reductions_total",
                "agg_device_reductions",
            ),
            ("mqtt_tpu_predicate_oracle_checks_total", "oracle_checks"),
            ("mqtt_tpu_predicate_oracle_mismatches_total", "oracle_mismatches"),
            ("mqtt_tpu_predicate_device_errors_total", "device_errors"),
        ):
            registry.counter(
                name,
                f"PredicateEngine.{attr}",
                fn=lambda a=attr: getattr(self, a),
            )
        registry.gauge(
            "mqtt_tpu_predicate_filtered_ratio",
            "Predicated deliveries suppressed / decided (selectivity)",
            fn=self.filtered_ratio,
        )


def _format_agg(value: float) -> bytes:
    """Serialize one aggregate emission payload (ASCII decimal)."""
    return b"%.10g" % value
