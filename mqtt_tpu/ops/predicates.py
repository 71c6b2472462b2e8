"""Device kernel for MQTT+ payload-predicate evaluation (ROADMAP item 4).

The host (mqtt_tpu.predicates) compiles the live predicate set into a
vectorized RULE TABLE — parallel arrays of op-code, feature slot, float32
threshold, and contains-bit — resident on device beside the flat topic
index. Per staged batch the broker ships the per-publish payload feature
matrix (float32 ``[B, S]`` field values + uint32 ``[B, W]`` contains
bitmask) and ONE fused kernel evaluates every rule for every publish:

- numeric ops gather each rule's feature column (``take`` along the slot
  axis) and compare against the threshold row; NaN features force PASS
  (skip-to-pass: a predicate whose field is absent does not apply);
- CONTAINS ops gather the rule's bit from the host-computed bitmask
  (substring search is host work — the registered substrings are
  interned, so it is O(distinct substrings) per publish, not per rule);
- the ``[B, R]`` verdict matrix is bit-packed on device into uint32
  ``[B, R/32]`` so the transfer back is 1 bit per (publish, rule) — at
  1M rules and a 64-publish batch that is 8MB, not 256MB of bools.

The evaluation is dispatched asynchronously in the SAME staged batch as
topic matching (mqtt_tpu.staging issues both before the drain loop's
single executor sync), so predicate filtering adds no extra device round
trip. Shapes are power-of-two bucketed like the flat matcher's, so churn
in rule count or batch size reuses a handful of jitted executables.
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

from .flat import _bucket, _LazyJit

# op codes — shared vocabulary with mqtt_tpu.predicates (host compiler)
OP_NONE = 0
OP_GT = 1
OP_GTE = 2
OP_LT = 3
OP_LTE = 4
OP_EQ = 5
OP_NE = 6
OP_CONTAINS = 7
# aggregation ops (host-stateful windows; the REDUCTION runs on device
# for large windows — agg_reduce below)
OP_MEAN = 8
OP_MAX = 9
OP_MIN = 10
# string equality: rides the host-computed bitmask exactly like CONTAINS
# (the host interns (field, literal) pairs and sets the bit per publish)
OP_EQS = 11
# compound ops never appear in the device table: their CHILDREN compile
# to ordinary rows and the boolean combine happens host-side per verdict
OP_AND = 12
OP_OR = 13


def rules_eval_core(op, slot, thresh, cbit, feats, cmask):
    """Evaluate ``R`` predicate rules over ``B`` publishes in one fused
    dispatch; returns packed pass bits ``uint32 [B, R // 32]`` (R is
    padded to a multiple of 32 by the caller).

    ``op``/``slot``/``thresh``/``cbit`` are the ``[R]`` rule table;
    ``feats`` is ``float32 [B, S]`` (NaN = feature absent), ``cmask``
    ``uint32 [B, W]`` (bit per interned substring, host-computed)."""
    import jax.numpy as jnp

    B = feats.shape[0]
    R = op.shape[0]
    f = jnp.take(feats, jnp.clip(slot, 0, feats.shape[1] - 1), axis=1)  # [B,R]
    t = thresh[None, :]
    nanp = jnp.isnan(f)
    res = jnp.select(
        [op == OP_GT, op == OP_GTE, op == OP_LT, op == OP_LTE, op == OP_EQ],
        [f > t, f >= t, f < t, f <= t, f == t],
        default=(f != t),  # OP_NE (and padding rows: don't-care)
    )
    # skip-to-pass: a NaN feature (missing field / non-numeric payload)
    # passes every numeric op — matching eval_rule_host bit-for-bit
    res = res | nanp
    cword = jnp.take(cmask, jnp.clip(cbit, 0, None) >> 5, axis=1)  # [B,R]
    cpass = ((cword >> (jnp.clip(cbit, 0, None) & 31).astype(jnp.uint32)) & 1) != 0
    bitop = (op[None, :] == OP_CONTAINS) | (op[None, :] == OP_EQS)
    res = jnp.where(bitop, cpass, res)
    bits = res.astype(jnp.uint32).reshape(B, R // 32, 32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))[None, None, :]
    return (bits * weights).sum(axis=2).astype(jnp.uint32)


def _jit_rules_eval():
    import jax

    return jax.jit(rules_eval_core)


rules_eval = _LazyJit(_jit_rules_eval, kernel="rules_eval")


def agg_reduce_core(vals, ops, counts):
    """Reduce ``W`` completed aggregation windows in ONE fused dispatch:
    ``vals`` is float32 ``[W, N]`` NaN-padded (window buffers packed by
    the host), ``ops`` int32 ``[W]`` (OP_MEAN/OP_MAX/OP_MIN), ``counts``
    int32 ``[W]`` live samples per window. Returns float32 ``[W]``.

    This is the PR 8 carried-over residual: large predicate windows ride
    a compact device reduction — only the ``W`` aggregates come back,
    the per-row value columns never materialize host-side. MEAN reduces
    in float32 (device-native); MAX/MIN are order-insensitive and
    bit-identical to the host interpreter."""
    import jax.numpy as jnp

    live = ~jnp.isnan(vals)
    s = jnp.where(live, vals, 0.0).sum(axis=1)
    mean = s / jnp.maximum(counts.astype(jnp.float32), 1.0)
    mx = jnp.where(live, vals, -jnp.inf).max(axis=1)
    mn = jnp.where(live, vals, jnp.inf).min(axis=1)
    return jnp.select([ops == OP_MEAN, ops == OP_MAX], [mean, mx], default=mn)


def _jit_agg_reduce():
    import jax

    return jax.jit(agg_reduce_core)


agg_reduce = _LazyJit(_jit_agg_reduce, kernel="agg_reduce")


def agg_reduce_batch(pending: list) -> np.ndarray:
    """Host driver for one fused window-reduction dispatch. ``pending``
    is a list of ``(op_code, values)`` with ``values`` a non-empty
    sequence of floats; returns float32 ``[len(pending)]`` aggregates.
    Shapes are power-of-two bucketed so churn in window count or width
    reuses a handful of jitted executables."""
    import jax.numpy as jnp

    w = len(pending)
    n = max(len(values) for _op, values in pending)
    wp = _bucket(max(1, w), minimum=2)
    np_ = _bucket(max(1, n), minimum=8)
    vals = np.full((wp, np_), np.nan, dtype=np.float32)
    ops = np.zeros(wp, dtype=np.int32)
    counts = np.ones(wp, dtype=np.int32)
    for i, (op, values) in enumerate(pending):
        vals[i, : len(values)] = np.asarray(values, dtype=np.float32)
        ops[i] = op
        counts[i] = len(values)
    out = agg_reduce(
        jnp.asarray(vals), jnp.asarray(ops), jnp.asarray(counts)
    )
    return np.asarray(out)[:w]


class DeviceRuleEvaluator:
    """The device-resident predicate rule table + batched evaluation.

    ``rebuild`` compiles a rule list into padded device arrays (rule
    order defines the dense index the host uses to decode pass bits);
    ``eval_async`` issues one batch and returns a zero-arg resolver that
    performs the D2H sync — the staging drain loop runs it inside the
    same executor call as the topic-match resolver, so both transfers
    land in one blocking leg."""

    def __init__(self) -> None:
        self.n_rules = 0  # live rules (pre-padding)
        self.n_slots = 1  # feature-vector width the table was built for
        self.n_cwords = 1  # contains-bitmask width (uint32 words)
        self._arrays: Optional[tuple] = None

    def rebuild(
        self,
        specs: list,
        slots: list,
        cbits: list,
        n_slots: int,
        n_cwords: int,
    ) -> None:
        """Compile the rule table to device arrays. ``specs`` are
        mqtt_tpu.predicates.PredicateSpec (non-aggregation ops only);
        ``slots``/``cbits`` the per-rule feature slot / contains bit."""
        import jax.numpy as jnp

        R = len(specs)
        self.n_rules = R
        self.n_slots = max(1, n_slots)
        self.n_cwords = max(1, n_cwords)
        if R == 0:
            self._arrays = None
            return
        # pad to a power-of-two multiple of 32 so rule-set churn reuses
        # the jitted executable; padding rows are OP_NONE (don't-care)
        pad = max(32, _bucket(R, minimum=32))
        op = np.zeros(pad, dtype=np.int32)
        slot = np.zeros(pad, dtype=np.int32)
        thresh = np.zeros(pad, dtype=np.float32)
        cbit = np.zeros(pad, dtype=np.int32)
        for i, spec in enumerate(specs):
            op[i] = spec.op
            slot[i] = max(0, slots[i])
            thresh[i] = np.float32(spec.value)
            cbit[i] = max(0, cbits[i])
        self._arrays = tuple(jnp.asarray(a) for a in (op, slot, thresh, cbit))

    def eval_async(self, feats: np.ndarray, cmask: np.ndarray) -> Callable:
        """Dispatch one evaluation batch; returns the resolver yielding
        ``uint32 [B, ceil(R_padded/32)]`` pass-bit rows (padding rows in
        both dimensions are sliced/ignored by the caller)."""
        import jax.numpy as jnp

        arrays = self._arrays
        if arrays is None:
            raise RuntimeError("evaluator has no compiled rules")
        B = feats.shape[0]
        pad_b = _bucket(max(1, B), minimum=16)
        if pad_b != B:
            feats = np.vstack(
                [feats, np.zeros((pad_b - B, feats.shape[1]), dtype=np.float32)]
            )
            cmask = np.vstack(
                [cmask, np.zeros((pad_b - B, cmask.shape[1]), dtype=np.uint32)]
            )
        rows_dev = rules_eval(
            *arrays, jnp.asarray(feats), jnp.asarray(cmask)
        )
        # overlap the D2H with the rest of the staged batch (the topic
        # matcher does the same for its packed result)
        rows_dev.copy_to_host_async()

        def resolve() -> np.ndarray:
            # brokerlint: ok=R15 the blessed resolve seam: ONE batched D2H after copy_to_host_async
            return np.asarray(rows_dev)[:B]

        return resolve
