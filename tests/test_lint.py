"""brokerlint's own test suite: every rule proves it FIRES on a minimal
positive fixture and stays quiet on the matching negative, then the live
``mqtt_tpu/`` tree is asserted clean — which makes the lint pass part of
tier-1 (`make verify`), not an advisory side channel.

The fixtures double as rule documentation: each positive snippet is the
smallest version of the real defect class the rule encodes (see
README.md "Static analysis" for the incident history).
"""

import json
import subprocess
import sys
import textwrap

import pytest

from tools.brokerlint import DEFAULT_BASELINE, RULE_DOC, lint_paths
from tools.brokerlint.core import load_baseline, run, save_baseline
from tools.brokerlint.rules import FILE_RULES, PROJECT_RULES


def lint_snippet(tmp_path, source, rules):
    """Lint one snippet with a selected subset of rules; returns the rule
    ids that fired (duplicates collapsed)."""
    mod = tmp_path / "mod.py"
    mod.write_text(textwrap.dedent(source))
    picked = {r: FILE_RULES[r] for r in rules}
    new, _ = run([str(mod)], str(tmp_path), picked, {})
    return [f.rule for f in new], new


# -- R1: blocking calls under a held lock -----------------------------------


def test_r1_fires_on_sleep_under_lock(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        import threading, time

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            def bad(self):
                with self._lock:
                    time.sleep(0.1)
        """,
        ["R1"],
    )
    assert fired == ["R1"]


def test_r1_fires_on_mkdtemp_and_thread_join_under_lock(tmp_path):
    # the FlightRecorder regression (PR 4): first-dump mkdtemp ran inside
    # the ring lock the event loop appends under
    fired, findings = lint_snippet(
        tmp_path,
        """
        import tempfile, threading

        class Rec:
            def __init__(self):
                self._lock = threading.Lock()

            def dump(self, writer_thread):
                with self._lock:
                    d = tempfile.mkdtemp(prefix="x_")
                    writer_thread.join(timeout=5)
                return d
        """,
        ["R1"],
    )
    assert fired == ["R1", "R1"]
    assert "mkdtemp" in findings[0].msg


def test_r1_fires_on_await_under_sync_lock(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            async def bad(self):
                with self._lock:
                    await self.flush()
        """,
        ["R1"],
    )
    assert fired == ["R1"]


def test_r1_quiet_on_callback_defined_directly_in_lock_scope(tmp_path):
    # a def sitting DIRECTLY in the with body only DEFINES the callback;
    # its blocking call runs later, outside this lock scope (regression:
    # _iter_scope used to prune nested defs only one level down)
    fired, _ = lint_snippet(
        tmp_path,
        """
        import threading, time

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            def register(self):
                with self._lock:
                    def cb():
                        time.sleep(0.1)
                    self.on_event = cb
        """,
        ["R1"],
    )
    assert fired == []


def test_r1_quiet_on_io_outside_lock_and_str_join(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        import threading, time

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            def good(self, parts):
                with self._lock:
                    snapshot = ",".join(parts)  # str.join is not a thread join
                time.sleep(0.1)
                return snapshot
        """,
        ["R1"],
    )
    assert fired == []


# -- R2: thread-reachable code touching the event loop ----------------------


def test_r2_fires_on_set_result_reachable_from_thread(tmp_path):
    fired, findings = lint_snippet(
        tmp_path,
        """
        import threading

        class B:
            def start(self):
                self._t = threading.Thread(target=self._run, daemon=True)
                self._t.start()

            def _run(self):
                self._finish()

            def _finish(self):
                self.fut.set_result(1)
        """,
        ["R2"],
    )
    assert fired == ["R2"]
    assert "call_soon_threadsafe" in findings[0].msg


def test_r2_quiet_when_routed_through_call_soon_threadsafe(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        import threading

        class B:
            def start(self):
                self._t = threading.Thread(target=self._run, daemon=True)
                self._t.start()

            def _run(self):
                # the cluster presence-wake pattern: hand the loop-side
                # mutation to the loop instead of performing it here
                self.loop.call_soon_threadsafe(self._wake)

            def _wake(self):
                self.fut.set_result(1)
        """,
        ["R2"],
    )
    assert fired == []


def test_r2_fires_on_partial_fix_direct_call_plus_scheduled(tmp_path):
    # the partial-fix shape: the threadsafe wake was added on one path
    # but a direct cross-thread call to the same function survives
    fired, _ = lint_snippet(
        tmp_path,
        """
        import threading

        class B:
            def start(self):
                self._t = threading.Thread(target=self._run, daemon=True)
                self._t.start()

            def _run(self, fast):
                if fast:
                    self._wake()  # BUG: direct cross-thread loop mutation
                else:
                    self.loop.call_soon_threadsafe(self._wake)

            def _wake(self):
                self.fut.set_result(1)
        """,
        ["R2"],
    )
    assert fired == ["R2"]


def test_r2_quiet_without_thread_entry_points(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        class Loop:
            def resolve(self, fut):
                fut.set_result(1)  # loop-side completion is the normal case
        """,
        ["R2"],
    )
    assert fired == []


# -- R3: wall-clock time.time() ---------------------------------------------


def test_r3_fires_on_time_time(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        import time

        def latency():
            t0 = time.time()
            return time.time() - t0
        """,
        ["R3"],
    )
    assert fired == ["R3", "R3"]


def test_r3_quiet_on_monotonic_and_pragmad_wall_time(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        import time

        def ok():
            t0 = time.monotonic()
            started = int(time.time())  # brokerlint: ok=R3 persisted wall-clock stamp
            return time.perf_counter() - t0, started
        """,
        ["R3"],
    )
    assert fired == []


def test_r3_fires_on_from_import_alias(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        from time import time

        def bad():
            return time()
        """,
        ["R3"],
    )
    assert fired == ["R3"]


# -- R4: silent exception swallows ------------------------------------------


def test_r4_fires_on_silent_swallow_and_bare_except(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        def bad(fn):
            try:
                fn()
            except Exception:
                pass
            try:
                fn()
            except:
                return None
        """,
        ["R4"],
    )
    assert sorted(fired) == ["R4", "R4"]


def test_r4_quiet_on_logged_counted_or_fallback_handlers(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        import logging

        log = logging.getLogger(__name__)

        def ok(fn, stats):
            buffered = 1
            try:
                fn()
            except Exception:
                log.exception("fn failed")
            try:
                fn()
            except Exception:
                stats.errors += 1
            try:
                fn()
            except Exception:
                buffered = 0  # fallback value is an observable outcome
            return buffered
        """,
        ["R4"],
    )
    assert fired == []


# -- R5: observer callbacks under a held lock -------------------------------


def test_r5_fires_on_direct_observer_call_under_lock(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.on_change = None

            def bad(self):
                with self._lock:
                    if self.on_change is not None:
                        self.on_change()
        """,
        ["R5"],
    )
    assert fired == ["R5"]


def test_r5_fires_inside_locked_suffix_functions(tmp_path):
    # the breaker regression (PR 4): _trip_locked invoked on_trip while
    # record_failure still held the breaker lock
    fired, _ = lint_snippet(
        tmp_path,
        """
        class Breaker:
            def _trip_locked(self):
                cb = self.on_trip
                if cb is not None:
                    cb()
        """,
        ["R5"],
    )
    assert fired == ["R5"]


def test_r5_propagates_into_functions_only_called_under_locks(tmp_path):
    # the trie-notify shape: _fanout itself takes no lock, but its every
    # call site holds one
    fired, _ = lint_snippet(
        tmp_path,
        """
        import threading

        class Trie:
            def __init__(self):
                self._lock = threading.RLock()
                self._observers = []

            def _fanout(self, m):
                for fn in self._observers:
                    fn(m)

            def mutate(self, m):
                with self._lock:
                    self._fanout(m)
        """,
        ["R5"],
    )
    assert fired == ["R5"]


def test_r5_quiet_when_callback_fires_after_release(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.on_change = None

            def good(self):
                with self._lock:
                    cb = self.on_change
                if cb is not None:
                    cb()
        """,
        ["R5"],
    )
    assert fired == []


# -- R6: metric catalog + naming scheme (project rule) ----------------------


def run_r6(tmp_path, module_src, catalog_names):
    (tmp_path / "m.py").write_text(textwrap.dedent(module_src))
    rows = "\n".join(f"| `{n}` | x | x | x |" for n in catalog_names)
    (tmp_path / "README.md").write_text(
        "Metrics catalog (`mqtt_tpu_` prefix elided):\n\n"
        "| name | type | labels | source |\n| --- | --- | --- | --- |\n"
        + rows + "\n"
    )
    new, _ = run([str(tmp_path / "m.py")], str(tmp_path), {}, PROJECT_RULES)
    return new


def test_r6_fires_on_catalog_drift_both_directions(tmp_path):
    findings = run_r6(
        tmp_path,
        """
        def wire(r):
            r.counter("mqtt_tpu_undocumented_total", "absent from catalog")
        """,
        ["documented_only_total"],
    )
    msgs = [f.msg for f in findings]
    assert any("missing from the README" in m for m in msgs)
    assert any("no code registers a matching metric" in m for m in msgs)


def test_r6_fires_on_naming_scheme_violations(tmp_path):
    findings = run_r6(
        tmp_path,
        """
        def wire(r):
            r.counter("mqtt_tpu_events", "counter without _total")
            r.histogram("mqtt_tpu_latency", "histogram without a unit")
            r.gauge("mqtt_tpu_depth_total", "gauge masquerading as counter")
        """,
        ["events", "latency", "depth_total"],
    )
    assert len([f for f in findings if f.rule == "R6"]) == 3


def test_r6_quiet_on_catalog_globs_and_loop_registration(tmp_path):
    findings = run_r6(
        tmp_path,
        """
        def wire(r):
            r.counter("mqtt_tpu_messages_in_total", "x")
            for name, attr in (
                ("mqtt_tpu_messages_out_total", "out"),
            ):
                r.counter(name, "mirror")
            r.histogram("mqtt_tpu_wait_seconds", "x")
        """,
        ["messages_*_total", "wait_seconds"],
    )
    assert findings == []


def test_r6_reads_rows_of_a_metric_table(tmp_path):
    """A NamedTuple with ``metric`` and ``kind`` fields declares a metric
    a row: its names are held to the scheme and the catalog as a
    factory call's are."""
    findings = run_r6(
        tmp_path,
        """
        from typing import NamedTuple

        class Row(NamedTuple):
            attr: str
            metric: str
            kind: str = "counter"

        ROWS = (
            Row("a", "mqtt_tpu_a_total"),
            Row("b", "mqtt_tpu_b"),
            Row("c", "mqtt_tpu_c_total", kind="gauge"),
            Row("d", "mqtt_tpu_undocumented_total"),
        )
        """,
        ["a_total", "b", "c_total", "stale_total"],
    )
    msgs = sorted(f.msg for f in findings if f.rule == "R6")
    assert len(msgs) == 4, msgs
    assert any("'mqtt_tpu_b' must end in `_total`" in m for m in msgs)
    assert any("gauge 'mqtt_tpu_c_total'" in m for m in msgs)
    assert any("'mqtt_tpu_undocumented_total' missing" in m for m in msgs)
    assert any("'mqtt_tpu_stale_total'" in m for m in msgs)


# -- R7: thread daemon/tracking discipline ----------------------------------


def test_r7_fires_on_missing_daemon_and_unbound_thread(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        import threading

        def bad(fn):
            threading.Thread(target=fn).start()
        """,
        ["R7"],
    )
    assert sorted(fired) == ["R7", "R7"]  # no daemon=, no binding


def test_r7_quiet_on_bound_explicit_daemon_thread(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        import threading

        class P:
            def start(self, fn):
                self._t = threading.Thread(target=fn, daemon=True)
                self._t.start()
                self._writers.append(
                    threading.Thread(target=fn, daemon=True)
                )
        """,
        ["R7"],
    )
    assert fired == []


# -- R8: mutable defaults / module singletons -------------------------------


def test_r8_fires_on_mutable_default_and_module_singleton(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        _CACHE = []

        def bad(items=[]):
            _CACHE.append(items)
        """,
        ["R8"],
    )
    assert sorted(fired) == ["R8", "R8"]


def test_r8_quiet_on_none_default_and_constant_tables(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        _TABLE = {"a": 1}  # populated constant lookup table
        _FROZEN = (1, 2)

        def ok(items=None):
            return _TABLE, _FROZEN, items
        """,
        ["R8"],
    )
    assert fired == []


# -- R9: whole-program lock-order graph (project rule) -----------------------


def run_r9(tmp_path, module_src):
    (tmp_path / "m.py").write_text(textwrap.dedent(module_src))
    new, _ = run(
        [str(tmp_path / "m.py")], str(tmp_path), {},
        {"R9": PROJECT_RULES["R9"]},
    )
    return new


CYCLE_SRC = """
    import threading

    class A:
        def __init__(self):
            self._alpha_lock = threading.Lock()
            self._beta_lock = threading.Lock()

        def one(self):
            with self._alpha_lock:
                with self._beta_lock:
                    pass

        def two(self):
            with self._beta_lock:
                with self._alpha_lock:{pragma}
                    pass
"""


def test_r9_fires_on_anonymous_lock_cycle(tmp_path):
    findings = run_r9(tmp_path, CYCLE_SRC.format(pragma=""))
    assert [f.rule for f in findings] and all(f.rule == "R9" for f in findings)
    assert any("cycle" in f.msg for f in findings)


def test_r9_quiet_on_consistent_order(tmp_path):
    findings = run_r9(
        tmp_path,
        """
        import threading

        class A:
            def __init__(self):
                self._alpha_lock = threading.Lock()
                self._beta_lock = threading.Lock()

            def one(self):
                with self._alpha_lock:
                    with self._beta_lock:
                        pass

            def two(self):
                with self._alpha_lock:
                    with self._beta_lock:
                        pass
        """,
    )
    assert findings == []


def test_r9_fires_on_reversed_blessed_edge(tmp_path):
    # metrics_registry (last in LOCK_ORDER) must never wrap a governor
    # acquisition — the scrape-path-calls-into-the-control-plane shape
    findings = run_r9(
        tmp_path,
        """
        from mqtt_tpu.utils.locked import InstrumentedLock

        class Registry:
            def __init__(self, gov: "Gov"):
                self._lock = InstrumentedLock("metrics_registry")
                self.gov = gov

            def bad(self):
                with self._lock:
                    with self.gov._lock:
                        pass

        class Gov:
            def __init__(self):
                self._lock = InstrumentedLock("overload_governor")
        """,
    )
    assert any("reversed" in f.msg for f in findings)


def test_r9_propagates_one_call_level(tmp_path):
    # the cycle only exists through the call: locked_path() holds the
    # gate lock while _touch() takes the inner one
    findings = run_r9(
        tmp_path,
        """
        import threading

        class B:
            def __init__(self):
                self._gate_lock = threading.Lock()
                self._inner_lock = threading.Lock()

            def locked_path(self):
                with self._gate_lock:
                    self._touch()

            def _touch(self):
                with self._inner_lock:
                    pass

            def reverse(self):
                with self._inner_lock:
                    with self._gate_lock:
                        pass
        """,
    )
    assert any(f.rule == "R9" and "cycle" in f.msg for f in findings)


def test_r9_param_named_rlock_reentry_is_not_a_cycle(tmp_path):
    # a parameter-named RLock resolves to EVERY name it can carry; one
    # scope still holds exactly one of them, so legal same-instance
    # re-entry through a helper must not fabricate cross-name edges
    # between the alternatives (review regression — the TopicsIndex
    # lock_name shape)
    findings = run_r9(
        tmp_path,
        """
        import threading
        from mqtt_tpu.utils.locked import InstrumentedLock

        class Trie:
            def __init__(self, lock_name: str = "topics_trie") -> None:
                self._lock = InstrumentedLock(lock_name, rlock=True)

            def outer(self):
                with self._lock:
                    self.inner()

            def inner(self):
                with self._lock:
                    pass

        def make_remote():
            return Trie(lock_name="cluster_remote_trie")
        """,
    )
    assert findings == []


def test_r9_lock_graph_export_survives_syntax_error(tmp_path):
    # a committed syntax error must surface as the PARSE finding, not
    # crash the --lock-graph export mid-JSON (review regression)
    (tmp_path / "ok.py").write_text("import threading\n")
    (tmp_path / "broken.py").write_text("def oops(:\n")
    out = tmp_path / "artifacts"
    r = subprocess.run(
        [sys.executable, "-m", "tools.brokerlint", str(tmp_path),
         "--root", str(tmp_path), "--baseline", str(tmp_path / "bl.json"),
         "--json", "--lock-graph", str(out)],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 1  # the PARSE finding fails the run...
    payload = json.loads(r.stdout)  # ...but the JSON is still complete
    assert any(f["rule"] == "PARSE" for f in payload["findings"])
    assert (out / "lockgraph.json").exists()


def test_r9_callback_defined_inside_with_block_is_not_held(tmp_path):
    # a def nested INSIDE the with block runs later, not under the
    # lock: no phantom edge, no false cycle (review regression — the
    # first fix only covered the call-propagation path)
    findings = run_r9(
        tmp_path,
        """
        import threading

        class G:
            def __init__(self):
                self._outer_lock = threading.Lock()
                self._inner_lock = threading.Lock()

            def register(self):
                with self._outer_lock:
                    def cb():
                        with self._inner_lock:
                            pass
                    self.on_event = cb

            def legit(self):
                with self._inner_lock:
                    with self._outer_lock:
                        pass
        """,
    )
    assert findings == []


def test_r9_module_level_with_statements_are_scanned(tmp_path):
    # module-scope lock nesting executes at import time and is part of
    # the whole-program order; a reversed nesting elsewhere is a real
    # AB-BA cycle (review regression — module body used to be skipped)
    findings = run_r9(
        tmp_path,
        """
        import threading

        _g_lock = threading.Lock()
        _h_lock = threading.Lock()

        with _g_lock:
            with _h_lock:
                pass

        def reverse():
            with _h_lock:
                with _g_lock:
                    pass
        """,
    )
    assert any(f.rule == "R9" and "cycle" in f.msg for f in findings)


def test_r9_scans_duplicate_class_names_in_every_file(tmp_path):
    # two modules defining the same class name: BOTH bodies must be
    # scanned — a cycle in the second must not hide behind the first
    # (review regression: first-definition-wins used to skip it)
    (tmp_path / "first.py").write_text(textwrap.dedent(
        """
        class Dup:
            def harmless(self):
                return 1
        """
    ))
    (tmp_path / "second.py").write_text(textwrap.dedent(
        """
        import threading

        class Dup:
            def __init__(self):
                self._p_lock = threading.Lock()
                self._q_lock = threading.Lock()

            def one(self):
                with self._p_lock:
                    with self._q_lock:
                        pass

            def two(self):
                with self._q_lock:
                    with self._p_lock:
                        pass
        """
    ))
    new, _ = run(
        [str(tmp_path / "first.py"), str(tmp_path / "second.py")],
        str(tmp_path), {}, {"R9": PROJECT_RULES["R9"]},
    )
    assert any(f.rule == "R9" and "cycle" in f.msg for f in new)


def test_r9_unblessed_lock_baseline_keys_are_per_lock(tmp_path):
    # baselining ONE unblessed lock must not suppress a DIFFERENT one in
    # the same file (review regression: empty context collapsed them)
    mod = tmp_path / "m.py"
    mod.write_text(textwrap.dedent(
        """
        from mqtt_tpu.utils.locked import InstrumentedLock

        class C:
            def __init__(self):
                self._lock = InstrumentedLock("first_unblessed")
        """
    ))
    rules = {"R9": PROJECT_RULES["R9"]}
    new, _ = run([str(mod)], str(tmp_path), {}, rules)
    bl = tmp_path / "bl.json"
    save_baseline(str(bl), new)
    mod.write_text(textwrap.dedent(
        """
        from mqtt_tpu.utils.locked import InstrumentedLock

        class C:
            def __init__(self):
                self._lock = InstrumentedLock("first_unblessed")
                self._other_lock = InstrumentedLock("second_unblessed")
        """
    ))
    new2, old2 = run(
        [str(mod)], str(tmp_path), {}, rules, baseline=load_baseline(str(bl))
    )
    assert any("second_unblessed" in f.msg for f in new2), new2
    assert all("first_unblessed" not in f.msg for f in new2)


def test_r9_fires_on_unblessed_named_lock(tmp_path):
    findings = run_r9(
        tmp_path,
        """
        from mqtt_tpu.utils.locked import InstrumentedLock

        class C:
            def __init__(self):
                self._lock = InstrumentedLock("nobody_blessed_me")
        """,
    )
    assert any("LOCK_ORDER" in f.msg for f in findings)


def test_r9_multi_item_with_orders_left_to_right(tmp_path):
    # `with a, b:` acquires left-to-right; reversed nesting elsewhere is
    # a genuine AB-BA cycle and must fire (review regression)
    findings = run_r9(
        tmp_path,
        """
        import threading

        class E:
            def __init__(self):
                self._a_lock = threading.Lock()
                self._b_lock = threading.Lock()

            def one(self):
                with self._a_lock, self._b_lock:
                    pass

            def two(self):
                with self._b_lock:
                    with self._a_lock:
                        pass
        """,
    )
    assert any(f.rule == "R9" and "cycle" in f.msg for f in findings)


def test_r9_callback_definition_is_not_an_acquisition(tmp_path):
    # a method that only DEFINES a callback taking a lock (the server's
    # _trip_dump registration shape) must not be credited with that
    # acquisition — the phantom edge would fabricate a cycle here
    # (review regression)
    findings = run_r9(
        tmp_path,
        """
        import threading

        class F:
            def __init__(self):
                self._outer_lock = threading.Lock()
                self._inner_lock = threading.Lock()

            def register(self):
                def cb():
                    with self._inner_lock:
                        pass
                self.on_event = cb

            def outer_path(self):
                with self._outer_lock:
                    self.register()

            def legit(self):
                with self._inner_lock:
                    with self._outer_lock:
                        pass
        """,
    )
    assert findings == []


def test_r9_locked_suffix_scope_counts_as_held(tmp_path):
    findings = run_r9(
        tmp_path,
        """
        import threading

        class D:
            def __init__(self):
                self._lock = threading.Lock()
                self._aux_lock = threading.Lock()

            def _step_locked(self):
                with self._aux_lock:
                    pass

            def other(self):
                with self._aux_lock:
                    with self._lock:
                        pass
        """,
    )
    assert any(f.rule == "R9" and "cycle" in f.msg for f in findings)


def test_r9_reasoned_pragma_suppresses_and_reasonless_does_not(tmp_path):
    ok = run_r9(
        tmp_path,
        CYCLE_SRC.format(
            pragma="  # brokerlint: ok=R9 proven single-threaded in tests"
        ),
    )
    # the pragma'd site is suppressed; the cycle seen from the OTHER
    # direction still reports (the cycle genuinely still exists)
    assert all(f.line != 16 for f in ok)
    (tmp_path / "m.py").write_text(
        textwrap.dedent(CYCLE_SRC.format(pragma="  # brokerlint: ok=R9"))
    )
    new, _ = run(
        [str(tmp_path / "m.py")], str(tmp_path), {},
        {"R9": PROJECT_RULES["R9"]},
    )
    assert any(f.rule == "PRAGMA" for f in new)


def test_r9_baseline_and_json_round_trip(tmp_path):
    """R9 rides the identical --json/--write-baseline machinery as
    R1-R8: findings appear in JSON, grandfather into a baseline, and
    vanish from the next run."""
    mod = tmp_path / "m.py"
    mod.write_text(textwrap.dedent(CYCLE_SRC.format(pragma="")))
    bl = tmp_path / "bl.json"
    base = [
        sys.executable, "-m", "tools.brokerlint", str(mod),
        "--root", str(tmp_path), "--baseline", str(bl),
    ]
    r = subprocess.run(base + ["--json"], capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 1
    payload = json.loads(r.stdout)
    assert any(f["rule"] == "R9" for f in payload["findings"])
    r = subprocess.run(base + ["--write-baseline"], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    r = subprocess.run(base + ["--json"], capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert payload["findings"] == [] and payload["baselined"] > 0


def test_lock_graph_export_artifacts(tmp_path):
    """--lock-graph writes Graphviz + JSON artifacts for the CI upload;
    the JSON carries the blessed order, every named lock, and the edge
    sites."""
    out = tmp_path / "artifacts"
    r = subprocess.run(
        [sys.executable, "-m", "tools.brokerlint", "mqtt_tpu",
         "--lock-graph", str(out)],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    dot = (out / "lockgraph.dot").read_text()
    assert dot.startswith("digraph lockorder")
    data = json.loads((out / "lockgraph.json").read_text())
    from tools.brokerlint.lockgraph import LOCK_ORDER

    assert data["order"] == list(LOCK_ORDER)
    names = {n["name"] for n in data["nodes"]}
    assert set(LOCK_ORDER) <= names
    assert data["cycles"] == []
    edges = {(e["src"], e["dst"]) for e in data["edges"]}
    assert ("topics_trie", "retained") in edges
    assert all(e["sites"] for e in data["edges"])


def test_loop_graph_export_artifacts(tmp_path):
    """--loop-graph writes Graphviz + JSON artifacts beside the lock
    graph; the JSON carries the blessed affinity table, the discovered
    owner-attach sites, and the live (evidence-backed) seams."""
    out = tmp_path / "artifacts"
    r = subprocess.run(
        [sys.executable, "-m", "tools.brokerlint", "mqtt_tpu",
         "--loop-graph", str(out)],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    dot = (out / "loopgraph.dot").read_text()
    assert dot.startswith("digraph loopaffinity")
    data = json.loads((out / "loopgraph.json").read_text())
    from tools.brokerlint.loopgraph import LOOP_AFFINITY

    assert data["affinity"] == [list(p) for p in LOOP_AFFINITY]
    # the live tree must supply owner-attach evidence for the core kinds
    assert {"outbound_queue", "match_stage", "shard_task"} <= set(
        data["owners"]
    )
    for sites in data["owners"].values():
        assert all(s["path"] and s["line"] > 0 for s in sites)
    # every live seam is a blessed pair (the zz gate's static half)
    seams = {tuple(p) for p in data["seams"]}
    assert seams <= set(LOOP_AFFINITY)
    assert ("outbound_queue", "put_local") in seams


# -- R10: foreign-thread mutation of loop-affine objects ---------------------


def test_r10_fires_on_thread_reachable_event_set(tmp_path):
    # the generalized R2 shape: an asyncio.Event owned by a shard loop
    # set() directly from a worker thread (the pre-fix delta-poller bug
    # class) instead of via call_soon_threadsafe
    fired, findings = lint_snippet(
        tmp_path,
        """
        import threading

        class Poller:
            def start(self):
                self._t = threading.Thread(target=self._run, daemon=True)
                self._t.start()

            def _run(self):
                self._stopped.set()
        """,
        ["R10"],
    )
    assert fired == ["R10"]
    assert "call_soon_threadsafe" in findings[0].msg


def test_r10_quiet_on_threading_event(tmp_path):
    # the delta.py/resilience.py real shape: the event IS a
    # threading.Event, thread-safe by construction — foreign set() is
    # the intended use
    fired, _ = lint_snippet(
        tmp_path,
        """
        import threading

        class Poller:
            def __init__(self):
                self._stopped = threading.Event()

            def start(self):
                self._t = threading.Thread(target=self._run, daemon=True)
                self._t.start()

            def _run(self):
                self._stopped.set()
        """,
        ["R10"],
    )
    assert fired == []


def test_r10_quiet_without_thread_entry(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        class Loop:
            def stop(self):
                self._stopped.set()
        """,
        ["R10"],
    )
    assert fired == []


def test_r10_fires_on_writer_close_and_task_cancel_from_thread(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        import threading

        class Reaper:
            def start(self):
                threading.Thread(target=self._reap).start()

            def _reap(self):
                self._writer.close()
                self._tick_task.cancel()
        """,
        ["R10"],
    )
    assert fired == ["R10", "R10"]


# -- R11: blocking calls in async bodies / loop callbacks --------------------


def test_r11_fires_on_sleep_in_async_def(tmp_path):
    fired, findings = lint_snippet(
        tmp_path,
        """
        import time

        async def handler():
            time.sleep(0.1)
        """,
        ["R11"],
    )
    assert fired == ["R11"]
    assert "stalls" in findings[0].msg


def test_r11_fires_on_untimed_acquire_in_loop_callback(tmp_path):
    # the sync body runs ON the loop because it is scheduled with
    # call_soon — async-context rules apply to it too
    fired, _ = lint_snippet(
        tmp_path,
        """
        import threading

        class Stage:
            def __init__(self):
                self._lock = threading.Lock()

            def submit(self, loop):
                loop.call_soon_threadsafe(self._drain)

            def _drain(self):
                self._lock.acquire()
        """,
        ["R11"],
    )
    assert fired == ["R11"]


def test_r11_fires_on_storage_append_in_async_def(tmp_path):
    # storage hooks hit the durability path (fsync under
    # durability_fsync=always): never inline on a loop
    fired, _ = lint_snippet(
        tmp_path,
        """
        async def persist(self, rec):
            self._store.append(rec)
        """,
        ["R11"],
    )
    assert fired == ["R11"]


def test_r11_quiet_on_bounded_acquire_and_executor(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        import threading

        class Stage:
            def __init__(self):
                self._lock = threading.Lock()

            def submit(self, loop):
                loop.call_soon_threadsafe(self._drain)

            def _drain(self):
                if self._lock.acquire(timeout=0.5):
                    self._lock.release()

        async def persist(loop, store, rec):
            await loop.run_in_executor(None, store.append, rec)
        """,
        ["R11"],
    )
    assert fired == []


# -- R12: future resolution loop discipline ----------------------------------


def test_r12_fires_on_unguarded_set_exception(tmp_path):
    # the staging._fallback_all defect this rule found live (PR 19):
    # rejecting parked futures inline on the stage's thread runs their
    # done-callbacks cross-loop
    fired, findings = lint_snippet(
        tmp_path,
        """
        class Stage:
            def _fallback_all(self, exc):
                for fut in self._pending:
                    fut.set_exception(exc)
        """,
        ["R12"],
    )
    assert fired == ["R12"]
    assert "marshal" in findings[0].msg


def test_r12_quiet_under_loop_identity_guard(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        import asyncio

        class Stage:
            def _resolve(self, fut, val):
                if fut.get_loop() is asyncio.get_running_loop():
                    fut.set_result(val)
                else:
                    fut.get_loop().call_soon_threadsafe(fut.set_result, val)
        """,
        ["R12"],
    )
    assert fired == []


def test_r12_quiet_when_resolver_is_itself_marshaled(tmp_path):
    # the resolver body IS the marshal seam: it only ever runs on the
    # target loop because every reference to it rides call_soon*
    fired, _ = lint_snippet(
        tmp_path,
        """
        class Stage:
            def _resolve(self, loop):
                loop.call_soon_threadsafe(self._finish)

            def _finish(self):
                self.fut.set_result(1)
        """,
        ["R12"],
    )
    assert fired == []


def test_r12_quiet_on_locally_created_future(tmp_path):
    # same-scope create_future + resolve never crosses a loop
    fired, _ = lint_snippet(
        tmp_path,
        """
        class Stage:
            def park(self, loop):
                fut = loop.create_future()
                fut.set_result(1)
                return fut
        """,
        ["R12"],
    )
    assert fired == []


# -- R13: spawned tasks must be tracked --------------------------------------


def test_r13_fires_on_fire_and_forget_create_task(tmp_path):
    # the server.inject_packet defect this rule found live (PR 19): the
    # bridged fan-out task held no reference and could be GC'd mid-flight
    fired, findings = lint_snippet(
        tmp_path,
        """
        async def inject(loop, coro):
            loop.create_task(coro)
        """,
        ["R13"],
    )
    assert fired == ["R13"]
    assert "weak reference" in findings[0].msg


def test_r13_quiet_on_tracked_spawns(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        import asyncio

        async def spawn(shard, loop, coros):
            task = loop.create_task(coros[0])
            shard.track(loop.create_task(coros[1]))
            acks = [asyncio.ensure_future(c) for c in coros]
            return task, acks
        """,
        ["R13"],
    )
    assert fired == []


# -- R14: await/blocking under a lock, one call level deep -------------------


def test_r14_fires_on_blocking_call_in_lock_only_function(tmp_path):
    fired, findings = lint_snippet(
        tmp_path,
        """
        import threading, time

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                with self._lock:
                    self._flush()

            def _flush(self):
                time.sleep(0.1)
        """,
        ["R14"],
    )
    assert fired == ["R14"]
    assert "one call level deep" in findings[0].msg


def test_r14_fires_on_await_in_lock_only_function(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            async def outer(self):
                with self._lock:
                    await self._flush()

            async def _flush(self):
                await self.writer.drain()
        """,
        ["R14"],
    )
    assert fired == ["R14"]


def test_r14_quiet_when_also_called_outside_locks(tmp_path):
    # a lock-free call site means the function is NOT a lock-held scope
    fired, _ = lint_snippet(
        tmp_path,
        """
        import threading, time

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                with self._lock:
                    self._flush()

            def direct(self):
                self._flush()

            def _flush(self):
                time.sleep(0.1)
        """,
        ["R14"],
    )
    assert fired == []


# -- R15: implicit D2H syncs on the device hot path --------------------------


def lint_ops_snippet(tmp_path, source, rel="mqtt_tpu/ops/x.py"):
    """R15 gates on the file's repo-relative path, so its fixtures must
    live under the scoped subtree of the lint root."""
    mod = tmp_path / rel
    mod.parent.mkdir(parents=True, exist_ok=True)
    mod.write_text(textwrap.dedent(source))
    new, _ = run([str(mod)], str(tmp_path), {"R15": FILE_RULES["R15"]}, {})
    return [f.rule for f in new], new


def test_r15_fires_on_implicit_d2h_syncs(tmp_path):
    fired, findings = lint_ops_snippet(
        tmp_path,
        """
        import jax
        import numpy as np

        def resolve(out_dev):
            n = out_dev.sum().item()
            host = np.asarray(out_dev)
            got = jax.device_get(out_dev)
            return n, host, got, float(out_dev[0])
        """,
    )
    assert fired == ["R15"] * 4
    assert any("blocking" in f.msg for f in findings)


def test_r15_quiet_on_host_arrays_and_outside_scope(tmp_path):
    # host-named arrays don't trip the device heuristic...
    fired, _ = lint_ops_snippet(
        tmp_path,
        """
        import numpy as np

        def pack(ids):
            return np.asarray(ids, dtype=np.int32)
        """,
    )
    assert fired == []
    # ...and the same D2H shapes OUTSIDE ops//sharded.py are not R15's
    # business (hooks and tests read scalars freely)
    fired, _ = lint_ops_snippet(
        tmp_path,
        """
        def read(out_dev):
            return out_dev.item()
        """,
        rel="mqtt_tpu/hooks/h.py",
    )
    assert fired == []


def test_r15_pragma_round_trip(tmp_path):
    # a reasoned pragma blesses the ONE batched resolve seam; the same
    # pragma without a reason is itself a finding (the R3 contract,
    # checked for a loop-rule id)
    src = """
        import numpy as np

        def resolve(out_dev):
            return np.asarray(out_dev)  # brokerlint: ok=R15 {reason}
    """
    fired, _ = lint_ops_snippet(
        tmp_path, src.format(reason="the one batched D2H at the resolve seam")
    )
    assert fired == []
    fired, _ = lint_ops_snippet(tmp_path, src.format(reason=""))
    assert sorted(fired) == ["PRAGMA", "R15"]


# -- lockgraph callback propagation (the PR 10 residual, closed) -------------


def test_r9_propagates_through_registered_callback(tmp_path):
    # a callback registered as an observer attribute and FIRED under a
    # lock contributes its own acquisitions to the firing site's edge
    # set: retained -> topics_trie here reverses the blessed order
    findings = run_r9(
        tmp_path,
        """
        from mqtt_tpu.utils.locked import InstrumentedLock

        class Store:
            def __init__(self):
                self._fire_lock = InstrumentedLock("retained")
                self._note_lock = InstrumentedLock("topics_trie")
                self.on_change = self._note

            def mutate(self):
                with self._fire_lock:
                    self.on_change()

            def _note(self):
                with self._note_lock:
                    pass
        """,
    )
    assert any(f.rule == "R9" and "reversed" in f.msg for f in findings)


def test_r9_callback_propagation_quiet_on_blessed_order(tmp_path):
    findings = run_r9(
        tmp_path,
        """
        from mqtt_tpu.utils.locked import InstrumentedLock

        class Store:
            def __init__(self):
                self._fire_lock = InstrumentedLock("topics_trie")
                self._note_lock = InstrumentedLock("retained")
                self.on_change = self._note

            def mutate(self):
                with self._fire_lock:
                    self.on_change()

            def _note(self):
                with self._note_lock:
                    pass
        """,
    )
    assert findings == []


def test_r9_propagates_through_callback_container(tmp_path):
    # the container shape: registered via .append, fired by subscript
    # over the observer-named container
    findings = run_r9(
        tmp_path,
        """
        from mqtt_tpu.utils.locked import InstrumentedLock

        class Bus:
            def __init__(self):
                self._fire_lock = InstrumentedLock("retained")
                self._note_lock = InstrumentedLock("topics_trie")
                self._observers = []
                self._observers.append(self._note)

            def mutate(self):
                with self._fire_lock:
                    self._observers[0]()

            def _note(self):
                with self._note_lock:
                    pass
        """,
    )
    assert any(f.rule == "R9" and "reversed" in f.msg for f in findings)


# -- pragmas and baseline ---------------------------------------------------


def test_pragma_without_reason_is_itself_a_finding(tmp_path):
    fired, _ = lint_snippet(
        tmp_path,
        """
        import time

        def f():
            return time.time()  # brokerlint: ok=R3
        """,
        ["R3"],
    )
    assert sorted(fired) == ["PRAGMA", "R3"]  # unreasoned pragma suppresses nothing


def test_baseline_suppresses_known_findings(tmp_path):
    mod = tmp_path / "mod.py"
    mod.write_text("import time\n\ndef f():\n    return time.time()\n")
    new, old = run([str(mod)], str(tmp_path), {"R3": FILE_RULES["R3"]}, {})
    assert [f.rule for f in new] == ["R3"] and old == []
    bl = tmp_path / "baseline.json"
    save_baseline(str(bl), new)
    new2, old2 = run(
        [str(mod)], str(tmp_path), {"R3": FILE_RULES["R3"]}, {},
        baseline=load_baseline(str(bl)),
    )
    assert new2 == [] and [f.rule for f in old2] == ["R3"]


# -- the enforcing gates ----------------------------------------------------


def test_live_tree_is_clean():
    """The tentpole acceptance: zero un-baselined findings over mqtt_tpu/
    with the checked-in (empty) baseline."""
    new, baselined = lint_paths(["mqtt_tpu"])
    assert new == [], "\n".join(f.render() for f in new)
    # the checked-in baseline must stay empty: violations get fixed or
    # pragma'd at the site, not grandfathered
    assert load_baseline(DEFAULT_BASELINE) == set()
    assert baselined == []


def test_cli_exits_zero_on_live_tree():
    r = subprocess.run(
        [sys.executable, "-m", "tools.brokerlint", "mqtt_tpu", "--json"],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert payload["findings"] == []


def test_rule_catalog_is_complete():
    for rid in list(FILE_RULES) + list(PROJECT_RULES):
        assert rid in RULE_DOC


@pytest.mark.skipif(
    subprocess.run(
        [sys.executable, "-c", "import mypy"], capture_output=True
    ).returncode != 0,
    reason="mypy not installed (CI installs it; the gate is advisory locally)",
)
def test_mypy_gate_on_typed_core_modules():
    """`mypy` (config: mypy.ini) must pass over the typed core modules
    — the scope grew to faults.py, tenancy.py, inflight.py, config.py
    and utils/loopwitness.py in ISSUE 19."""
    r = subprocess.run(
        [sys.executable, "-m", "mypy", "--config-file", "mypy.ini"],
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stdout + r.stderr
