#!/usr/bin/env bash
# C analysis gate over the native sources (mqtt_tpu/native/*.c).
#
# Runs every analyzer the host provides and fails on any finding:
#   - gcc -fanalyzer -Wall -Wextra -Werror  (gcc >= 10; the PR-1 UAF class
#     in accelmod.c is exactly what the analyzer's use-after-free and
#     refcount-shaped path checks cover)
#   - cppcheck --enable=warning,portability  (when installed; CI installs it)
#
# Every finding must be FIXED or suppressed in the source with a comment
# explaining why it is safe — this script takes no suppression flags by
# design.
#
# --san adds the ASAN/UBSAN leg (ISSUE 13 satellite): both native
# modules are REBUILT with -fsanitize=address,undefined (a distinct
# artifact tag, so the plain build's cache is never poisoned) and the
# native-facing test suite runs under them — the lazy-view/freelist C
# code needs runtime lifetime verification, not just -fanalyzer.
# detect_leaks stays off (CPython interns allocate for process lifetime
# by design); UBSan runs -fno-sanitize-recover so any finding is fatal.
#
# Usage: tools/c_gate.sh [--san] [output-log]
set -u
cd "$(dirname "$0")/.."

SAN=0
if [ "${1:-}" = "--san" ]; then
    SAN=1
    shift
fi

LOG="${1:-/tmp/c_gate.log}"
: > "$LOG"
NATIVE=mqtt_tpu/native
# honor the Makefile's interpreter choice (PY=...) so the headers match
# the Python actually running the suite
PY="${PY:-python}"
PY_INC="$("$PY" -c 'import sysconfig; print(sysconfig.get_paths()["include"])')"
if [ -z "$PY_INC" ] || [ ! -e "$PY_INC/Python.h" ]; then
    echo "c_gate: cannot locate Python.h via $PY (got: '$PY_INC')" >&2
    exit 2
fi
rc=0
ran=0

say() { echo "$@" | tee -a "$LOG"; }

if gcc -fanalyzer --version >/dev/null 2>&1; then
    ran=1
    say "== gcc -fanalyzer =="
    # mqtt_native.c is freestanding C; accelmod.c needs the CPython headers
    if ! gcc -fanalyzer -Wall -Wextra -Werror -O1 -c -o /tmp/_cgate_native.o \
            "$NATIVE/mqtt_native.c" >>"$LOG" 2>&1; then
        say "FAIL: gcc -fanalyzer on mqtt_native.c"; rc=1
    fi
    if ! gcc -fanalyzer -Wall -Wextra -Werror -O1 -I"$PY_INC" \
            -c -o /tmp/_cgate_accel.o "$NATIVE/accelmod.c" >>"$LOG" 2>&1; then
        say "FAIL: gcc -fanalyzer on accelmod.c"; rc=1
    fi
else
    say "gcc -fanalyzer unavailable (need gcc >= 10); skipping"
fi

if command -v cppcheck >/dev/null 2>&1; then
    ran=1
    say "== cppcheck =="
    # warning+portability only: style/perf on a CPython extension is noise;
    # missingIncludeSystem so Python.h resolution is not a finding
    if ! cppcheck --enable=warning,portability --error-exitcode=1 \
            --suppress=missingIncludeSystem --inline-suppr \
            -I "$PY_INC" "$NATIVE/mqtt_native.c" "$NATIVE/accelmod.c" \
            >>"$LOG" 2>&1; then
        say "FAIL: cppcheck"; rc=1
    fi
else
    say "cppcheck unavailable; skipping"
fi

if [ "$SAN" = 1 ]; then
    LIBASAN="$(gcc -print-file-name=libasan.so 2>/dev/null || true)"
    if [ -n "$LIBASAN" ] && [ -e "$LIBASAN" ]; then
        ran=1
        say "== ASAN/UBSAN native test leg =="
        # the sanitizer flags change the artifact name (its digest
        # covers them: native/source_digest), so this leg builds its
        # own .so pair and the plain build stays untouched
        # MQTT_TPU_SAN=1 deselects the jax-backed e2e tests: jaxlib is
        # not ASAN-instrumented and its XLA compiler aborts under the
        # preloaded runtime — the leg verifies OUR C (views, pool,
        # flush, framing), not XLA
        if env \
            MQTT_TPU_NATIVE_CFLAGS="-fsanitize=address,undefined -fno-sanitize-recover=undefined -g" \
            ASAN_OPTIONS="detect_leaks=0:abort_on_error=1" \
            LD_PRELOAD="$LIBASAN" \
            MQTT_TPU_SAN=1 \
            "$PY" -m pytest tests/test_native.py tests/test_fanout.py \
                -q -m 'not slow' -p no:cacheprovider >>"$LOG" 2>&1; then
            say "sanitizer leg: clean"
        else
            say "FAIL: native tests under ASAN/UBSAN"; rc=1
        fi
        # sanitized artifacts are throwaway (tagged -x<digest>)
        rm -f mqtt_tpu/native/libmqtt_native-*-x????????????.so \
              mqtt_tpu/native/mqtt_accel-*-x????????????.so
    else
        say "libasan unavailable; sanitizer leg skipped"
    fi
fi

if [ "$ran" = 0 ]; then
    say "c_gate: NO analyzer available — gate vacuous on this host"
    # vacuous pass locally; CI always has gcc >= 10
fi
if [ "$rc" != 0 ]; then
    say "c_gate: findings above (full log: $LOG)"
else
    say "c_gate: clean"
fi
exit "$rc"
