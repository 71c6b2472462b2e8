"""Test configuration.

Forces JAX onto the host CPU platform with 8 virtual devices BEFORE any test
imports jax, so multi-chip sharding tests (mqtt_tpu.parallel) compile and run
without TPU hardware. The chip is reached through chip_smoke.py, outside
pytest.
"""

import os

# Force CPU even when the environment preselects a TPU platform — tests
# must run on the virtual 8-device mesh, and must never take the chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Lock-order witness (ISSUE 10): armed for the WHOLE session so every
# named-lock acquisition any test provokes feeds the process-wide edge
# set. tests/test_zz_lockwitness.py (named to sort last under
# -p no:randomly) asserts the accumulated edges all appear in the
# statically extracted lock graph — an unexplained runtime edge is an
# extraction gap and fails tier-1. Cost: a disarmed-stats acquire grows
# by one held-stack append/pop and one dict probe per held lock.
from mqtt_tpu.utils.locked import DEFAULT_PLANE  # noqa: E402

DEFAULT_PLANE.arm_witness()

# Loop-affinity witness (ISSUE 19): same contract as the lock witness —
# recording (non-raising) for the whole session, so every instrumented
# affinity seam any test traverses feeds the process-wide (kind, seam)
# set. tests/test_zz_loopwitness.py asserts observed ⊆ the blessed
# LOOP_AFFINITY table (tools/brokerlint/loopgraph.py) and that zero
# guarded touches ran off their owning loop. Disarmed cost at every
# touch point: one plane-flag read + branch.
from mqtt_tpu.utils.loopwitness import DEFAULT_LOOP_PLANE  # noqa: E402

DEFAULT_LOOP_PLANE.arm_witness()
