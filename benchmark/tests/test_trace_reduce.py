"""The reduction from a trace to busy time and kernel times, on a small
recorded trace: the last 2.9 s of a ``telemetry-1m.saturate`` window on
one TPU v5 lite chip (PR 24's first chip run, seed 1002), in which five
4,096-topic batches ran ``jit__packed_core``."""

import os

import pytest

import trace_reduce

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "saturate_slice.xplane.pb")


def test_union_counts_overlap_once_and_names_gaps():
    busy, gaps = trace_reduce.union_seconds(
        [(0, 10, "a"), (5, 12, "b"), (20, 30, "c"), (30, 31, "d"), (50, 60, "e")]
    )
    assert busy == 12 + 11 + 10
    assert gaps == [(8, "c"), (19, "e")]
    assert trace_reduce.union_seconds([]) == (0, [])


def test_recorded_slice():
    from jax.profiler import ProfileData

    out = trace_reduce.reduce_profile(ProfileData.from_file(TRACE))
    assert out["device_planes"] == 1
    # the driver's idle share comes from this number: 1.23 ms busy
    assert out["busy_s"] == pytest.approx(0.001230087, rel=1e-6)
    (name, seconds), = [
        kv for kv in out["kernels"].items() if "_packed_core" in kv[0]
    ]
    assert seconds == pytest.approx(0.001231022, rel=1e-6)
    # operations on one core run one at a time: their sum is the union
    assert sum(s for _n, s in out["device_ops"]) == pytest.approx(
        out["busy_s"], rel=1e-3
    )
    assert out["device_ops"][0][0].startswith("%copy-done.1 = u32[1048576,16]")
    assert all(len(n) <= 96 for n, _s in out["device_ops"])
    assert len(out["idle_gaps"]) == 10
    assert out["idle_gaps"][0][1] > 0.4  # between two batches, half a second


def test_no_device_plane_reads_nothing(tmp_path):
    class Empty:
        planes = []

    out = trace_reduce.reduce_profile(Empty())
    assert out["device_planes"] == 0 and out["busy_s"] == 0 and not out["kernels"]
    with pytest.raises(FileNotFoundError):
        trace_reduce.reduce_dir(str(tmp_path))
