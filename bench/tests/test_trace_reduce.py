"""The trace reduction on a small trace with known intervals."""

import time

import jax
import pytest

from bench import trace_reduce as tr

# one device plane: two programs, a loop running two ops, the kernel, and a
# host thread with the window span and one job span inside it (times in
# ns); ops are named by their HLO line, as on the TPU
XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Modules"
    timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 11 offset_ps: 6000000 duration_ps: 2000000 }
  }
  lines {
    id: 2
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 23 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 20 offset_ps: 0 duration_ps: 500000 }
    events { metadata_id: 21 offset_ps: 500000 duration_ps: 2500000 }
    events { metadata_id: 22 offset_ps: 6000000 duration_ps: 2000000 }
  }
  event_metadata { key: 10 value { id: 10 name: "jit__absorb_fn(4)" } }
  event_metadata { key: 11 value { id: 11 name: "jit__packed_fn(7)" } }
  event_metadata { key: 20 value { id: 20 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p.1), kind=kLoop" } }
  event_metadata { key: 21 value { id: 21 name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %fusion.1), kind=kLoop" } }
  event_metadata { key: 22 value { id: 22 name: "%_paged_clustered_decode_jit.3 = bf16[8]{0} custom-call(s32[4]{0} %p.2)" } }
  event_metadata { key: 23 value { id: 23 name: "%while.4 = (s32[]) while((s32[]) %t.1), body=%region_0.2" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 3
    name: "python"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 600000 duration_ps: 9400000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench_window" } }
  event_metadata { key: 2 value { id: 2 name: "bench_job" } }
}
"""


@pytest.fixture(scope="module")
def summary():
    pd = jax.profiler.ProfileData.from_text_proto(XSPACE)
    return tr.reduce(pd)


def test_busy_is_the_union_of_op_intervals(summary):
    # window [500, 10500) ns; ops cover [1000, 4000) and [7000, 9000)
    assert summary.window_s == pytest.approx(10000e-9)
    assert summary.busy_s == pytest.approx(5000e-9)
    assert summary.n_devices == 1


def test_programs_and_kernels(summary):
    assert summary.program_seconds("absorb") == (pytest.approx(3000e-9), 1)
    assert summary.program_seconds("_packed_fn") == (pytest.approx(2000e-9), 1)
    sec, n = summary.op_seconds("paged_clustered_decode", "_packed_fn")
    assert (sec, n) == (pytest.approx(2000e-9), 1)
    assert summary.op_seconds("paged_clustered_decode", "_absorb") == (0, 0)
    assert summary.op_seconds("no_such_kernel") == (0, 0)
    ops = {(o.name, o.module): o.seconds for o in summary.ops}
    assert ops[("fusion.1", "jit__absorb_fn")] == pytest.approx(500e-9)
    assert ops[("fusion.2", "jit__absorb_fn")] == pytest.approx(2500e-9)
    assert ops[("_paged_clustered_decode_jit.3", "jit__packed_fn")] == \
        pytest.approx(2000e-9)
    # the loop spans its two ops: counted in busy time, not as an op
    assert ("while.4", "jit__absorb_fn") not in ops


def test_idle_gaps_are_labelled_by_the_open_host_span(summary):
    gaps = dict(summary.idle_gaps)
    assert gaps["bench_job -> jit__absorb_fn"] == pytest.approx(500e-9)
    assert gaps["bench_job -> jit__packed_fn"] == pytest.approx(3000e-9)
    assert gaps["bench_job -> end of window"] == pytest.approx(1500e-9)
    assert sum(gaps.values()) == pytest.approx(summary.window_s
                                               - summary.busy_s)


def test_breakdown_shape(summary):
    b = tr.breakdown(summary)
    assert b["device_ops"][0] == ["jit__absorb_fn:fusion.2",
                                  pytest.approx(2500e-9)]
    assert len(b["idle_gaps"]) == 3 and len(b["device_ops"]) <= 10


def test_enclosing_ops():
    ops = [("loop", 0, 10), ("a", 0, 4), ("b", 4, 10), ("c", 12, 13),
           ("d", 12, 13)]
    assert tr.enclosing(ops) == {0, 3}
    assert tr.op_name("%while.96 = (f32[8]) while(%t), body=%r") == "while.96"


def test_interval_helpers():
    assert tr.union([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert tr.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]
    assert tr.gaps_of([(1, 4), (5, 6)], 0, 8) == [(0, 1), (4, 5), (6, 8)]


def test_recorded_host_spans(tmp_path):
    """A real trace: host spans come back with the durations slept."""
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        with jax.profiler.TraceAnnotation("bench_job"):
            time.sleep(0.05)
        time.sleep(0.02)
    jax.profiler.stop_trace()
    pd = jax.profiler.ProfileData.from_file(tr.find_xplane(str(tmp_path)))
    spans, win = tr._host_spans(pd, tr.WINDOW)
    job = [s for s in spans if s[0] == "bench_job"][0]
    assert (job[2] - job[1]) * 1e-9 == pytest.approx(0.05, abs=0.02)
    assert (win[2] - win[1]) * 1e-9 == pytest.approx(0.07, abs=0.03)
    assert win[1] <= job[1] and job[2] <= win[2]
