"""The readers of the engine's counters, on a made-up window:
their values by hand, and nothing (no error) where the program does not
count what they read."""

import types

import pytest

from bench import run as harness

NEW = ("itl_compact_gap_frac", "ttft_queue_share", "setup_build_s")


def job(stats, served):
    """A job record: ``served`` is [(tokens served, TTFT ms)]."""
    return {"stats": stats,
            "requests": [(u, 16, len(toks), toks, ttft, False)
                         for u, (toks, ttft) in enumerate(served)]}


STATS = [
    {"kv_compact_gaps": 5.0, "queue_wait_s": 1.5,
     "program_build_s": 2.5, "program_build_s_total": 10.0},
    {"kv_compact_gaps": 3.0, "queue_wait_s": 1.5,
     "program_build_s": 0.0, "program_build_s_total": 10.0},
]
SERVED = [[([1, 2, 3, 4], 1000.0), ([5, 6], 2000.0)],
          [([7, 8, 9], 1500.0), ([], None)]]


def ctx(jobs):
    return types.SimpleNamespace(jobs=jobs, trace=None)


def test_values_by_hand():
    jobs = [job(s, v) for s, v in zip(STATS, SERVED)]
    got = {name: harness.load_reader(name)(ctx(jobs))
           for name in NEW}
    # tokens less one per served request: 3 + 1 + 2
    assert got["itl_compact_gap_frac"] == pytest.approx(100 * 8 / 6)
    # TTFT of the served requests: 1 + 2 + 1.5 s
    assert got["ttft_queue_share"] == pytest.approx(100 * 3.0 / 4.5)
    assert got["setup_build_s"] == pytest.approx(7.5)


def test_silent_where_the_program_counts_nothing():
    """The parent program publishes none of these counters: every reader
    returns nothing, and none raises."""
    bare = [job({"gen_tokens": 6.0}, v) for v in SERVED]
    for name in NEW:
        assert harness.load_reader(name)(ctx(bare)) is None, name
        assert harness.load_reader(name)(ctx([])) is None, name

