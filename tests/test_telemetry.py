"""Serving telemetry layer (runtime/telemetry.py + engine integration).

Unit level: the typed metrics registry (get-or-create, kind collision,
begin_serve per-serve drop vs lifetime persist, exact-then-bucketed
histogram quantiles, markdown reference table), the trace-schema
validator on synthetic good/bad event sequences, and the Chrome
trace-event exporter roundtrip.  Engine level: lifecycle tracing must be
schedule-invisible (greedy tokens bit-identical with tracing on vs off,
including under preemption/swap/resume pressure), the emitted trace must
satisfy every schema invariant and reconcile against ``last_stats``, and
dynamic per-serve keys from one serve must never leak into the next
serve's stats (the stale-``last_stats``-keys regression).  The engine's
counters of compaction work, queue waits and program builds follow a
schedule derived by hand, a compaction pass skipped because it would fold
nothing leaves tokens and memory state bit-identical, and every named
scope of ``TRACE_NAMES`` reaches the metadata of the lowered compaction,
absorb and packed-step programs.
"""

import dataclasses
import json
import pathlib
import re

import numpy as np
import pytest

import jax

from repro.core import kv_compress, layer_state
from repro.core.request_cluster import Request
from repro.models import transformer as tfm
from repro.models.config import ModelConfig
from repro.runtime.kv_pool import BlockPool, PagedKVConfig
from repro.runtime import server as server_mod
from repro.runtime.scheduler import SLOConfig
from repro.runtime.server import Server, ServerConfig
from repro.runtime.telemetry import (BUILD_EVENTS, TRACE_NAMES, TRACE_SCHEMA,
                                     MetricsRegistry, ProgramBuilds,
                                     StepSpans, TelemetryConfig, Tracer,
                                     annotation, events_from_chrome,
                                     phase_breakdown, scope, spanned,
                                     validate_chrome_file,
                                     validate_jsonl_file, validate_trace,
                                     write_chrome_trace, write_jsonl)
from repro.runtime.template_store import TemplateStoreConfig

from _subproc import run_sub

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab=64,
                   pad_vocab_multiple=16, dtype="float32")
CCFG = kv_compress.KVCompressConfig(n_clusters=8, iters=4, keep_recent=16,
                                    refresh_every=8)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(jax.random.PRNGKey(0), TINY)


def _mixed_stream(n=8, n_high=3, seed=3, vocab=64):
    rng = np.random.default_rng(seed)
    reqs, prompts = [], {}
    for i in range(n):
        plen = int(rng.integers(6, 30))
        prompts[i] = rng.integers(0, vocab, size=(plen,)).astype(np.int32)
        reqs.append(Request(i, plen, int(rng.integers(6, 14)),
                            priority=1 if i >= n - n_high else 0))
    return reqs, prompts


# ---------------------------------------------------------------------------
# unit: metrics registry
# ---------------------------------------------------------------------------


class TestMetricsRegistry:

    def test_get_or_create_and_kind_collision(self):
        reg = MetricsRegistry()
        c = reg.counter("x", "help")
        c.add(2)
        assert reg.counter("x") is c            # same object back
        assert reg.flat_view() == {"x": 2.0}
        with pytest.raises(ValueError):
            reg.gauge("x")                      # kind collision

    def test_begin_serve_drops_per_serve_keeps_persist(self):
        reg = MetricsRegistry()
        reg.gauge("template_cluster0_cohesion").set(0.9)
        reg.counter("sched_preemptions").add(3)
        reg.counter("template_hits_total", persist=True).set_to(7)
        reg.begin_serve()
        assert reg.flat_view() == {"template_hits_total": 7.0}
        # republish is monotone: a fresh store view can't move it back
        reg.counter("template_hits_total", persist=True).set_to(5)
        assert reg.flat_view() == {"template_hits_total": 7.0}

    def test_histogram_exact_matches_percentile(self):
        reg = MetricsRegistry()
        h = reg.histogram("ttft", quantiles=(50, 95, 99), scale=1e3,
                          suffix="_ms")
        rng = np.random.default_rng(0)
        vals = rng.exponential(0.05, size=200)
        for v in vals:
            h.observe(v)
        assert h.exact
        view = h.view()
        for q in (50, 95, 99):
            want = float(np.percentile(vals, q) * 1e3)
            assert view[f"ttft_p{q}_ms"] == want   # bit-identical

    def test_histogram_bucket_fallback_past_cap(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", quantiles=(50,), max_samples=8)
        for v in np.linspace(0.5, 4.0, 32):
            h.observe(v)
        assert not h.exact
        got = h.quantile(50)
        # bucketed estimate stays inside the observed range
        assert 0.5 <= got <= 8.0
        assert h.count == 32

    def test_flat_view_insertion_order(self):
        reg = MetricsRegistry()
        for name in ("b", "a", "c"):
            reg.gauge(name).set(1.0)
        assert list(reg.flat_view()) == ["b", "a", "c"]

    def test_reference_table(self):
        reg = MetricsRegistry()
        reg.counter("gen_tokens", "tokens generated")
        reg.counter("template_hits_total", "lifetime hits", persist=True)
        reg.histogram("ttft", "time to first token", quantiles=(50, 95),
                      suffix="_ms")
        table = reg.reference_table()
        assert "| `gen_tokens` | counter | tokens generated |" in table
        assert "counter (lifetime)" in table
        assert "`ttft_p50_ms`, `ttft_p95_ms`" in table


# ---------------------------------------------------------------------------
# unit: trace validator on synthetic sequences
# ---------------------------------------------------------------------------


def _ev(name, ts, uid=None, tid="engine", pid=0, **args):
    return {"name": name, "ph": "i", "ts": float(ts), "pid": pid,
            "tid": tid, "uid": uid, "args": args}


def _sp(name, ts, dur, uid=None, tid="engine", pid=0, **args):
    return {"name": name, "ph": "X", "ts": float(ts), "dur": float(dur),
            "pid": pid, "tid": tid, "uid": uid, "args": args}


class TestValidateTrace:

    def _good(self):
        return [
            _ev("queued", 0.0, uid=1, tid="queue"),
            _ev("queued", 1.0, uid=2, tid="queue"),
            _sp("engine_step", 10.0, 5.0, kind="mixed"),
            _ev("first_token", 15.0, uid=1, tid="slot0"),
            _sp("swap_out", 20.0, 2.0, uid=1, tid="slot0"),
            _sp("run", 5.0, 22.0, uid=1, tid="slot0", tokens=3),
            _sp("resume", 30.0, 2.0, uid=1, tid="slot0"),
            _ev("finish", 40.0, uid=1, tid="slot0"),
            _sp("run", 30.0, 10.0, uid=1, tid="slot0", tokens=4),
            _ev("shed", 41.0, uid=2, tid="queue"),
        ]

    def test_clean_sequence_validates(self):
        assert validate_trace(self._good()) == []

    def test_missing_terminal_flagged(self):
        evs = [e for e in self._good()
               if not (e["name"] == "finish" and e["uid"] == 1)]
        assert any("uid 1" in p and "terminal" in p
                   for p in validate_trace(evs))

    def test_double_terminal_flagged(self):
        evs = self._good() + [_ev("finish", 50.0, uid=1, tid="slot0")]
        assert any("uid 1: 2 terminal" in p for p in validate_trace(evs))

    def test_partial_overlap_flagged(self):
        evs = [_sp("engine_step", 0.0, 10.0),
               _sp("compact", 5.0, 10.0)]      # straddles the step end
        assert any("partially overlaps" in p for p in validate_trace(evs))
        # proper nesting and disjoint siblings both pass
        assert validate_trace([_sp("engine_step", 0.0, 10.0),
                               _sp("compact", 2.0, 3.0),
                               _sp("engine_step", 20.0, 5.0)]) == []

    def test_swap_pairing(self):
        bad = [_sp("resume", 5.0, 1.0, uid=3, tid="slot0")]
        assert any("resume without matching swap_out" in p
                   for p in validate_trace(bad))
        parked = [_sp("swap_out", 1.0, 1.0, uid=3, tid="slot0")]
        assert any("still parked" in p for p in validate_trace(parked))
        # parked-then-shed is a legal end state
        assert validate_trace(parked
                              + [_ev("shed", 9.0, uid=3)]) == []

    def test_totals_reconciliation(self):
        evs = self._good()
        totals = {"sched_swaps_out": 1.0, "sched_swaps_in": 1.0,
                  "sched_sheds": 1.0, "decode_steps": 1.0,
                  "gen_tokens": 7.0}
        assert validate_trace(evs, totals=totals) == []
        assert any("gen_tokens" in p for p in validate_trace(
            evs, totals={**totals, "gen_tokens": 99.0}))
        assert any("decode_steps" in p for p in validate_trace(
            evs, totals={**totals, "decode_steps": 2.0}))

    def test_phase_breakdown(self):
        # compact/absorb spans time a dispatch, not a phase: the device
        # time of a compaction lands in the next engine_step
        ph = phase_breakdown([
            _sp("engine_step", 0.0, 1000.0, kind="decode"),
            _sp("engine_step", 2000.0, 3000.0, kind="mixed"),
            _sp("compact", 6000.0, 500.0),
            _sp("absorb", 7000.0, 200.0),
            _sp("swap_out", 8000.0, 250.0, uid=1, tid="slot0")])
        assert ph == {"phase_decode_ms": 1.0, "phase_mixed_ms": 3.0,
                      "phase_swap_out_ms": 0.25}


# ---------------------------------------------------------------------------
# unit: exporters
# ---------------------------------------------------------------------------


class TestExporters:

    def test_chrome_roundtrip(self, tmp_path):
        tr = Tracer()
        tr.begin_serve(100.0, n_shards=2)
        tr.event("queued", tid="queue", uid=4, t=100.0, queue_pos=0)
        tr.span("run", 100.0, 100.5, pid=1, tid="slot3", uid=4, tokens=5)
        tr.event("finish", 100.5, uid=4, tid="slot3", t=100.5)
        evs = tr.finish()
        path = str(tmp_path / "trace.json")
        write_chrome_trace(evs, path, n_shards=2,
                           stats={"gen_tokens": 5.0})
        obj = json.load(open(path))
        assert obj["otherData"]["schema"] == TRACE_SCHEMA
        assert obj["otherData"]["ts_origin"] == "serve"
        # metadata names every (pid, tid) track for Perfetto
        meta = {(e["pid"], e["name"]) for e in obj["traceEvents"]
                if e["ph"] == "M"}
        assert (1, "process_name") in meta and (1, "thread_name") in meta
        back = events_from_chrome(obj)
        assert [(e["name"], e["tid"], e["uid"]) for e in back] == \
            [("queued", "queue", 4), ("run", "slot3", 4),
             ("finish", "slot3", 4)]
        assert back[1]["args"]["tokens"] == 5
        assert validate_chrome_file(path) == []

    def test_jsonl_roundtrip(self, tmp_path):
        tr = Tracer()
        tr.begin_serve(0.0)
        tr.span("run", 0.0, 1.0, uid=1, tid="slot0", tokens=2)
        tr.event("finish", t=1.0, uid=1, tid="slot0")
        path = str(tmp_path / "trace.jsonl")
        write_jsonl(tr.finish(), path, meta={"last_stats":
                                             {"gen_tokens": 2.0}})
        assert validate_jsonl_file(path) == []
        bad = str(tmp_path / "bad.jsonl")
        write_jsonl([_sp("run", 0.0, 1.0, uid=9, tid="slot0")], bad)
        assert validate_jsonl_file(bad) != []

    def test_tracer_cap_counts_dropped(self):
        tr = Tracer(max_events=2)
        tr.begin_serve(0.0)
        for i in range(5):
            tr.event("queued", uid=i, t=float(i))
        assert len(tr.events) == 2 and tr.dropped == 3


# ---------------------------------------------------------------------------
# engine: tracing is schedule-invisible and traces validate
# ---------------------------------------------------------------------------


def _scfg(trace, pool_blocks=10):
    return ServerConfig(
        batch_size=2, max_seq=96, kv_compress=CCFG, prefill_chunk=8,
        paged=PagedKVConfig(block_size=4, pool_blocks=pool_blocks),
        use_clustered_batching=False,
        scheduler=SLOConfig(priority_admission=False),
        telemetry=TelemetryConfig(trace=True) if trace else None)


class TestEngineTracing:

    def test_tokens_bit_identical_and_trace_validates(self, params,
                                                      tmp_path):
        """Tracing on vs off under preemption/swap/resume pressure:
        tokens must be bit-identical, and the emitted trace must pass
        every schema invariant AND reconcile against last_stats."""
        reqs, prompts = _mixed_stream()
        off = Server(TINY, _scfg(False), params)
        ref = {o.uid: o.tokens for o in off.serve(reqs, prompts)}
        assert off.last_trace == []            # tracer never constructed

        on = Server(TINY, _scfg(True), params)
        outs = {o.uid: o.tokens for o in on.serve(reqs, prompts)}
        assert outs == ref
        assert on.last_stats["sched_preemptions"] >= 1.0
        evs = on.last_trace
        assert validate_trace(evs, totals=on.last_stats) == []
        names = {e["name"] for e in evs}
        # the lifecycle story is all there, including the swap arc
        for want in ("queued", "run", "first_token", "finish",
                     "engine_step", "prefill_chunk", "swap_out",
                     "resume", "brownout"):
            assert want in names, want
        # brownout events carry the rung and a reason
        br = [e for e in evs if e["name"] == "brownout"]
        assert br and all("rung" in e["args"] and "why" in e["args"]
                          for e in br)
        # exported chrome file validates standalone (CI's check)
        path = str(tmp_path / "trace.json")
        on.export_trace(path)
        assert validate_chrome_file(path) == []
        ph = phase_breakdown(evs)
        assert ph.get("phase_swap_out_ms", 0.0) > 0.0
        assert any(k.startswith("phase_") for k in ph)

    def test_trace_resets_between_serves(self, params):
        srv = Server(TINY, _scfg(True, pool_blocks=48), params)
        reqs, prompts = _mixed_stream(n=3, n_high=0)
        srv.serve(reqs, prompts)
        first = srv.last_trace
        srv.serve(reqs, prompts)
        assert validate_trace(srv.last_trace,
                              totals=srv.last_stats) == []
        assert srv.last_trace is not first


# ---------------------------------------------------------------------------
# engine: stale last_stats keys cannot leak across serves
# ---------------------------------------------------------------------------


class TestStaleStatsRegression:

    def test_dynamic_keys_dropped_between_serves(self, params):
        """Per-serve dynamic keys (template_cluster*, prefix_*) from a
        templated serve must vanish from last_stats once the traffic
        that produced them is gone; lifetime *_total keys persist."""
        scfg = ServerConfig(
            batch_size=2, max_seq=96, kv_compress=CCFG, prefill_chunk=8,
            paged=PagedKVConfig(block_size=4, pool_blocks=24),
            template_store=TemplateStoreConfig(max_entries=2))
        srv = Server(TINY, scfg, params)
        rng = np.random.default_rng(0)
        tpl = rng.integers(0, 64, size=(16,)).astype(np.int32)
        reqs, prompts = [], {}
        for i in range(4):
            sfx = rng.integers(0, 64, size=(3,))
            prompts[i] = np.concatenate([tpl, sfx]).astype(np.int32)
            reqs.append(Request(i, len(prompts[i]), 4))
        def cid_keys(st):
            # per-cluster keys only: template_cluster<digit>..., not the
            # aggregate template_clusters / template_clusters_retired
            return {k for k in st if k.startswith("template_cluster")
                    and k[len("template_cluster")].isdigit()}

        srv.serve(reqs, prompts)
        srv.serve(reqs, prompts)               # warm serve forms clusters
        st1 = dict(srv.last_stats)
        assert cid_keys(st1)
        hits_total = st1["template_hits_total"]
        assert hits_total >= 1.0

        srv.invalidate_templates()             # template traffic is gone
        reqs2, prompts2 = _mixed_stream(n=3, n_high=0, seed=9)
        srv.serve(reqs2, prompts2)
        st2 = srv.last_stats
        # the invalidated store re-clusters fresh traffic under NEW cids
        # (the cid counter never resets), so serve 3's stats may carry
        # new-cid keys — but every serve-2-era cid key is stale and must
        # be gone, and the keys present must mirror the live clusters
        live = {int(c["cid"]) for c in srv._store.cluster_stats()[:8]}
        got = cid_keys(st2)
        want = {f"template_cluster{cid}_{sfx}" for cid in live
                for sfx in ("cohesion", "hit_rate", "bytes_pinned")}
        assert got == want
        assert not (got & cid_keys(st1))
        # lifetime totals survive the per-serve drop, monotonically
        assert st2["template_hits_total"] >= hits_total

    def test_sched_keys_absent_without_scheduler(self, params):
        """A scheduler-less server built after a scheduled one shares no
        registry, and a single server never leaks sched_* keys into a
        serve that has no scheduler — the per-server config is fixed, so
        the cross-serve hazard is per-serve dynamic keys only (covered
        above); here: the baseline absence contract still holds."""
        reqs, prompts = _mixed_stream(n=3, n_high=0)
        srv = Server(TINY, ServerConfig(
            batch_size=2, max_seq=96, kv_compress=CCFG, prefill_chunk=8,
            paged=PagedKVConfig(block_size=4)), params)
        srv.serve(reqs, prompts)
        assert not any(k.startswith("sched_") for k in srv.last_stats)
        assert not any(k.startswith("template_") for k in srv.last_stats)


class TestMetricsReference:
    """The committed metrics reference (docs/metrics.md) is generated
    from the live registrations via `python -m repro.runtime.telemetry
    reference` — this pins it fresh so a new or renamed metric cannot
    ship undocumented."""

    def test_docs_metrics_md_up_to_date(self):
        import pathlib
        from repro.runtime.telemetry import reference_doc
        root = pathlib.Path(__file__).resolve().parent.parent
        path = root / "docs" / "metrics.md"
        assert path.exists(), "docs/metrics.md missing — generate with " \
            "`python -m repro.runtime.telemetry reference > docs/metrics.md`"
        doc = reference_doc()
        assert path.read_text() == doc, \
            "docs/metrics.md is stale — regenerate with " \
            "`python -m repro.runtime.telemetry reference > docs/metrics.md`"

    def test_reference_covers_recurrent_family_metrics(self):
        """The layer-state refactor's new always-present metrics are in
        the reference (and therefore in the committed docs)."""
        from repro.runtime.telemetry import reference_registry
        names = set(reference_registry()._metrics)
        for key in ("kv_retired_recurrent", "state_bytes_ring",
                    "state_bytes_recurrent", "sched_swap_bytes"):
            assert key in names, key


# ---------------------------------------------------------------------------
# engine: compaction work, queue waits and program builds are counted
# ---------------------------------------------------------------------------


def _two_streams(keep_recent, trace=False):
    """Two slots, two requests of an 8-token prompt and 10 tokens each,
    compaction every 4 decode tokens."""
    ccfg = kv_compress.KVCompressConfig(n_clusters=8, iters=4,
                                        keep_recent=keep_recent,
                                        refresh_every=4)
    scfg = ServerConfig(batch_size=2, max_seq=64, kv_compress=ccfg,
                        prefill_chunk=8, paged=PagedKVConfig(block_size=4),
                        use_clustered_batching=False,
                        telemetry=TelemetryConfig(trace=trace))
    rng = np.random.default_rng(0)
    prompts = {u: rng.integers(0, 64, size=(8,)).astype(np.int32)
               for u in range(2)}
    return scfg, [Request(u, 8, 10) for u in range(2)], prompts


class TestEngineCounters:

    @pytest.mark.parametrize("keep_recent,folded,skipped,gaps",
                             [(32, 0, 4, 0), (16, 2, 2, 3)],
                             ids=["no_fold", "fold"])
    def test_compaction_and_queue_counters(self, params, keep_recent,
                                           folded, skipped, gaps):
        """The schedule, by hand: one admitting slot per shard, so A's
        one chunk goes in launch 1 and B's in launch 2; A decodes its
        tokens at launches 1-10, B at 2-11.  A slot is due after 4 of its
        own decode tokens: A at launches 5 and 9, B at 6 and 10, four
        passes of 2 slot rows.  Streams still decoding at the passes: 2,
        2, 2 and 1 (A finished at launch 10).  The frontier target is
        pos - keep_recent + 4 and pos is 12 at a stream's first pass, 16
        at its second: a ring of 16 folds the second passes only, a ring
        of 32 folds none.  A pass whose due slots fold nothing is not
        launched: with the ring of 16 the first two passes are skipped
        and the passes at launches 9 and 10 run (2 and 1 streams still
        decoding); with the ring of 32 all four are skipped.  Slots due
        count every pass, launched or skipped."""
        scfg, reqs, prompts = _two_streams(keep_recent)
        srv = Server(TINY, scfg, params)
        outs = srv.serve(reqs, prompts)
        st = srv.last_stats
        assert [len(o.tokens) for o in outs] == [10, 10]
        assert st["decode_steps"] == 11.0
        assert st["kv_compactions"] == 4.0 - skipped
        assert st["kv_compact_slot_rows"] == 2.0 * (4 - skipped)
        assert st["kv_compact_slots_due"] == 4.0
        assert st["kv_compact_slots_folded"] == float(folded)
        assert st["kv_compact_gaps"] == float(gaps)
        assert st["kv_compact_passes_skipped"] == float(skipped)
        assert st["kv_retired_frontier"] == 4.0 * folded
        assert 0.0 < st["queue_slot_wait_s"] <= st["queue_wait_s"]
        ttft_s = sum(o.prefill_ms for o in outs) / 1e3
        assert st["queue_wait_s"] <= ttft_s

    def test_programs_built_counts_new_programs_only(self, params):
        scfg, reqs, prompts = _two_streams(16, trace=True)
        srv = Server(TINY, scfg, params)
        srv.serve(reqs, prompts)
        first = dict(srv.last_stats)
        built = [e for e in srv.last_trace if e["name"] == "program_built"]
        assert first["programs_built"] >= 1.0
        assert first["program_build_s"] > 0.0
        assert len(built) == first["programs_built"]
        assert any("_packed_fn" in e["args"]["fun_name"] for e in built)
        assert validate_trace(srv.last_trace, totals=first) == []

        srv.serve(reqs, prompts)                   # same shapes again
        second = srv.last_stats
        assert second["programs_built"] == 0.0
        assert not [e for e in srv.last_trace
                    if e["name"] == "program_built"]
        assert (second["programs_built_total"]
                == first["programs_built_total"] >= first["programs_built"])
        assert (second["program_build_s_total"]
                >= first["program_build_s_total"]
                >= first["program_build_s"])


def _clustered_leaves(cache):
    """Every clustered-KV leaf's centroid bank and frontier, on the host."""
    if isinstance(cache, dict) and "k_cents" in cache:
        return [{k: np.asarray(cache[k])
                 for k in ("cov", "counts", "k_cents", "v_cents")}]
    kids = (cache.values() if isinstance(cache, dict)
            else cache if isinstance(cache, list) else ())
    return [leaf for kid in kids for leaf in _clustered_leaves(kid)]


def skip_matches_launch(engine):
    """Serve ``_two_streams(16)`` (first passes fold nothing, second
    passes fold) with every due pass launched, as before the skip rule,
    and with the rule; check tokens, the final centroid banks and
    frontiers, and the host frontier mirror.  ``engine`` is ``dense``,
    ``paged`` or ``mesh`` (the dense engine on a 2x1 mesh, which needs
    two devices)."""
    params = tfm.init_params(jax.random.PRNGKey(0), TINY)
    scfg, reqs, prompts = _two_streams(16)
    if engine != "paged":
        scfg = dataclasses.replace(scfg, paged=None)
    if engine == "mesh":
        from repro.launch.mesh import make_serving_mesh
        scfg = dataclasses.replace(scfg, mesh=make_serving_mesh("2x1"))
    rule, state_bytes = (server_mod._frontier_advances,
                         layer_state.ring_state_bytes)
    runs = {}
    for name in ("launch", "skip"):
        seen = {}

        def advances(due, pos, fr, name=name, seen=seen):
            seen["fr"] = fr
            return name == "launch" or rule(due, pos, fr)

        def final_cache(cache, n, seen=seen):
            seen["cache"] = cache           # read once, at the serve's end
            return state_bytes(cache, n)

        server_mod._frontier_advances = advances
        layer_state.ring_state_bytes = final_cache
        try:
            srv = Server(TINY, scfg, params)
            outs = srv.serve(reqs, prompts)
        finally:
            server_mod._frontier_advances = rule
            layer_state.ring_state_bytes = state_bytes
        runs[name] = ({o.uid: o.tokens for o in outs},
                      _clustered_leaves(seen["cache"]), seen["fr"].cov,
                      srv.last_stats)
    (tok_l, leaves_l, _, st_l), (tok_s, leaves_s, cov_s, st_s) = (
        runs["launch"], runs["skip"])
    # each stream's first pass folds nothing and its second folds; on the
    # mesh both streams start in the first launch, so each pass holds
    # both due slots: two passes, not four
    passes = st_l["kv_compactions"]
    assert passes in (2.0, 4.0) and st_l["kv_compact_passes_skipped"] == 0
    assert st_s["kv_compactions"] == st_s["kv_compact_passes_skipped"]
    assert st_s["kv_compactions"] + st_s["kv_compact_passes_skipped"] == passes
    assert st_s["kv_compact_slots_folded"] == st_l["kv_compact_slots_folded"]
    assert tok_s == tok_l
    assert len(leaves_s) == len(leaves_l) > 0
    for got, want in zip(leaves_s, leaves_l):
        for key in want:
            np.testing.assert_array_equal(got[key], want[key], err_msg=key)
        # both slots sit at rows 0 and 1 (one shard of two slots, or two
        # shards of one); a stacked leaf repeats the frontier per layer
        dev = got["cov"].reshape(-1, got["cov"].shape[-1])
        for row in dev:
            np.testing.assert_array_equal(row, cov_s[:len(row)])


class TestCompactionSkip:

    @pytest.mark.parametrize("engine", ["dense", "paged", "mesh"])
    def test_skipped_passes_serve_bit_identical_state(self, engine):
        if engine != "mesh":
            skip_matches_launch(engine)
            return
        run_sub(f"""
            import sys
            sys.path.insert(0, {str(pathlib.Path(__file__).parent)!r})
            import test_telemetry
            test_telemetry.skip_matches_launch("mesh")
        """)


class TestProgramBuilds:

    def test_nested_traces_count_once_and_watchers_see_compiles(self):
        trace, lower, compile_ = BUILD_EVENTS
        b = ProgramBuilds()
        seen = []
        b._on_start(trace, 0.0, fun_name="outer")
        b._on_start(trace, 0.0, fun_name="inner")
        b._on_duration(trace, 1.0, fun_name="inner")
        b._on_duration(trace, 3.0, fun_name="outer")
        b._on_duration("/jax/other_duration", 7.0)
        with b.watch(lambda name, secs: seen.append((name, secs))):
            for ev, secs in ((lower, 0.5), (compile_, 2.0)):
                b._on_start(ev, 0.0, fun_name="jit(f)")
                b._on_duration(ev, secs, fun_name="jit(f)")
        b._on_start(compile_, 0.0, fun_name="jit(g)")
        b._on_duration(compile_, 1.0, fun_name="jit(g)")
        assert b.n == 2
        assert b.seconds == 3.0 + 0.5 + 2.0 + 1.0
        assert seen == [("jit(f)", 2.0)]


# ---------------------------------------------------------------------------
# profiler names: one table, scopes in the program metadata
# ---------------------------------------------------------------------------


def _in_name_stack(text, name):
    """Whether an op location of lowered program text names ``name`` as
    one level of its name stack (a transform wraps the level it starts
    at: ``vmap(name)/...``)."""
    return re.search(r'loc\("(?:[^"]*[/(])?' + re.escape(name) + '[/)]',
                     text) is not None


class TestProfilerNames:

    def test_names_are_checked_against_the_table(self):
        with pytest.raises(KeyError):
            scope("no_such_scope")
        with pytest.raises(KeyError):
            annotation("kmedians_median")          # a scope, not a span
        assert {k for k, _ in TRACE_NAMES.values()} == {"scope", "span"}

    def test_step_spans_nest_and_close(self):
        log = []

        class Rec:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                log.append(("+", self.name))

            def __exit__(self, *exc):
                log.append(("-", self.name))

        sp = StepSpans(Rec)
        sp.step()
        sp.phase("sched_admit")
        sp.phase("kv_compact")
        sp.step()
        sp.phase("engine_pack")
        sp.end_phase()
        sp.close()
        assert log == [("+", "engine_step"), ("+", "sched_admit"),
                       ("-", "sched_admit"), ("+", "kv_compact"),
                       ("-", "kv_compact"), ("-", "engine_step"),
                       ("+", "engine_step"), ("+", "engine_pack"),
                       ("-", "engine_pack"), ("-", "engine_step")]

        @spanned(Rec, "sched_preempt")
        def work(x):
            log.append(("work", x))
            return x + 1

        del log[:]
        assert work(1) == 2
        assert log == [("+", "sched_preempt"), ("work", 1),
                       ("-", "sched_preempt")]

    @pytest.fixture(scope="class")
    def lowered(self, params):
        """Metadata text of the paged engine's compaction, absorb and
        packed-step programs, lowered from shapes."""
        scfg, _, _ = _two_streams(16)
        srv = Server(TINY, scfg, params)
        n, r, c = scfg.batch_size, 16, 8
        pool = BlockPool(n, r, scfg.paged, n_shards=1, slots_per_shard=n,
                         full_tail_resident=True)
        cache = jax.eval_shape(lambda: tfm.init_cache(
            TINY, n, scfg.max_seq, kv_mode="clustered", kv_clusters=c,
            kv_tail=r, kv_pool_blocks=pool.n_blocks,
            kv_block_size=scfg.paged.block_size))

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, np.int32)

        t = pool.blocks_per_slot
        lows = {
            "compact": srv._compact_paged.lower(cache, i32(n), i32(n, t)),
            "absorb": srv._absorb_paged.lower(cache, i32(), i32(), i32(),
                                              i32(t)),
            "packed": srv._decode_packed.lower(
                params, cache, *(i32(4) for _ in range(5)), i32(n, t), 1),
        }
        return {k: low.as_text(debug_info=True) for k, low in lows.items()}

    @pytest.mark.parametrize("program,scopes", [
        ("compact", ("compact_gather", "kmedians_assign", "kmedians_median",
                     "compact_write")),
        ("absorb", ("compact_gather", "kmedians_assign", "kmedians_median",
                    "kmedians_reseed", "compact_write")),
        ("packed", ("kv_pool_write", "paged_attention", "mlp", "lm_head")),
    ])
    def test_scopes_reach_program_metadata(self, lowered, program, scopes):
        for name in scopes:
            assert _in_name_stack(lowered[program], name), (program, name)

    def test_every_scope_is_in_a_program(self, lowered):
        text = "".join(lowered.values())
        assert [name for name, (kind, _) in TRACE_NAMES.items()
                if kind == "scope" and not _in_name_stack(text, name)] == []

    # the needles bench/metrics matches in device-trace program names
    # (kv_manage_share, step_device_ms) and op names
    # (paged_decode_roofline): a rename silences a metric
    @pytest.mark.parametrize("attr,needle", [
        ("_decode_packed", "_packed_fn"), ("_compact_paged", "compact"),
        ("_absorb_paged", "absorb"), ("_reset_slot", "reset_slot"),
        ("_write_slot_paged", "write_slot"), ("_cow", "cow"),
        ("_swap_in", "swap_in"), ("_swap_out", "swap_out")])
    def test_program_names_keep_the_benchmark_needles(self, params, attr,
                                                      needle):
        scfg, _, _ = _two_streams(16)
        assert needle in getattr(Server(TINY, scfg, params), attr).__name__

    def test_module_and_kernel_names_in_lowered_programs(self, lowered):
        def module(text):
            return next(ln for ln in text.splitlines()
                        if ln.startswith("module @"))

        assert "_packed_fn" in module(lowered["packed"])
        assert "compact" in module(lowered["compact"])
        assert "absorb" in module(lowered["absorb"])
        assert "paged_clustered_decode" in lowered["packed"]
