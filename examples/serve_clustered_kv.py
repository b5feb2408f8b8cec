"""Serving example: continuous batching + clustered-KV compression.

1. a queue of mixed-length requests is clustered into a padding-minimal
   admission order (bit-serial k-medians over (prompt_len, gen_len)
   features) — padding waste vs FIFO is reported,
2. a slot-based continuous batcher admits requests as decode slots free
   and serves them with a small dense LM (per-slot positions, early exit
   at each request's own token budget),
3. the same queue is re-served from a clustered KV cache that is
   re-compacted mid-stream (batched bit-serial k-medians, fused Pallas
   clustered_decode attention) — the "memory management" half of the
   title — and the standalone compression error vs exact attention is
   reported alongside the memory ratio,
4. when more than one device is visible, the same queue runs once more on
   a (data, model) serving mesh — decode slots shard over `data`,
   attention heads over `model` — and token parity with the single-device
   run is reported (it is bit-exact by construction).

Run: PYTHONPATH=src python examples/serve_clustered_kv.py

Mesh-enabled run (8 fake CPU devices → a 2x4 serving mesh):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/serve_clustered_kv.py
"""

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import kv_compress
from repro.core.request_cluster import Request, plan_batches, plan_fifo
from repro.models import transformer as tfm
from repro.models.config import ModelConfig
from repro.runtime.server import Server, ServerConfig

SMALL = ModelConfig(name="serve-lm", family="dense", n_layers=4, d_model=128,
                    n_heads=4, n_kv_heads=4, head_dim=32, d_ff=512,
                    vocab=512, pad_vocab_multiple=128, dtype="float32")


def main():
    rng = np.random.default_rng(0)
    params = tfm.init_params(jax.random.PRNGKey(0), SMALL)

    # --- request processing: clustered batching ---
    lens = np.where(rng.random(24) < 0.5,
                    rng.integers(8, 24, 24), rng.integers(96, 160, 24))
    reqs = [Request(i, int(l), 8) for i, l in enumerate(lens)]
    fifo = plan_fifo(reqs, batch_size=4)
    clus = plan_batches(reqs, batch_size=4)
    print(f"[batcher] padding waste: fifo {fifo.waste * 100:.1f}% → "
          f"clustered {clus.waste * 100:.1f}%")

    srv = Server(SMALL, ServerConfig(batch_size=4, max_seq=256), params)
    prompts = {r.uid: rng.integers(0, 512, size=(r.prompt_len,)).astype(
        np.int32) for r in reqs}
    outs = srv.serve(reqs, prompts)
    st = srv.last_stats
    print(f"[server] continuous batching: {len(outs)} completions, "
          f"{st['tokens_per_s_wall']:.1f} tok/s, slot waste "
          f"{st['slot_waste'] * 100:.1f}%")

    # --- chunked prefill interleaved with decode (--prefill-chunk) ---
    # Admission stops blocking the decode loop: each engine step feeds one
    # 16-token prompt chunk for at most one admitting slot per data shard,
    # fused into the decode launch (mixed-mode Pallas clustered_decode).
    # Greedy tokens stay identical to blocking admission; TTFT collapses
    # because decode slots never wait for a prefill call, and the
    # bucketed-launch stats show the drain tail shrinking the decode
    # launch once the queue empties.
    srv_k = Server(SMALL, ServerConfig(batch_size=4, max_seq=256,
                                       prefill_chunk=16), params)
    outs_k = srv_k.serve(reqs, prompts)
    same = all(a.tokens == b.tokens for a, b in
               zip(sorted(outs_k, key=lambda o: o.uid),
                   sorted(outs, key=lambda o: o.uid)))
    st = srv_k.last_stats
    print(f"[server] chunked prefill (--prefill-chunk 16): "
          f"{st['tokens_per_s_wall']:.1f} tok/s wall, TTFT p50/p95 "
          f"{st['ttft_p50_ms']:.0f}/{st['ttft_p95_ms']:.0f} ms, "
          f"{st['prefill_chunks']:.0f} chunks, tokens "
          f"{'identical' if same else 'DIVERGED'} vs blocking admission")
    print(f"[server] bucketed launches: mean bucket "
          f"{st['launch_bucket_mean']:.2f} slots/shard "
          f"({st['launch_rows_frac'] * 100:.0f}% of slots launched per "
          f"step; the drain tail stops paying for empty slots)")

    # same queue served from a clustered KV cache with mid-stream
    # compaction (fused Pallas clustered_decode, interpret mode on CPU);
    # prefill_chunk additionally streams long prompts straight into
    # clustered form via kv_compress.absorb_chunk (compaction-aware
    # admission: no exact prompt KV is ever materialized)
    ccfg = kv_compress.KVCompressConfig(n_clusters=24, iters=4,
                                        keep_recent=32, refresh_every=16)
    srv_c = Server(SMALL, ServerConfig(batch_size=4, max_seq=256,
                                       kv_compress=ccfg, prefill_chunk=16),
                   params)
    outs_c = srv_c.serve(reqs, prompts)
    agree = np.mean([np.mean(np.array(a.tokens[:len(b.tokens)])
                             == np.array(b.tokens[:len(a.tokens)]))
                     for a, b in zip(sorted(outs_c, key=lambda o: o.uid),
                                     sorted(outs, key=lambda o: o.uid))])
    print(f"[server] clustered-KV + compaction (+chunked admission, "
          f"{srv_c.last_stats['kv_absorbs']:.0f} absorbs): "
          f"{srv_c.last_stats['tokens_per_s_wall']:.1f} tok/s, token "
          f"agreement vs exact serving {agree * 100:.0f}%")

    # --- paged clustered-KV memory manager (ServerConfig.paged) ---
    # The engines above allocate every slot's exact tail as a full dense
    # ring.  The paged engine instead draws fixed-size blocks from a
    # shared per-shard pool behind per-slot block tables
    # (runtime/kv_pool.py): blocks map lazily right before the write that
    # needs them, recycle the moment a request exits, and return to the
    # pool mid-stream once compaction's coverage frontier passes them.
    # Decode runs as PACKED ragged launches — one row per real
    # (slot, position) pair via the Pallas paged_clustered_decode kernel
    # gathering tail blocks through the block table — so mixed
    # prefill+decode compute scales with real tokens instead of
    # slots × chunk (PagedAttention-style).  Greedy tokens stay
    # bit-identical to the dense clustered engine.
    from repro.runtime.kv_pool import PagedKVConfig
    srv_p = Server(SMALL, ServerConfig(batch_size=4, max_seq=256,
                                       kv_compress=ccfg, prefill_chunk=16,
                                       paged=PagedKVConfig(block_size=8)),
                   params)
    outs_p = srv_p.serve(reqs, prompts)
    same_p = all(a.tokens == b.tokens for a, b in
                 zip(sorted(outs_p, key=lambda o: o.uid),
                     sorted(outs_c, key=lambda o: o.uid)))
    stp, stc = srv_p.last_stats, srv_c.last_stats
    print(f"[server] paged KV (8-pos blocks): tokens "
          f"{'identical' if same_p else 'DIVERGED'} vs dense clustered; "
          f"launch padding {stp['launch_pad_frac'] * 100:.0f}% vs dense "
          f"{stc['launch_pad_frac'] * 100:.0f}%, pool peak "
          f"{stp['pool_occupancy_peak'] * 100:.0f}% of "
          f"{stp['pool_blocks_total']:.0f} blocks "
          f"({stp['pool_allocs']:.0f} allocs / {stp['pool_frees']:.0f} "
          f"frees, {stp['pool_blocks_end']:.0f} still held at drain)")

    # --- prefix-shared paged admission (ServerConfig.prefix_share) ---
    # Bursty templated traffic: many prompts = one shared template + a
    # short unique suffix.  The paged engine's block tables + ref counts
    # let admissions share structure ACROSS requests: chunked admission
    # registers each prompt's prefix state (live tail blocks + absorbed
    # centroids + coverage frontier) at chunk boundaries into a per-shard
    # prefix cache (runtime/prefix_cache.py), and a later request whose
    # prompt matches adopts those blocks and restores that state instead
    # of re-streaming the template — copy-on-write at the first divergent
    # ring write keeps shared payloads immutable.  Greedy tokens stay
    # bit-identical to unshared paged serving (the reused state is
    # exactly what the unshared run would recompute from the same
    # tokens); TTFT collapses because shared-prefix chunks are never
    # fed, and the template's tail blocks exist once per shard instead
    # of once per slot (kv_bytes_saved).  Note the physical peak can
    # still RISE here: admissions that skip the template finish ~5x
    # sooner, so more requests decode concurrently — the engine trades
    # the saved bytes for throughput (benchmarks/run.py prefix_share
    # pins a regime where both p95 TTFT and physical peak KV drop).
    from repro.runtime.prefix_cache import PrefixShareConfig
    tpl = rng.integers(0, 512, size=(96,)).astype(np.int32)
    tpl_reqs, tpl_prompts = [], {}
    for i in range(12):
        sfx = rng.integers(0, 512, size=(int(rng.integers(4, 12)),))
        tpl_prompts[i] = np.concatenate([tpl, sfx]).astype(np.int32)
        tpl_reqs.append(Request(i, len(tpl_prompts[i]), 8))
    srv_u = Server(SMALL, ServerConfig(batch_size=4, max_seq=256,
                                       kv_compress=ccfg, prefill_chunk=16,
                                       paged=PagedKVConfig(block_size=8)),
                   params)
    outs_u = srv_u.serve(tpl_reqs, tpl_prompts)
    srv_s = Server(SMALL, ServerConfig(batch_size=4, max_seq=256,
                                       kv_compress=ccfg, prefill_chunk=16,
                                       paged=PagedKVConfig(block_size=8),
                                       prefix_share=PrefixShareConfig()),
                   params)
    outs_s = srv_s.serve(tpl_reqs, tpl_prompts)
    same_s = all(a.tokens == b.tokens for a, b in
                 zip(sorted(outs_s, key=lambda o: o.uid),
                     sorted(outs_u, key=lambda o: o.uid)))
    stu, sts = srv_u.last_stats, srv_s.last_stats
    print(f"[server] prefix sharing (96-token template x "
          f"{len(tpl_reqs)} requests): tokens "
          f"{'identical' if same_s else 'DIVERGED'} vs unshared paged; "
          f"{sts['prefix_hits']:.0f} hits reused "
          f"{sts['prefix_tokens_reused']:.0f} prompt tokens, TTFT p95 "
          f"{sts['ttft_p95_ms']:.0f} vs {stu['ttft_p95_ms']:.0f} ms, "
          f"{sts['kv_bytes_saved'] / 1024:.0f} KiB of tail KV shared, "
          f"{sts['pool_cow']:.0f} copy-on-write swaps (physical peak "
          f"{sts['kv_bytes_peak_per_shard'] / 1024:.0f} vs "
          f"{stu['kv_bytes_peak_per_shard'] / 1024:.0f} KiB/shard — "
          f"faster admission keeps more requests in flight)")

    # --- persistent template store (ServerConfig.template_store) ---
    # The prefix cache above dies with its serve() call: a second burst
    # of the same template re-pays the whole template prefill.  The
    # template store (runtime/template_store.py) hoists the cache to the
    # Server — entries and the pool blocks they pin survive the
    # inter-stream drain, so a LATER serve of the same templated traffic
    # starts warm: every admission adopts the boundary registered by the
    # previous serve from its first engine step.  The store also
    # clusters the live traffic online (Mettu–Plaxton-style medoid
    # promotion over prefix digests) and steers same-cluster requests
    # onto the shards already holding their blocks.  Two things to know:
    # the pool needs headroom above full slot provisioning (pinned
    # entries live in the surplus — a zero-surplus pool pressure-evicts
    # every entry before the drain), and tokens stay bit-identical
    # because a snapshot is only adopted under the exact config epoch +
    # verified token match that produced it.
    from repro.runtime.template_store import TemplateStoreConfig
    tpl_reqs2, tpl_prompts2 = [], {}
    for i in range(12):
        sfx = rng.integers(0, 512, size=(int(rng.integers(4, 12)),))
        tpl_prompts2[i] = np.concatenate([tpl, sfx]).astype(np.int32)
        tpl_reqs2.append(Request(i, len(tpl_prompts2[i]), 8))
    srv_t = Server(SMALL, ServerConfig(
        batch_size=4, max_seq=256, kv_compress=ccfg, prefill_chunk=16,
        paged=PagedKVConfig(block_size=8, pool_blocks=24),
        template_store=TemplateStoreConfig(max_entries=2)), params)
    srv_t.serve(tpl_reqs, tpl_prompts)        # serve #1 fills the store
    st1 = dict(srv_t.last_stats)
    outs_t = srv_t.serve(tpl_reqs2, tpl_prompts2)   # serve #2: warm
    st2 = srv_t.last_stats
    srv_ref = Server(SMALL, ServerConfig(
        batch_size=4, max_seq=256, kv_compress=ccfg, prefill_chunk=16,
        paged=PagedKVConfig(block_size=8, pool_blocks=24)), params)
    outs_ref = srv_ref.serve(tpl_reqs2, tpl_prompts2)  # cold reference
    ref_uid = {o.uid: o.tokens for o in outs_ref}
    same_t = all(o.tokens == ref_uid[o.uid] for o in outs_t)
    print(f"[server] template store (persistent across serves): warm "
          f"serve TTFT p95 {st2['ttft_p95_ms']:.0f} ms vs "
          f"{st1['ttft_p95_ms']:.0f} ms for the store-filling serve, "
          f"{st2['prefix_hits']:.0f} warm hits reused "
          f"{st2['prefix_tokens_reused']:.0f} prompt tokens, tokens "
          f"{'identical' if same_t else 'DIVERGED'} vs a cold store")
    print(f"[server] store state: {st2['template_entries']:.0f} entries "
          f"pinning {st2['template_pinned_blocks']:.0f} blocks between "
          f"serves ({st2['template_bytes_pinned'] / 1024:.0f} KiB), "
          f"{st2['template_clusters']:.0f} traffic clusters, cohesion "
          f"{st2['template_cohesion_mean']:.2f}")
    srv_t.invalidate_templates()              # drains the pool to zero

    # --- SLO-aware scheduling (ServerConfig.scheduler) ---
    # Overload changes the question from "how fast?" to "who eats the
    # shortage?".  Each Request carries a priority (and optional TTFT
    # deadline); the paged engine plus an SLOConfig walks a brownout
    # ladder when the block pool can't back every in-flight request:
    # defer the admission, then PREEMPT a lower-priority slot — its
    # tail-ring blocks and clustered centroid snapshot are gathered to
    # host memory, its blocks freed, and it resumes mid-stream later,
    # bit-identically, because per-slot state is a deterministic
    # function of the slot's own token stream — and only then shed
    # best-effort work.  The protected class is never shed.  Here the
    # same queue runs priority-tagged (high class arriving LAST, the
    # FIFO worst case) against a pool ~40% under full provisioning;
    # non-shed tokens must match the unpressured paged serve above.
    from repro.runtime.scheduler import SLOConfig
    sreqs = [Request(r.uid, r.prompt_len, r.max_new_tokens,
                     priority=1 if r.uid >= 18 else 0) for r in reqs]
    srv_s = Server(SMALL, ServerConfig(batch_size=4, max_seq=256,
                                       kv_compress=ccfg, prefill_chunk=16,
                                       paged=PagedKVConfig(block_size=8,
                                                           pool_blocks=10),
                                       scheduler=SLOConfig()), params)
    outs_s = srv_s.serve(sreqs, prompts)
    sts = srv_s.last_stats
    p_uid = {o.uid: o.tokens for o in outs_p}
    same_s = all(o.tokens == p_uid[o.uid] for o in outs_s if not o.shed)
    hi_ttft = [o.prefill_ms for o in outs_s if o.uid >= 18 and not o.shed]
    print(f"[server] SLO scheduling (pool 10/16 blocks, 6 priority-1 at "
          f"the tail): {sts['sched_preemptions']:.0f} preemptions, "
          f"{sts['sched_swaps_in']:.0f} swap-ins, "
          f"{sts['sched_deferrals']:.0f} deferrals, "
          f"{sts['sched_sheds']:.0f} best-effort shed "
          f"({sts['sched_shed_high']:.0f} protected shed); priority-1 "
          f"TTFT p95 {np.percentile(hi_ttft, 95):.0f} ms; non-shed "
          f"tokens {'identical' if same_s else 'DIVERGED'} vs the "
          f"unpressured paged serve")

    # --- observability (ServerConfig.telemetry) ---
    # Every number printed above came out of `server.last_stats` — which
    # is now a flat view over a typed metrics registry (`server.metrics`,
    # runtime/telemetry.py): counters, gauges, and histograms with help
    # strings, re-registered each serve so dynamic keys (per-cluster,
    # per-shard, sched_*) can never leak across serves.  Turning on
    # TelemetryConfig(trace=True) additionally records the request
    # LIFECYCLE: queued → admit → prefill chunks → first token → decode
    # → compact/absorb → preempt/swap-out → resume → finish/shed, plus
    # one span per engine step (launch kind, rows, pool occupancy) and a
    # brownout event naming the rung and WHY whenever the SLO ladder
    # acts.  Tracing is host-side only — greedy tokens are bit-identical
    # with it on or off — and `export_trace()` writes a Chrome
    # trace-event file loadable in Perfetto / chrome://tracing (one
    # process per data shard, one thread per decode slot).
    from repro.runtime.telemetry import (TelemetryConfig, phase_breakdown,
                                         validate_trace)
    srv_o = Server(SMALL, ServerConfig(batch_size=4, max_seq=256,
                                       kv_compress=ccfg, prefill_chunk=16,
                                       paged=PagedKVConfig(block_size=8,
                                                           pool_blocks=10),
                                       scheduler=SLOConfig(),
                                       telemetry=TelemetryConfig(
                                           trace=True)), params)
    outs_o = srv_o.serve(sreqs, prompts)
    traced_same = ({o.uid: o.tokens for o in outs_o}
                   == {o.uid: o.tokens for o in outs_s})
    evs = srv_o.last_trace
    problems = validate_trace(evs, totals=srv_o.last_stats)
    kinds = sorted({e["name"] for e in evs})
    ph = phase_breakdown(evs)
    print(f"[telemetry] traced serve: {len(evs)} events "
          f"({', '.join(kinds)}), schema problems: {len(problems)}, "
          f"tokens {'identical' if traced_same else 'DIVERGED'} vs the "
          f"untraced serve")
    print("[telemetry] phase breakdown: " + ", ".join(
        f"{k.removeprefix('phase_').removesuffix('_ms')} {v:.0f} ms"
        for k, v in ph.items()))
    # srv_o.export_trace("slo_trace.json") writes the Perfetto timeline;
    # the registry documents itself — the serving metrics reference:
    table = srv_o.metrics.reference_table()
    print(f"[telemetry] metrics reference ({len(table.splitlines()) - 2} "
          f"metrics; first rows):")
    for line in table.splitlines()[:6]:
        print("    " + line)

    # --- sliding-window serving (RetentionPolicy opens the model zoo) ---
    # Everything above serves an all-global-attention model, where "which
    # ring positions may be dropped?" is answered by the clustered
    # coverage frontier.  That question now lives behind a per-layer
    # RetentionPolicy (core/retention.py), so gemma2/3-style models with
    # alternating local ('L') sliding-window layers serve through the
    # SAME chunked + paged engine: 'G' layers keep FrontierRetention
    # (centroids + cov frontier, unchanged), while each 'L' layer holds a
    # dense window-sized ring under WindowRetention — positions retire
    # the moment they fall more than `sliding_window` steps behind, the
    # pool reclaims their blocks mid-stream, and the paged decode kernel
    # applies the per-row window floor (wlo) alongside the cov mask.
    # Greedy tokens stay bit-identical to blocking dense admission.
    # (QuotaRetention, the third policy, gives un-clustered paged exact
    # KV a per-slot block budget — see benchmarks/run.py serve --paged
    # without --kv-* flags and tests/test_serving_engine.py.)
    import dataclasses as dc
    GLWIN = dc.replace(SMALL, name="serve-lm-gl", layer_pattern="GL",
                       sliding_window=16)
    params_w = tfm.init_params(jax.random.PRNGKey(1), GLWIN)
    w_reqs = [Request(i, int(rng.integers(8, 28)), 8) for i in range(12)]
    w_prompts = {r.uid: rng.integers(0, 512, size=(r.prompt_len,)).astype(
        np.int32) for r in w_reqs}
    ccfg_w = kv_compress.KVCompressConfig(n_clusters=8, iters=4,
                                          keep_recent=32, refresh_every=8)
    srv_wb = Server(GLWIN, ServerConfig(batch_size=4, max_seq=96,
                                        kv_compress=ccfg_w), params_w)
    outs_wb = srv_wb.serve(w_reqs, w_prompts)
    srv_w = Server(GLWIN, ServerConfig(batch_size=4, max_seq=96,
                                       kv_compress=ccfg_w, prefill_chunk=8,
                                       paged=PagedKVConfig(block_size=8)),
                   params_w)
    outs_w = srv_w.serve(w_reqs, w_prompts)
    same_w = all(a.tokens == b.tokens for a, b in
                 zip(sorted(outs_w, key=lambda o: o.uid),
                     sorted(outs_wb, key=lambda o: o.uid)))
    stw = srv_w.last_stats
    print(f"[server] sliding-window model ('GL' x2, window=16, chunked + "
          f"paged): tokens {'identical' if same_w else 'DIVERGED'} vs "
          f"blocking dense; window retired {stw['kv_retired_window']:.0f} "
          f"positions, frontier retired {stw['kv_retired_frontier']:.0f}, "
          f"{stw['pool_blocks_end']:.0f} blocks held at drain")

    # --- recurrent-state serving (layer-state families open mamba2) ---
    # RetentionPolicy answers "which ring positions may drop?", but a
    # mamba2 ('M') or RG-LRU ('R') layer holds no ring at all — its
    # per-slot state is a fixed-size (conv window, state matrix) pair.
    # core/layer_state.py names that split: every layer belongs to a
    # LayerState family, RingKVState ('G'/'L', retention-governed,
    # pool-backed when paged) or RecurrentState ('M'/'R', advanced inside
    # the same mixed prefill+decode launch, snapshotted whole).  A hybrid
    # 'GM' model therefore serves through the SAME chunked + paged engine
    # — 'G' layers cluster and page as above while the 'M' layer's state
    # rides along — and greedy tokens stay bit-identical to blocking
    # one-at-a-time decode.  Checkpoints carry both families, so
    # prefix-sharing and preempt -> swap -> resume work unchanged (the
    # recurrent state's bytes are priced into the swap ledger).
    from repro.models.config import SSMConfig
    GMREC = ModelConfig(name="serve-lm-gm", family="hybrid", n_layers=2,
                        d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                        d_ff=128, vocab=512, pad_vocab_multiple=128,
                        dtype="float32", layer_pattern="GM",
                        ssm=SSMConfig(d_state=16, d_conv=4, expand=2,
                                      head_dim=32, n_groups=1, chunk=32))
    params_r = tfm.init_params(jax.random.PRNGKey(2), GMREC)
    r_reqs = [Request(i, int(rng.integers(8, 28)), 8) for i in range(8)]
    r_prompts = {r.uid: rng.integers(0, 512, size=(r.prompt_len,)).astype(
        np.int32) for r in r_reqs}
    srv_rb = Server(GMREC, ServerConfig(batch_size=1, engine="static",
                                        use_clustered_batching=False),
                    params_r)
    outs_rb = srv_rb.serve(r_reqs, r_prompts)
    srv_r = Server(GMREC, ServerConfig(batch_size=4, max_seq=96,
                                       kv_compress=ccfg_w, prefill_chunk=8,
                                       paged=PagedKVConfig(block_size=8)),
                   params_r)
    outs_r = srv_r.serve(r_reqs, r_prompts)
    same_r = all(a.tokens == b.tokens for a, b in
                 zip(sorted(outs_r, key=lambda o: o.uid),
                     sorted(outs_rb, key=lambda o: o.uid)))
    str_ = srv_r.last_stats
    print(f"[server] hybrid recurrent model ('GM', chunked + paged): tokens "
          f"{'identical' if same_r else 'DIVERGED'} vs blocking decode; "
          f"state bytes/slot ring {str_['state_bytes_ring']:.0f} / "
          f"recurrent {str_['state_bytes_recurrent']:.0f}, recurrent "
          f"retired {str_['kv_retired_recurrent']:.0f} (fixed-size state "
          f"never retires), {str_['pool_blocks_end']:.0f} blocks at drain")

    # --- mesh-sharded serving (slots x tensor parallel) ---
    # With N>1 visible devices (XLA_FLAGS above) the same queue is served
    # on a (data, model) mesh: the engine cache becomes sharded arrays
    # (slots over data, kv heads over model), the Pallas clustered_decode
    # kernel dispatches per shard via shard_map, and greedy tokens stay
    # bit-identical to the single-device run.
    n_dev = len(jax.devices())
    if n_dev > 1:
        from repro.launch.mesh import make_serving_mesh
        model_par = 4 if n_dev % 8 == 0 else 2
        spec = f"{n_dev // model_par}x{model_par}"
        mesh = make_serving_mesh(spec)
        srv_m = Server(SMALL, ServerConfig(batch_size=4, max_seq=256,
                                           kv_compress=ccfg,
                                           prefill_chunk=16, mesh=mesh),
                       params)
        outs_m = srv_m.serve(reqs, prompts)
        by_uid = {o.uid: o.tokens for o in outs_c}
        exact = all(o.tokens == by_uid[o.uid] for o in outs_m)
        print(f"[server] mesh {spec}: "
              f"{srv_m.last_stats['tokens_per_s_wall']:.1f} tok/s, tokens "
              f"{'bit-identical' if exact else 'DIVERGED'} vs single-device")
    else:
        print("[server] mesh serving skipped (1 device; set XLA_FLAGS="
              "--xla_force_host_platform_device_count=8 to try a 2x4 mesh)")

    # --- memory management: clustered-KV compression ---
    long_prompt = rng.integers(0, 512, size=(1, 192)).astype(np.int32)
    _, cache = jax.jit(lambda tk: tfm.prefill(params, SMALL, tk,
                                              max_seq=256))(
        jnp.asarray(long_prompt))
    kc = np.asarray(cache["scan"]["sub0"]["k"])[0, 0]    # (S, H, Dh) layer 0
    vc = np.asarray(cache["scan"]["sub0"]["v"])[0, 0]
    kj, vj = jnp.asarray(kc[:192]), jnp.asarray(vc[:192])
    cfg = kv_compress.KVCompressConfig(n_clusters=24, iters=8,
                                       keep_recent=32)
    ckv = kv_compress.compress_cache(kj, vj, cfg)
    q = jnp.asarray(rng.normal(size=(SMALL.n_kv_heads,
                                     SMALL.head_dim)).astype(np.float32))
    out_c = kv_compress.clustered_attention(q, ckv, scale=SMALL.head_dim**-0.5)
    out_e = kv_compress.exact_attention(q, kj, vj,
                                        scale=SMALL.head_dim**-0.5)
    err = float(jnp.linalg.norm(out_c - out_e) / jnp.linalg.norm(out_e))
    print(f"[kv] 192 keys → {cfg.n_clusters} median centroids + "
          f"{cfg.keep_recent} exact tail: memory "
          f"{kv_compress.memory_ratio(192, cfg):.1f}× smaller, "
          f"attention rel-err {err:.3f}")


if __name__ == "__main__":
    main()
