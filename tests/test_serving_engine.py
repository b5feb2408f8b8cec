"""Continuous-batching serving engine tests.

  * parity: the slot-based continuous batcher emits every request's exact
    greedy tokens (vs a one-at-a-time static decode — no cross-request
    contamination from shared slots, ragged positions, or bucket padding),
  * mid-stream clustered-KV compaction preserves outputs within tolerance
    and keeps completions well-formed,
  * the batched (vmap over batch ⊕ head) compress_cache matches an
    explicit per-(batch, head) Python loop on identical inputs/weights,
  * incremental re-compaction conserves summary mass and advances the
    coverage frontier monotonically.
"""

import gc

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import kv_compress
from repro.core.request_cluster import Request
from repro.models import transformer as tfm
from repro.models.config import ModelConfig
from repro.runtime.kv_pool import PagedKVConfig, PoolExhausted
from repro.runtime.server import Server, ServerConfig

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab=64,
                   pad_vocab_multiple=16, dtype="float32")


@pytest.fixture(scope="module")
def pieces():
    params = tfm.init_params(jax.random.PRNGKey(0), TINY)
    rng = np.random.default_rng(0)
    reqs = [Request(i, int(l), g) for i, (l, g) in
            enumerate([(5, 4), (23, 6), (9, 3), (17, 5), (6, 1), (21, 4)])]
    prompts = {r.uid: rng.integers(0, 64, size=(r.prompt_len,)).astype(
        np.int32) for r in reqs}
    ref = Server(TINY, ServerConfig(batch_size=1, max_seq=64,
                                    engine="static",
                                    use_clustered_batching=False), params)
    ref_out = {o.uid: o.tokens for o in ref.serve(reqs, prompts)}
    return params, reqs, prompts, ref_out


class TestContinuousEngine:
    def test_exact_greedy_parity(self, pieces):
        params, reqs, prompts, ref_out = pieces
        srv = Server(TINY, ServerConfig(batch_size=2, max_seq=64), params)
        outs = srv.serve(reqs, prompts)
        assert sorted(o.uid for o in outs) == sorted(r.uid for r in reqs)
        for o in outs:
            assert o.tokens == ref_out[o.uid], o.uid
        # per-request early exit: each slot stopped at its own budget
        for o in outs:
            assert len(o.tokens) == reqs[o.uid].max_new_tokens
        assert srv.last_stats["gen_tokens"] == sum(
            r.max_new_tokens for r in reqs)

    def test_parity_independent_of_slot_count_and_bucket(self, pieces):
        params, reqs, prompts, ref_out = pieces
        srv = Server(TINY, ServerConfig(batch_size=3, max_seq=64,
                                        prefill_bucket=8,
                                        use_clustered_batching=False),
                     params)
        for o in srv.serve(reqs, prompts):
            assert o.tokens == ref_out[o.uid], o.uid

    def test_compaction_midstream_preserves_output(self, pieces):
        params, reqs, prompts, ref_out = pieces
        ccfg = kv_compress.KVCompressConfig(n_clusters=8, iters=4,
                                            keep_recent=16, refresh_every=8)
        srv = Server(TINY, ServerConfig(batch_size=2, max_seq=64,
                                        kv_compress=ccfg), params)
        outs = srv.serve(reqs, prompts)
        assert sorted(o.uid for o in outs) == sorted(r.uid for r in reqs)
        agree = []
        for o in outs:
            assert len(o.tokens) == reqs[o.uid].max_new_tokens
            assert all(0 <= t < TINY.padded_vocab for t in o.tokens)
            agree.append(np.mean(np.array(o.tokens)
                                 == np.array(ref_out[o.uid])))
        assert np.mean(agree) > 0.7, agree

    def test_sliding_window_layers_stay_exact_under_compaction(self):
        """compact_kv must never clusterize an 'L' ring buffer (only the
        leaves a clustered-mode cache holds in clustered form), and the
        engine must admit at exact prompt length for windowed models —
        bucket padding would enter the ring at wrong claimed positions."""
        cfg = ModelConfig(name="tiny-gl", family="dense", n_layers=2,
                          d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
                          d_ff=64, vocab=64, pad_vocab_multiple=16,
                          dtype="float32", layer_pattern="GL",
                          sliding_window=16)
        params = tfm.init_params(jax.random.PRNGKey(1), cfg)
        rng = np.random.default_rng(4)
        reqs = [Request(i, int(l), 6) for i, l in enumerate([30, 12, 25])]
        prompts = {r.uid: rng.integers(0, 64, size=(r.prompt_len,)).astype(
            np.int32) for r in reqs}
        ref = Server(cfg, ServerConfig(batch_size=1, max_seq=64,
                                      engine="static",
                                      use_clustered_batching=False), params)
        ref_out = {o.uid: o.tokens for o in ref.serve(reqs, prompts)}

        # exact continuous serving: parity must hold despite prefill_bucket
        # (the engine forces bucket 1 for windowed models)
        srv_e = Server(cfg, ServerConfig(batch_size=2, max_seq=64,
                                         prefill_bucket=16), params)
        for o in srv_e.serve(reqs, prompts):
            assert o.tokens == ref_out[o.uid], o.uid

        ccfg = kv_compress.KVCompressConfig(n_clusters=4, iters=2,
                                            keep_recent=8, refresh_every=4)
        srv = Server(cfg, ServerConfig(batch_size=2, max_seq=64,
                                       kv_compress=ccfg), params)
        outs = srv.serve(reqs, prompts)
        assert sorted(o.uid for o in outs) == [0, 1, 2]
        for o in outs:
            assert len(o.tokens) == 6
            assert all(0 <= t < cfg.padded_vocab for t in o.tokens)

    def test_refresh_interval_validated(self, pieces):
        params = pieces[0]
        ccfg = kv_compress.KVCompressConfig(keep_recent=16, refresh_every=0)
        with pytest.raises(ValueError, match="refresh_every"):
            Server(TINY, ServerConfig(kv_compress=ccfg), params)


class TestChunkedPrefill:
    """Chunked prefill interleaved with decode: admission streams the
    prompt through mixed-mode decode steps instead of a blocking prefill
    call — greedy outputs must stay token-identical to the blocking path
    on the exact-KV engine (same math, different schedule)."""

    @pytest.mark.parametrize("chunk", [4, 16])
    def test_token_identical_to_blocking(self, pieces, chunk):
        params, reqs, prompts, ref_out = pieces
        srv = Server(TINY, ServerConfig(batch_size=2, max_seq=64,
                                        prefill_chunk=chunk), params)
        outs = srv.serve(reqs, prompts)
        assert sorted(o.uid for o in outs) == sorted(r.uid for r in reqs)
        for o in outs:
            assert o.tokens == ref_out[o.uid], o.uid
        st = srv.last_stats
        assert st["prefill_chunks"] > 0
        assert st["prefill_pad_frac"] == 0.0      # exact positions, no pad
        assert st["ttft_p95_ms"] > 0 and st["itl_p50_ms"] >= 0

    def test_clustered_short_prompts_token_identical(self, pieces):
        """Prompts that fit the tail ring admit loss-free in both modes
        (tail-only form == streamed ring writes), so even the clustered
        engine stays token-identical while no absorb is needed."""
        params, reqs, prompts, ref_out = pieces
        ccfg = kv_compress.KVCompressConfig(n_clusters=8, iters=4,
                                            keep_recent=32, refresh_every=4)
        ref = Server(TINY, ServerConfig(batch_size=2, max_seq=64,
                                        kv_compress=ccfg), params)
        ref_c = {o.uid: o.tokens for o in ref.serve(reqs, prompts)}
        srv = Server(TINY, ServerConfig(batch_size=2, max_seq=64,
                                        kv_compress=ccfg, prefill_chunk=8),
                     params)
        for o in srv.serve(reqs, prompts):
            assert o.tokens == ref_c[o.uid], o.uid
        assert srv.last_stats["kv_absorbs"] == 0.0

    def test_long_prompt_streams_through_absorb(self, pieces):
        """A prompt longer than the tail ring must be admitted in
        clustered form via absorb_chunk (compaction-aware admission) and
        still decode sanely, agreeing with the blocking clustered path."""
        params = pieces[0]
        rng = np.random.default_rng(9)
        reqs = [Request(i, int(l), g) for i, (l, g) in
                enumerate([(60, 6), (9, 4), (48, 5)])]
        prompts = {r.uid: rng.integers(0, 64, size=(r.prompt_len,)).astype(
            np.int32) for r in reqs}
        ccfg = kv_compress.KVCompressConfig(n_clusters=8, iters=4,
                                            keep_recent=16, refresh_every=8)
        ref = Server(TINY, ServerConfig(batch_size=2, max_seq=64,
                                        kv_compress=ccfg), params)
        ref_out = {o.uid: o.tokens for o in ref.serve(reqs, prompts)}
        srv = Server(TINY, ServerConfig(batch_size=2, max_seq=64,
                                        kv_compress=ccfg, prefill_chunk=8),
                     params)
        outs = srv.serve(reqs, prompts)
        assert srv.last_stats["kv_absorbs"] > 0
        agree = []
        for o in outs:
            assert len(o.tokens) == reqs[o.uid].max_new_tokens
            assert all(0 <= t < TINY.padded_vocab for t in o.tokens)
            agree.append(np.mean(np.array(o.tokens)
                                 == np.array(ref_out[o.uid])))
        # streamed absorption vs whole-prompt batch k-medians differ only
        # in centroid placement; greedy tokens should rarely flip
        assert np.mean(agree) > 0.7, agree

    def test_rejects_unsupported_models(self, pieces):
        """The gate is per-(layer, kind) now: sliding-window 'L' layers
        serve chunked (WindowRetention) and recurrent 'M'/'R' layers
        serve as checkpointed fixed-size state (RecurrentRetention), so
        rejection happens only for state no family covers — and the
        diagnostic names each offending layer index and its kind."""
        params = pieces[0]
        import dataclasses as dc
        # 'L' without sliding_window has no window to retire behind
        gl = dc.replace(TINY, layer_pattern="GL")
        with pytest.raises(ValueError, match="without sliding_window"):
            Server(gl, ServerConfig(prefill_chunk=8), params)
        # recurrent sub-layers are a supported family now: the gate must
        # NOT fire for a 'GR' pattern (the serve itself is pinned in
        # TestRecurrentServing)
        gr = dc.replace(TINY, layer_pattern="GR", lru_width=32)
        assert gr.serving_gate_report() is None
        Server(gr, ServerConfig(prefill_chunk=8), params)
        ccfg = kv_compress.KVCompressConfig(keep_recent=8, refresh_every=4)
        with pytest.raises(ValueError, match="keep_recent"):
            Server(TINY, ServerConfig(prefill_chunk=16, kv_compress=ccfg),
                   params)

    def test_gate_report_enumerates_every_gap(self):
        """Regression: the report used to stop at the first blocking
        layer — a mixed config's diagnostics must name EVERY unsupported
        (layer, kind) pair at once, alongside any config-level gaps."""
        import dataclasses as dc
        # windowless 'L' at layers 1, 3, 5 — all three must be named
        gl = dc.replace(TINY, n_layers=6, layer_pattern="GL")
        report = gl.serving_gate_report()
        for i in (1, 3, 5):
            assert f"layer {i}: local attention without sliding_window" \
                in report, report
        # unknown kind + windowless 'L' together: both enumerated, with
        # per-layer indices and the closing statement of the rule
        weird = dc.replace(TINY, n_layers=4, layer_pattern="GLXG")
        report = weird.serving_gate_report()
        assert "layer 1: local attention without sliding_window" in report
        assert "layer 2: unknown kind 'X' has no layer-state family" \
            in report
        assert "recurrent-state layers" in report
        # config-level gaps (MLA) combine with per-layer gaps in one pass
        mla = dc.replace(TINY, n_layers=2, layer_pattern="GL",
                         attn_kind="mla")
        report = mla.serving_gate_report()
        assert "latent KV" in report
        assert "layer 1: local attention without sliding_window" in report
        # supported kinds never appear as problems
        ok = dc.replace(TINY, layer_pattern="GL", sliding_window=8)
        assert ok.serving_gate_report() is None


class TestBucketedLaunch:
    """Bucketed decode launches: the drain tail shrinks the physical
    batch (powers of two per data shard) without changing outputs."""

    def test_drain_shrinks_launch_and_keeps_tokens(self, pieces):
        params, _, _, _ = pieces
        rng = np.random.default_rng(4)
        # one straggler keeps decoding long after the others exit, so the
        # drain walks the bucket down to 1 slot
        reqs = [Request(0, 9, 40)] + [
            Request(i, int(rng.integers(5, 20)), 3) for i in range(1, 6)]
        prompts = {r.uid: rng.integers(0, 64, size=(r.prompt_len,)).astype(
            np.int32) for r in reqs}
        ref = Server(TINY, ServerConfig(batch_size=1, max_seq=64,
                                        engine="static",
                                        use_clustered_batching=False),
                     params)
        ref_out = {o.uid: o.tokens for o in ref.serve(reqs, prompts)}
        srv = Server(TINY, ServerConfig(batch_size=4, max_seq=64), params)
        outs = srv.serve(reqs, prompts)
        st = srv.last_stats
        assert st["launch_rows_frac"] < 1.0, st
        assert st["launch_bucket_mean"] < 4.0
        for o in outs:
            assert o.tokens == ref_out[o.uid], o.uid

    def test_uniform_occupancy_never_shrinks(self, pieces):
        params = pieces[0]
        rng = np.random.default_rng(8)
        # identical budgets on a full batch: every slot is busy until the
        # same final step, so no launch is ever smaller than the batch
        reqs = [Request(i, 7, 5) for i in range(2)]
        prompts = {r.uid: rng.integers(0, 64, size=(7,)).astype(np.int32)
                   for r in reqs}
        srv = Server(TINY, ServerConfig(batch_size=2, max_seq=64), params)
        srv.serve(reqs, prompts)
        assert srv.last_stats["launch_rows_frac"] == 1.0


class TestPagedEngine:
    """Paged clustered-KV memory manager: block-pool tail rings behind
    per-slot block tables, decoded via packed ragged launches.  The paged
    engine must emit greedy tokens BIT-IDENTICAL to the dense clustered
    engine (same ccfg, same queue) — the pool only changes where tail
    bytes live, and the packed kernel reproduces the dense kernel's math
    exactly — across blocking and chunked admission, with mid-stream
    compaction and streaming absorbs in play."""

    CCFG = kv_compress.KVCompressConfig(n_clusters=8, iters=4,
                                        keep_recent=16, refresh_every=8)
    PG = PagedKVConfig(block_size=4)

    @staticmethod
    def _stream(seed=9):
        rng = np.random.default_rng(seed)
        # long prompts (> keep_recent → absorbs under chunked admission)
        # and long budgets (> refresh_every → mid-stream compactions)
        reqs = [Request(i, int(l), g) for i, (l, g) in
                enumerate([(60, 12), (9, 10), (48, 9), (21, 14)])]
        prompts = {r.uid: rng.integers(0, 64, size=(r.prompt_len,)).astype(
            np.int32) for r in reqs}
        return reqs, prompts

    @pytest.mark.parametrize("chunk", [0, 8])
    def test_token_identical_to_dense(self, pieces, chunk):
        params = pieces[0]
        reqs, prompts = self._stream()
        dense = Server(TINY, ServerConfig(batch_size=2, max_seq=96,
                                          kv_compress=self.CCFG,
                                          prefill_chunk=chunk), params)
        ref = {o.uid: o.tokens for o in dense.serve(reqs, prompts)}
        srv = Server(TINY, ServerConfig(batch_size=2, max_seq=96,
                                        kv_compress=self.CCFG,
                                        prefill_chunk=chunk, paged=self.PG),
                     params)
        outs = srv.serve(reqs, prompts)
        for o in outs:
            assert o.tokens == ref[o.uid], o.uid
        st = srv.last_stats
        assert st["kv_compactions"] > 0       # the paths really diverged
        if chunk:
            assert st["kv_absorbs"] > 0
        # every block recycled once the stream drains
        assert st["pool_blocks_end"] == 0.0
        assert 0.0 < st["pool_occupancy_peak"] <= 1.0

    def test_packed_launch_beats_dense_padding(self, pieces):
        """Mixed prefill+decode compute ∝ real tokens: the packed ragged
        launch must waste strictly less padded compute than the dense
        bucketed launch on the same chunked stream, at identical
        tokens."""
        params = pieces[0]
        reqs, prompts = self._stream()
        dense = Server(TINY, ServerConfig(batch_size=2, max_seq=96,
                                          kv_compress=self.CCFG,
                                          prefill_chunk=8), params)
        dense.serve(reqs, prompts)
        srv = Server(TINY, ServerConfig(batch_size=2, max_seq=96,
                                        kv_compress=self.CCFG,
                                        prefill_chunk=8, paged=self.PG),
                     params)
        srv.serve(reqs, prompts)
        assert (srv.last_stats["launch_pad_frac"]
                < dense.last_stats["launch_pad_frac"])
        assert srv.last_stats["launch_ragged_frac"] > \
            dense.last_stats["launch_ragged_frac"]
        # the pool never allocates beyond the dense ring (it may touch it
        # transiently when every slot is at full depth at a compaction
        # boundary), and allocation tracks live tokens tighter than the
        # always-full dense ring does
        assert (srv.last_stats["kv_bytes_peak_per_shard"]
                <= dense.last_stats["kv_bytes_peak_per_shard"])
        assert srv.last_stats["kv_frag"] < dense.last_stats["kv_frag"]

    def test_blocks_recycle_and_reallocate(self, pieces):
        """Compaction give-back and slot recycling really return blocks:
        total allocations exceed the peak simultaneously live (blocks
        were freed and handed out again), and the pool drains to zero."""
        params = pieces[0]
        reqs, prompts = self._stream()
        srv = Server(TINY, ServerConfig(batch_size=2, max_seq=96,
                                        kv_compress=self.CCFG,
                                        prefill_chunk=8, paged=self.PG),
                     params)
        srv.serve(reqs, prompts)
        st = srv.last_stats
        assert st["pool_allocs"] > st["pool_blocks_peak"]
        assert st["pool_frees"] == st["pool_allocs"]      # all returned

    @pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
    def test_engine_cache_freed_when_serve_returns(self, pieces, paged):
        """No device buffer of a serve outlives it by reference count
        alone: one kept by a reference cycle lives until the cyclic
        collector runs, and then the next serve allocates its engine
        cache beside it (on the chip, the device memory peak grows by a
        whole cache)."""
        params = pieces[0]
        reqs, prompts = self._stream()
        srv = Server(TINY, ServerConfig(
            batch_size=2, max_seq=96, kv_compress=self.CCFG,
            prefill_chunk=8, paged=self.PG if paged else None), params)
        srv.serve(reqs, prompts)                # programs built, caches set
        gc.collect()
        before = {id(a) for a in jax.live_arrays()}
        gc.disable()
        try:
            srv.serve(reqs, prompts)
            left = [a.shape for a in jax.live_arrays()
                    if id(a) not in before]
        finally:
            gc.enable()
        assert left == []

    def test_oversubscribed_pool_serves_short_streams(self, pieces):
        """A pool smaller than slots × blocks-per-slot still serves when
        live windows stay short (blocks map lazily, only live positions
        hold storage); a pool too small for even serialized live windows
        on a deep stream still raises PoolExhausted — but only at
        genuine zero forward progress (every slot stalled, nothing
        reclaimable), after admission deferral and per-slot write stalls
        have been exhausted."""
        params = pieces[0]
        rng = np.random.default_rng(3)
        # every request's final depth <= 8 positions -> <= 2 live blocks
        # per slot, so 5 blocks serve 2 slots that would dense-allocate 8
        short = [Request(i, int(l), g) for i, (l, g) in
                 enumerate([(5, 3), (4, 2), (6, 2), (5, 3), (4, 2)])]
        sp = {r.uid: rng.integers(0, 64, size=(r.prompt_len,)).astype(
            np.int32) for r in short}
        dense = Server(TINY, ServerConfig(batch_size=2, max_seq=96,
                                          kv_compress=self.CCFG), params)
        ref = {o.uid: o.tokens for o in dense.serve(short, sp)}
        srv = Server(TINY, ServerConfig(
            batch_size=2, max_seq=96, kv_compress=self.CCFG,
            paged=PagedKVConfig(block_size=4, pool_blocks=5)), params)
        for o in srv.serve(short, sp):
            assert o.tokens == ref[o.uid], o.uid
        assert srv.last_stats["pool_occupancy_peak"] <= 1.0
        reqs, prompts = self._stream()
        with pytest.raises(PoolExhausted):
            tight = Server(TINY, ServerConfig(
                batch_size=2, max_seq=96, kv_compress=self.CCFG,
                paged=PagedKVConfig(block_size=4, pool_blocks=4)), params)
            tight.serve(reqs, prompts)

    def test_validation(self, pieces):
        params = pieces[0]
        # paged WITHOUT kv_compress is legal now (QuotaRetention exact
        # KV) but whole blocks must tile the full sequence depth
        with pytest.raises(ValueError, match="max_seq"):
            Server(TINY, ServerConfig(
                max_seq=30, paged=PagedKVConfig(block_size=4)), params)
        with pytest.raises(ValueError, match="block_size"):
            Server(TINY, ServerConfig(
                kv_compress=self.CCFG,
                paged=PagedKVConfig(block_size=5)), params)
        # per-layer gate: MLA latent caches have no retention policy
        import dataclasses as dc
        mla = dc.replace(TINY, attn_kind="mla")
        with pytest.raises(ValueError, match="latent KV"):
            Server(mla, ServerConfig(kv_compress=self.CCFG, paged=self.PG),
                   params)


GLWIN = ModelConfig(name="tiny-gl", family="dense", n_layers=2, d_model=32,
                    n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab=64,
                    pad_vocab_multiple=16, dtype="float32",
                    layer_pattern="GL", sliding_window=16)


class TestWindowedServing:
    """Sliding-window models under the retention-policy layer: 'L' layers
    retire behind WindowRetention while 'G' layers stay clustered behind
    FrontierRetention — chunked admission (dense AND paged) must emit
    greedy tokens BIT-IDENTICAL to blocking dense admission, because the
    staged per-layer ring writes never evict an in-window entry."""

    # prompts fit the tail ring (loss-free admission in both modes) but
    # exceed the 16-token window, and budgets push positions past
    # keep_recent so compactions advance the 'G' frontier mid-decode
    CCFG = kv_compress.KVCompressConfig(n_clusters=4, iters=2,
                                        keep_recent=32, refresh_every=4)

    @staticmethod
    def _stream(seed=13):
        rng = np.random.default_rng(seed)
        reqs = [Request(i, int(l), g) for i, (l, g) in
                enumerate([(26, 10), (12, 6), (20, 8), (8, 5)])]
        prompts = {r.uid: rng.integers(0, 64, size=(r.prompt_len,)).astype(
            np.int32) for r in reqs}
        return reqs, prompts

    @pytest.fixture(scope="class")
    def win_pieces(self):
        params = tfm.init_params(jax.random.PRNGKey(7), GLWIN)
        reqs, prompts = self._stream()
        ref = Server(GLWIN, ServerConfig(batch_size=2, max_seq=64,
                                         kv_compress=self.CCFG), params)
        ref_out = {o.uid: o.tokens for o in ref.serve(reqs, prompts)}
        assert ref.last_stats["kv_retired_window"] > 0
        return params, reqs, prompts, ref_out

    def test_chunked_dense_token_identical_to_blocking(self, win_pieces):
        params, reqs, prompts, ref_out = win_pieces
        srv = Server(GLWIN, ServerConfig(batch_size=2, max_seq=64,
                                         kv_compress=self.CCFG,
                                         prefill_chunk=8), params)
        outs = srv.serve(reqs, prompts)
        assert sorted(o.uid for o in outs) == sorted(r.uid for r in reqs)
        for o in outs:
            assert o.tokens == ref_out[o.uid], o.uid
        st = srv.last_stats
        # both policies really retired entries: windows slid past 16
        # positions and compactions advanced the clustered frontier
        assert st["kv_retired_window"] > 0
        assert st["kv_retired_frontier"] > 0
        assert st["prefill_chunks"] > 0

    def test_chunked_paged_token_identical_to_blocking(self, win_pieces):
        params, reqs, prompts, ref_out = win_pieces
        srv = Server(GLWIN, ServerConfig(
            batch_size=2, max_seq=64, kv_compress=self.CCFG,
            prefill_chunk=8, paged=PagedKVConfig(block_size=4)), params)
        outs = srv.serve(reqs, prompts)
        for o in outs:
            assert o.tokens == ref_out[o.uid], o.uid
        st = srv.last_stats
        assert st["kv_retired_window"] > 0
        assert st["kv_retired_frontier"] > 0
        assert st["pool_blocks_end"] == 0.0


class TestQuotaRetention:
    """Paged serving WITHOUT kv_compress: exact KV under QuotaRetention.
    Admission reserves the request's whole block budget up front
    (admitted => completable), nothing retires mid-flight, and blocks
    return only at request exit — so an oversubscribed pool defers
    admissions instead of raising PoolExhausted, at greedy tokens
    identical to the dense exact engine."""

    def test_exact_paged_oversubscribed_burst(self, pieces):
        params, reqs, prompts, ref_out = pieces
        # 8 blocks < the 13-block peak two full requests would need
        # concurrently: the second admission must defer until the first
        # exits, yet every request still completes with exact tokens
        srv = Server(TINY, ServerConfig(
            batch_size=2, max_seq=64,
            paged=PagedKVConfig(block_size=4, pool_blocks=8)), params)
        outs = srv.serve(reqs, prompts)
        assert sorted(o.uid for o in outs) == sorted(r.uid for r in reqs)
        for o in outs:
            assert o.tokens == ref_out[o.uid], o.uid
        st = srv.last_stats
        assert st["kv_retired_quota"] > 0
        assert st["kv_retired_frontier"] == 0.0   # nothing clustered
        assert st["pool_blocks_end"] == 0.0
        assert st["pool_occupancy_peak"] <= 1.0

    def test_chunked_quota_admission(self, pieces):
        params, reqs, prompts, ref_out = pieces
        srv = Server(TINY, ServerConfig(
            batch_size=2, max_seq=64, prefill_chunk=8,
            paged=PagedKVConfig(block_size=4, pool_blocks=8)), params)
        outs = srv.serve(reqs, prompts)
        for o in outs:
            assert o.tokens == ref_out[o.uid], o.uid
        assert srv.last_stats["kv_retired_quota"] > 0
        assert srv.last_stats["pool_blocks_end"] == 0.0


class TestPrefixSharing:
    """Prefix-shared paged admission (ServerConfig.prefix_share): chunked
    admissions register prefix-pure state (tail blocks + absorbed
    centroids + frontier) at chunk boundaries; later same-prefix requests
    adopt the blocks (copy-on-write) and restore the state.  Greedy
    tokens must be BIT-IDENTICAL to unshared paged serving — the reused
    state is exactly what the unshared run recomputes from the same
    prefix tokens, and per-slot compaction cadence + the
    recompact_clustered no-advance gate make every slot's stream
    schedule-independent."""

    PG = PagedKVConfig(block_size=4)

    @staticmethod
    def _template_stream(n=6, tpl_len=40, seed=5):
        """Bursty templated traffic: one shared template + short unique
        suffixes, everything queued at t0."""
        rng = np.random.default_rng(seed)
        template = rng.integers(0, 64, size=(tpl_len,)).astype(np.int32)
        reqs, prompts = [], {}
        for i in range(n):
            sfx = rng.integers(0, 64,
                               size=(int(rng.integers(3, 9)),)).astype(
                                   np.int32)
            prompts[i] = np.concatenate([template, sfx])
            reqs.append(Request(i, len(prompts[i]),
                                int(rng.integers(6, 12))))
        return reqs, prompts

    # refresh 8: compactions fire mid-stream (token budgets reach 11);
    # refresh 12: no slot ever hits the cadence — the ± compaction pair
    @pytest.mark.parametrize("refresh", [8, 12])
    def test_token_identical_to_unshared(self, pieces, refresh):
        from repro.runtime.prefix_cache import PrefixShareConfig
        params = pieces[0]
        reqs, prompts = self._template_stream()
        ccfg = kv_compress.KVCompressConfig(n_clusters=8, iters=4,
                                            keep_recent=16,
                                            refresh_every=refresh)
        base = Server(TINY, ServerConfig(batch_size=2, max_seq=96,
                                         kv_compress=ccfg, prefill_chunk=8,
                                         paged=self.PG), params)
        ref = {o.uid: o.tokens for o in base.serve(reqs, prompts)}
        srv = Server(TINY, ServerConfig(batch_size=2, max_seq=96,
                                        kv_compress=ccfg, prefill_chunk=8,
                                        paged=self.PG,
                                        prefix_share=PrefixShareConfig()),
                     params)
        outs = srv.serve(reqs, prompts)
        for o in outs:
            assert o.tokens == ref[o.uid], o.uid
        st = srv.last_stats
        # sharing really happened: admissions hit the cache, skipped
        # feeding prefix chunks, shared physical blocks, and COW fired
        # when divergent suffixes wrote into shared blocks
        assert st["prefix_hits"] > 0
        assert st["prefix_tokens_reused"] > 0
        assert st["kv_shared_blocks"] > 0 and st["kv_bytes_saved"] > 0
        # skipped prefix chunks = less prompt compute than unshared
        assert st["prefill_chunks"] < base.last_stats["prefill_chunks"]
        # every shared/retained block released at drain
        assert st["pool_blocks_end"] == 0.0
        if refresh == 8:
            assert st["kv_compactions"] > 0
            # divergent suffixes wrote into shared blocks → COW fired
            # (at refresh 12 the live window is too short for writes to
            # reach retained blocks, so sharing never needs a copy)
            assert st["pool_cow"] > 0

    def test_long_suffixes_still_hit_the_template_entry(self, pieces):
        """Suffixes LONGER than a chunk: each stream registers chunk
        boundaries inside its own unique suffix, but the pure-template
        boundary entry must survive (shorter prefixes are never evicted
        by longer registrations of the same stream) so every later
        same-template request still hits it — tokens bit-identical to
        unshared throughout."""
        from repro.runtime.prefix_cache import PrefixShareConfig
        params = pieces[0]
        rng = np.random.default_rng(11)
        template = rng.integers(0, 64, size=(24,)).astype(np.int32)
        reqs, prompts = [], {}
        for i in range(5):
            sfx = rng.integers(0, 64, size=(int(rng.integers(10, 21)),))
            prompts[i] = np.concatenate([template, sfx]).astype(np.int32)
            reqs.append(Request(i, len(prompts[i]), 5))
        ccfg = kv_compress.KVCompressConfig(n_clusters=8, iters=4,
                                            keep_recent=16,
                                            refresh_every=12)
        base = Server(TINY, ServerConfig(batch_size=2, max_seq=96,
                                         kv_compress=ccfg, prefill_chunk=8,
                                         paged=self.PG), params)
        ref = {o.uid: o.tokens for o in base.serve(reqs, prompts)}
        srv = Server(TINY, ServerConfig(batch_size=2, max_seq=96,
                                        kv_compress=ccfg, prefill_chunk=8,
                                        paged=self.PG,
                                        prefix_share=PrefixShareConfig()),
                     params)
        outs = srv.serve(reqs, prompts)
        for o in outs:
            assert o.tokens == ref[o.uid], o.uid
        # at least every request after the first shares the 24-token
        # template (3 chunks): the template boundary stays registered
        # even as each stream registers suffix-contaminated boundaries
        st = srv.last_stats
        assert st["prefix_hits"] >= len(reqs) - 2
        assert st["prefix_tokens_reused"] >= 24 * (len(reqs) - 2)
        assert st["pool_blocks_end"] == 0.0

    def test_oversubscribed_burst_defers_instead_of_raising(self, pieces):
        """Regression (PoolExhausted mid-serve used to kill the whole
        batch): an oversubscribed pool + burst completes — admissions
        defer back to the queue and ring writes stall their slot until
        the compaction give-back — with tokens STILL bit-identical to
        the dense engine (stalls delay slots, but per-slot cadence keeps
        every slot's stream a function of its own tokens)."""
        params = pieces[0]
        reqs, prompts = TestPagedEngine._stream()
        ccfg = TestPagedEngine.CCFG
        for chunk in (8, 0):
            dense = Server(TINY, ServerConfig(batch_size=2, max_seq=96,
                                              kv_compress=ccfg,
                                              prefill_chunk=chunk), params)
            ref = {o.uid: o.tokens for o in dense.serve(reqs, prompts)}
            srv = Server(TINY, ServerConfig(
                batch_size=2, max_seq=96, kv_compress=ccfg,
                prefill_chunk=chunk,
                paged=PagedKVConfig(block_size=4, pool_blocks=7)), params)
            outs = srv.serve(reqs, prompts)       # must not raise
            for o in outs:
                assert o.tokens == ref[o.uid], (chunk, o.uid)
            assert srv.last_stats["pool_blocks_end"] == 0.0

    def test_validation(self, pieces):
        from repro.runtime.prefix_cache import PrefixShareConfig
        params = pieces[0]
        ccfg = TestPagedEngine.CCFG
        with pytest.raises(ValueError, match="prefix_share"):
            Server(TINY, ServerConfig(batch_size=2, max_seq=96,
                                      kv_compress=ccfg, prefill_chunk=8,
                                      prefix_share=PrefixShareConfig()),
                   params)
        with pytest.raises(ValueError, match="prefix_share"):
            Server(TINY, ServerConfig(batch_size=2, max_seq=96,
                                      kv_compress=ccfg, paged=self.PG,
                                      prefix_share=PrefixShareConfig()),
                   params)


class TestBatchedCompress:
    def test_matches_per_head_loop(self):
        rng = np.random.default_rng(1)
        B, S, H, Dh = 2, 96, 2, 16
        k = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
        lengths = jnp.asarray([96, 80], jnp.int32)
        cfg = kv_compress.KVCompressConfig(n_clusters=8, iters=4,
                                           keep_recent=16, refresh_every=8)
        cc = kv_compress.compress_cache_batched(k, v, lengths, cfg)
        np.testing.assert_array_equal(np.asarray(cc["cov"]), [88, 72])
        for b in range(B):
            cov_b = int(np.asarray(cc["cov"])[b])
            w_b = (jnp.arange(S) < cov_b).astype(jnp.float32)
            for h in range(H):
                kc, vc, cnt = kv_compress.compress_head(
                    k[b, :, h], v[b, :, h], cfg, weights=w_b)
                np.testing.assert_allclose(
                    np.asarray(cc["k_cents"][b, :, h]), np.asarray(kc),
                    rtol=1e-4, atol=1e-4)
                np.testing.assert_allclose(
                    np.asarray(cc["v_cents"][b, :, h]), np.asarray(vc),
                    rtol=1e-4, atol=1e-4)
                np.testing.assert_allclose(
                    np.asarray(cc["counts"][b, :, h]), np.asarray(cnt),
                    rtol=1e-4, atol=1e-4)

    def test_tail_ring_layout(self):
        rng = np.random.default_rng(2)
        S, H, Dh = 64, 1, 8
        k = jnp.asarray(rng.normal(size=(1, S, H, Dh)), jnp.float32)
        cfg = kv_compress.KVCompressConfig(n_clusters=4, iters=2,
                                           keep_recent=8, refresh_every=4)
        cc = kv_compress.compress_cache_batched(
            k, k, jnp.asarray([50]), cfg)
        # position p lives at ring slot p % R: check position 47 (slot 7)
        np.testing.assert_allclose(np.asarray(cc["k_tail"][0, 47 % 8, 0]),
                                   np.asarray(k[0, 47, 0]), rtol=1e-6)

    def test_recompact_conserves_and_advances(self):
        rng = np.random.default_rng(3)
        B, S, H, Dh = 2, 96, 2, 16
        k = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
        lengths = jnp.asarray([96, 80], jnp.int32)
        cfg = kv_compress.KVCompressConfig(n_clusters=8, iters=4,
                                           keep_recent=16, refresh_every=8)
        cc = kv_compress.compress_cache_batched(k, v, lengths, cfg)
        cc2 = kv_compress.recompact_clustered(cc, lengths + 8, cfg)
        cov1, cov2 = np.asarray(cc["cov"]), np.asarray(cc2["cov"])
        assert (cov2 >= cov1).all()
        # total summarized mass == number of covered positions, per slot
        m1 = np.asarray(cc["counts"]).sum(axis=(1, 2))
        m2 = np.asarray(cc2["counts"]).sum(axis=(1, 2))
        h = np.asarray(cc["counts"]).shape[2]
        np.testing.assert_allclose(m1, cov1 * h, rtol=1e-5)
        np.testing.assert_allclose(m2, cov2 * h, rtol=1e-5)


# ---------------------------------------------------------------------------
# Recurrent-state families: mamba2-style ('M') and RG-LRU ('R') layers
# serving through the same chunked/paged continuous engine.  The exit pin
# for the layer-state refactor: greedy tokens bit-identical to a blocking
# one-request-at-a-time static decode, because (a) sequential recurrent
# prefill replays exactly the decode step and (b) per-slot recurrent
# state is advanced/checkpointed with slot-local math only.
# ---------------------------------------------------------------------------

from repro.models.config import SSMConfig  # noqa: E402

GM_REC = ModelConfig(name="gm", family="hybrid", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                     vocab=64, pad_vocab_multiple=16, dtype="float32",
                     layer_pattern="GM",
                     ssm=SSMConfig(d_state=16, d_conv=4, expand=2,
                                   head_dim=32, n_groups=1, chunk=32))
GR_REC = ModelConfig(name="gr", family="hybrid", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                     vocab=64, pad_vocab_multiple=16, dtype="float32",
                     layer_pattern="GR", lru_width=64)
M_PURE = ModelConfig(name="m", family="ssm", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                     vocab=64, pad_vocab_multiple=16, dtype="float32",
                     layer_pattern="M",
                     ssm=SSMConfig(d_state=16, d_conv=4, expand=2,
                                   head_dim=32, n_groups=1, chunk=32))


def _rec_stream(vocab=64, seed=9):
    rng = np.random.default_rng(seed)
    reqs = [Request(i, int(l), g) for i, (l, g) in
            enumerate([(60, 12), (9, 10), (48, 9), (21, 14)])]
    prompts = {r.uid: rng.integers(0, vocab, size=(r.prompt_len,)).astype(
        np.int32) for r in reqs}
    return reqs, prompts


@pytest.fixture(scope="module", params=["GM", "GR"], ids=["gm", "gr"])
def rec_pieces(request):
    cfg = {"GM": GM_REC, "GR": GR_REC}[request.param]
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    reqs, prompts = _rec_stream()
    ref = Server(cfg, ServerConfig(batch_size=1, max_seq=96,
                                   engine="static",
                                   use_clustered_batching=False), params)
    ref_out = {o.uid: o.tokens for o in ref.serve(reqs, prompts)}
    return cfg, params, reqs, prompts, ref_out


class TestRecurrentServing:
    CCFG = kv_compress.KVCompressConfig(n_clusters=8, iters=4,
                                        keep_recent=16, refresh_every=8)

    def test_chunked_dense_bit_identical(self, rec_pieces):
        cfg, params, reqs, prompts, ref_out = rec_pieces
        srv = Server(cfg, ServerConfig(batch_size=2, max_seq=96,
                                       kv_compress=self.CCFG,
                                       prefill_chunk=8), params)
        for o in srv.serve(reqs, prompts):
            assert o.tokens == ref_out[o.uid], f"uid {o.uid} diverged"

    def test_chunked_paged_bit_identical(self, rec_pieces):
        cfg, params, reqs, prompts, ref_out = rec_pieces
        srv = Server(cfg, ServerConfig(batch_size=2, max_seq=96,
                                       kv_compress=self.CCFG,
                                       prefill_chunk=8,
                                       paged=PagedKVConfig(block_size=4)),
                     params)
        for o in srv.serve(reqs, prompts):
            assert o.tokens == ref_out[o.uid], f"uid {o.uid} diverged"
        st = srv.last_stats
        # both families are priced and visible in the metrics surface
        assert st["state_bytes_recurrent"] > 0
        assert st["state_bytes_ring"] > 0
        # recurrent state never retires — the counter exists and stays 0
        assert st["kv_retired_recurrent"] == 0
        assert st["pool_blocks_end"] == 0

    def test_preempt_swap_resume_bit_identical(self, rec_pieces):
        """One preempt→host-swap→resume cycle through recurrent state:
        the snapshot carries the (conv, ssm)/(conv, h) leaves whole, the
        swap-bytes ledger prices them, and restored requests finish with
        exactly the tokens of an unpressured run."""
        from repro.runtime.scheduler import SLOConfig
        cfg, params, reqs, prompts, ref_out = rec_pieces
        rng = np.random.default_rng(3)
        reqs, prompts = [], {}
        for i in range(8):
            plen = int(rng.integers(6, 30))
            prompts[i] = rng.integers(0, 64, size=(plen,)).astype(np.int32)
            reqs.append(Request(i, plen, int(rng.integers(6, 14)),
                                priority=1 if i >= 5 else 0))
        big = Server(cfg, ServerConfig(
            batch_size=2, max_seq=96, kv_compress=self.CCFG,
            prefill_chunk=8,
            paged=PagedKVConfig(block_size=4, pool_blocks=48),
            use_clustered_batching=False), params)
        want = {o.uid: o.tokens for o in big.serve(reqs, prompts)}
        tight = Server(cfg, ServerConfig(
            batch_size=2, max_seq=96, kv_compress=self.CCFG,
            prefill_chunk=8,
            paged=PagedKVConfig(block_size=4, pool_blocks=10),
            use_clustered_batching=False,
            # arrival-order admission: the late high-priority tail can
            # only run by preempting a resident best-effort request
            scheduler=SLOConfig(priority_admission=False)), params)
        outs = tight.serve(reqs, prompts)
        st = tight.last_stats
        assert st["sched_preemptions"] >= 1
        assert st["sched_swaps_in"] >= 1
        assert st["sched_swap_bytes"] == 0  # ledger drains to zero
        shed = {o.uid for o in outs if o.shed}
        for o in outs:
            if o.uid not in shed:
                assert o.tokens == want[o.uid], f"uid {o.uid} diverged"

    def test_pure_recurrent_dense_chunked(self):
        """An attention-free pattern (no ring layers at all) still
        serves chunked dense — the engine no longer assumes a KV ring
        exists anywhere."""
        params = tfm.init_params(jax.random.PRNGKey(0), M_PURE)
        reqs, prompts = _rec_stream()
        ref = Server(M_PURE, ServerConfig(batch_size=1, max_seq=96,
                                          engine="static",
                                          use_clustered_batching=False),
                     params)
        ref_out = {o.uid: o.tokens for o in ref.serve(reqs, prompts)}
        srv = Server(M_PURE, ServerConfig(batch_size=2, max_seq=96,
                                          prefill_chunk=8), params)
        for o in srv.serve(reqs, prompts):
            assert o.tokens == ref_out[o.uid], f"uid {o.uid} diverged"

    def test_pure_recurrent_paged_rejected(self):
        """Recurrent state is never pool-backed, so a pure-recurrent
        pattern has nothing to page — the gate must say so."""
        params = tfm.init_params(jax.random.PRNGKey(0), M_PURE)
        with pytest.raises(ValueError, match="ring-family"):
            Server(M_PURE, ServerConfig(batch_size=2, max_seq=96,
                                        kv_compress=self.CCFG,
                                        prefill_chunk=8,
                                        paged=PagedKVConfig(block_size=4)),
                   params)
