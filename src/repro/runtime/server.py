"""Serving runtime: continuous-batching engine with device-resident
clustered-KV compaction (the paper's "memory management and request
processing" made concrete).

Request processing: requests arrive with (prompt_len, max_new_tokens); the
batcher clusters them (core/request_cluster.py) into a padding-minimal
admission order; a slot-based continuous batcher then admits a request the
moment a decode slot frees (per-slot position/length tracking, early exit
at each request's own max_new_tokens) instead of padding every request in
a static batch to the longest member.

Admission runs in one of two modes:

  * **chunked, decode-interleaved prefill** (``prefill_chunk > 0``): each
    engine step consumes one prompt chunk for at most one admitting slot
    per data shard, fused into the same launch that advances every decode
    slot by one token (mixed-mode ``decode_step`` / Pallas
    ``clustered_decode``), so admission never stalls decode and the
    prompt's KV streams straight into the already-sharded engine cache —
    in clustered form via ``kv_compress.absorb_chunk`` when the prompt
    outgrows the tail ring (compaction-aware admission with a prompt-time
    centroid budget).  No blocking prefill, no bucket padding, no B=1
    cache replication.
  * **blocking prefill** (``prefill_chunk == 0``, the baseline): a full
    right-padded prefill call per admission, then a donated slot-write.

Memory management: the clustered-KV cache is compressed/refreshed with one
jitted, vmap-over-(batch ⊕ head) call (core/kv_compress.py) — no host
loops — and decode attention over [centroids ⊕ tail ring] runs in the
fused Pallas ``clustered_decode`` kernel (interpret-mode on CPU).
Compaction runs on a **per-slot cadence**: a slot is refreshed after
``refresh_every`` of its own decode tokens, and slots whose frontier
does not move keep their summaries bit-identical (gated in
``recompact_clustered``) — each slot's state is a function of its own
token stream alone, independent of neighbours' admission timing.

Prefix sharing (``ServerConfig.prefix_share``, paged + chunked only):
admission hashes prompt prefixes at chunk boundaries into a per-data-
shard prefix cache (runtime/prefix_cache.py); a matching request adopts
the registered tail-ring pool blocks (ref-counted, copy-on-write at the
first divergent write via ``kv_pool.ensure``) and restores the absorbed
prompt centroids + coverage frontier, resuming admission mid-prompt with
greedy tokens bit-identical to unshared paged serving.

Pool pressure never kills the batch: an admission that cannot get its
blocks is deferred back to the queue, a slot whose ring write cannot be
backed stalls for the step (its packed row is simply not launched) and
retries after the next compaction give-back or prefix-cache eviction;
``PoolExhausted`` only surfaces when zero forward progress is possible.

Decode launches are **bucketed** per data shard: the physical cache holds
``shards × bucket`` slots where the bucket shrinks (powers of two) on the
end-of-stream drain — once the queue is empty and no prefill is in
flight — so a near-empty shard stops paying for dead slots.  Dead slot
content is dropped on shrink (finished requests hold no live state);
every new serve starts back at the full shape, and all admissions happen
at the full shape, so the admission traces exist at exactly one batch
size (``ensure_row`` is a defensive re-grow valve should that policy
ever change).
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import math
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import kv_compress
from repro.core import layer_state
from repro.core import retention
from repro.core.request_cluster import BatchPlan, Request, plan_batches, plan_fifo
from repro.models import attention as attn
from repro.models import transformer as tfm
from repro.models.config import ModelConfig
from repro.runtime import kv_pool
from repro.runtime import prefix_cache as prefix_mod
from repro.runtime import template_store as template_mod
from repro.runtime.scheduler import SLOConfig, SLOScheduler, SwapRecord
from repro.runtime import telemetry as tele_mod
from repro.runtime.telemetry import TelemetryConfig
from repro.sharding import (Rules, constrain_cache, default_table,
                            place_admission, place_block_tables,
                            place_prefix_snapshot, place_swap_payload,
                            serving_param_shardings, shard_cache,
                            use_rules)
from repro.sharding.rules import _key_str as _key_name


@dataclasses.dataclass
class ServerConfig:
    batch_size: int = 4            # decode slots
    max_seq: int = 256
    use_clustered_batching: bool = True
    n_request_clusters: int = 4
    greedy: bool = True
    engine: str = "continuous"     # "continuous" | "static"
    prefill_bucket: int = 16       # admission prompts are right-padded to a
                                   # multiple of this (bounds jit retraces;
                                   # causal masking keeps logits exact for
                                   # global attention / clustered KV; models
                                   # with sliding-window 'L' layers or SSM/
                                   # RG-LRU state should use 1 — pad tokens
                                   # enter the ring/recurrent state there).
                                   # Blocking admission only.
    prefill_chunk: int = 0         # >0: chunked prefill interleaved with
                                   # decode — each engine step feeds one
                                   # prompt chunk of this many tokens for at
                                   # most one admitting slot per data shard,
                                   # fused with the decode launch.  Exact
                                   # positions, so no bucket padding.
                                   # Covers both layer-state families
                                   # (G/L ring-KV layers and M/R
                                   # recurrent-state layers — see
                                   # core/layer_state.py); must be
                                   # <= kv_compress.keep_recent when
                                   # serving clustered.
    kv_compress: Optional[kv_compress.KVCompressConfig] = None
    # when set, the engine serves from a clustered KV cache end to end and
    # re-compacts every kv_compress.refresh decode steps
    paged: Optional[kv_pool.PagedKVConfig] = None
    # paged clustered-KV memory manager: the exact tail rings live in a
    # per-shard block pool (block_size positions per block, pool_blocks
    # blocks per data shard) behind per-slot block tables — blocks are
    # allocated on admission / right before the write that needs them,
    # recycled on request exit, and returned mid-stream once compaction
    # covers them (runtime/kv_pool.py).  Decode runs as PACKED ragged
    # launches: one row per real (slot, position) pair instead of
    # slots × chunk, so mixed prefill+decode compute scales with real
    # tokens.  Requires kv_compress (the clustered path is what paging
    # replaces); greedy outputs are token-identical to the dense engine.
    prefix_share: Optional[prefix_mod.PrefixShareConfig] = None
    # prefix-sharing paged admission: prompts are hashed at chunk
    # boundaries into a per-data-shard prefix cache
    # (runtime/prefix_cache.py); a new request whose prompt matches a
    # registered prefix adopts the matching tail-ring pool blocks
    # (ref-counted, copy-on-write at the first divergent write) and
    # restores the absorbed prompt centroids + coverage frontier instead
    # of re-prefilling — greedy tokens stay bit-identical to unshared
    # paged serving while shared-prefix bursts skip most prompt chunks
    # (TTFT) and share tail blocks (KV bytes).  Requires ``paged`` +
    # ``prefill_chunk``.
    template_store: Optional[object] = None
    # persistent cross-serve template store (runtime/template_store.py):
    # a TemplateStoreConfig (the server owns a private store) or a
    # TemplateStore instance (shareable across servers; epoch stamping
    # invalidates it whenever the model/KV config/pool it was warmed
    # against changes).  Subsumes ``prefix_share`` — same block-adopting
    # admission fast path, but entries and their pinned pool blocks
    # survive between serve() calls, eviction is hit-scored instead of
    # LRU, and incoming traffic is clustered online for steering.  The
    # end-of-serve pool invariant becomes
    # ``allocated() == store.pinned_blocks()`` (reported as
    # ``pool_blocks_end == 0`` after subtracting the pins); use
    # ``Server.invalidate_templates()`` to drain the pins explicitly.
    scheduler: Optional[SLOConfig] = None
    # SLO-aware scheduling (runtime/scheduler.py): requests carry
    # priorities/deadlines (Request.priority / .deadline_ms); under slot
    # or pool pressure the engine preempts the cheapest lower-priority
    # in-flight slot — its clustered snapshot + mapped tail blocks swap
    # to host memory and the blocks return to the pool — and re-admits
    # it mid-stream bit-identically when capacity returns.  Best-effort
    # load is deferred/shed to protect the high class's TTFT; the
    # brownout ladder (defer → preempt → swap-in → shed) runs ahead of
    # PoolExhausted, which then only fires when all remaining work is
    # the protected class.  Requires the paged clustered engine
    # (kv_compress= + paged=, all-'G' layers).
    telemetry: Optional[TelemetryConfig] = None
    # serving telemetry (runtime/telemetry.py): last_stats is always
    # regenerated from the typed metrics registry; telemetry.trace
    # additionally records host-side request-lifecycle spans and
    # engine-step events into Server.last_trace (exportable as JSONL or
    # Chrome trace JSON via Server.export_trace — loadable in Perfetto).
    # Tracing never runs inside jit and never touches device state, so
    # greedy tokens are bit-identical with tracing on vs off.
    mesh: Optional[Mesh] = None
    # (data, model) device mesh (launch/mesh.make_serving_mesh): decode
    # slots + their KV caches partition over "data", attention heads (and
    # the fused Pallas clustered_decode grid) over "model".  Model code
    # stays mesh-free — sharding/rules.py logical-axis annotations resolve
    # against this mesh during tracing, and a shard_map island dispatches
    # the Pallas kernel per model shard.  None = single-device engine.


@dataclasses.dataclass
class Completion:
    uid: int
    tokens: List[int]
    prefill_ms: float              # wall-clock time to first token (TTFT)
    decode_ms: float
    shed: bool = False             # dropped by SLO brownout: tokens are
                                   # partial (or empty if never admitted)


def _is_exact_kv(node) -> bool:
    return (isinstance(node, dict) and "k" in node and "v" in node
            and "k_scale" not in node)


def _is_clustered_kv(node) -> bool:
    return isinstance(node, dict) and "k_cents" in node


def _pow2ceil(x: int) -> int:
    return 1 << max(x - 1, 0).bit_length()


def _slot_resize(x, axis: int, shards: int, ob: int, nb: int):
    """Resize one cache leaf's slot axis from shards*ob to shards*nb rows,
    keeping each data shard's block contiguous (slice drops dead high
    slots; pad appends zero slots).  Reshape-based so a NamedSharding
    over the slot axis stays shard-local."""
    lead, rest = x.shape[:axis], x.shape[axis + 1:]
    xr = x.reshape(lead + (shards, ob) + rest)
    if nb < ob:
        xr = jax.lax.slice_in_dim(xr, 0, nb, axis=axis + 1)
    elif nb > ob:
        pad = [(0, 0)] * xr.ndim
        pad[axis + 1] = (0, nb - ob)
        xr = jnp.pad(xr, pad)
    return xr.reshape(lead + (shards * nb,) + rest)


def _greedy(logits):
    """Greedy tokens of one launch and its count of non-finite logits,
    fetched to host together."""
    nxt, bad = jax.device_get((jnp.argmax(logits, -1),
                               jnp.sum(~jnp.isfinite(logits))))
    return np.asarray(nxt).astype(np.int32), int(bad)


def _frontier_advances(due, pos, fr) -> bool:
    """Whether a compaction pass over the ``due`` slots folds anything:
    some due slot's coverage frontier moves on the host mirror (the
    formula ``recompact_clustered`` applies on the device).  A pass in
    which none moves hands every row back bit-identical
    (``changed = new_cov > cov``), so the engine skips its launch."""
    return any(fr.target(int(pos[j])) > fr.frontier(j) for j in due)


def _percentile_ms(vals: List[float], q: float) -> float:
    if not vals:
        return 0.0
    return float(np.percentile(np.asarray(vals), q) * 1e3)


class Server:
    def __init__(self, cfg: ModelConfig, scfg: ServerConfig, params):
        self.cfg = cfg
        self.scfg = scfg
        if scfg.kv_compress is not None:
            if scfg.engine != "continuous":
                raise ValueError(
                    "kv_compress serving requires the continuous engine "
                    "(the static path would silently ignore it)")
            if scfg.kv_compress.refresh < 1:
                raise ValueError(
                    "continuous serving with kv_compress needs "
                    "refresh_every >= 1 (ring entries must reach "
                    "centroids before eviction)")
        self._paged = scfg.paged
        if self._paged is not None:
            if scfg.engine != "continuous":
                raise ValueError("paged serving requires the continuous "
                                 "engine")
            if scfg.kv_compress is not None:
                if scfg.kv_compress.keep_recent % self._paged.block_size:
                    raise ValueError(
                        f"block_size {self._paged.block_size} must divide "
                        f"keep_recent {scfg.kv_compress.keep_recent} (ring "
                        "offsets map to whole blocks)")
            elif scfg.max_seq % self._paged.block_size:
                raise ValueError(
                    f"block_size {self._paged.block_size} must divide "
                    f"max_seq {scfg.max_seq}: paged serving without "
                    "kv_compress is exact-KV under QuotaRetention — the "
                    "full sequence is backed by whole blocks reserved as "
                    "a per-slot budget at admission")
            report = cfg.serving_gate_report()
            if report is not None:
                raise ValueError("paged serving: " + report)
            if not layer_state.families_for(cfg).has_ring:
                raise ValueError(
                    "paged serving needs at least one ring-family layer: "
                    "recurrent-state layers ('M'/'R') carry fixed-size "
                    "per-slot state that is never pool-backed, so a "
                    "pure-recurrent pattern has nothing to page — serve "
                    "dense chunked instead (prefill_chunk= without paged=)")
        # paged without kv_compress = exact-KV serving under a block
        # quota (core/retention.QuotaRetention): the cache keeps the
        # clustered LAYOUT (one permanently-dead centroid, counts == 0 ⇒
        # masked) with a full-depth tail ring, cov pinned at 0 so every
        # position stays exact, and blocks retire only at request exit
        self._kv_layout = scfg.kv_compress
        if self._paged is not None and scfg.kv_compress is None:
            self._kv_layout = kv_compress.KVCompressConfig(
                n_clusters=1, keep_recent=scfg.max_seq, refresh_every=0)
        self._pshare = scfg.prefix_share
        self._store: Optional[template_mod.TemplateStore] = None
        if scfg.template_store is not None:
            if self._pshare is not None:
                raise ValueError(
                    "template_store subsumes prefix_share (same adopting "
                    "admission path, persistent entries) — set only one")
            ts = scfg.template_store
            self._store = (ts if isinstance(ts, template_mod.TemplateStore)
                           else template_mod.TemplateStore(ts))
            self._pshare = self._store.share
        if self._pshare is not None:
            if (self._paged is None or not scfg.prefill_chunk
                    or scfg.kv_compress is None
                    or set(cfg.layer_pattern) - set("GMR")):
                raise ValueError(
                    "prefix_share/template_store requires the paged "
                    "clustered engine with chunked prefill over snapshot-"
                    "coverable layers ('G' clustered rings plus 'M'/'R' "
                    "recurrent state; 'L' window rings are not in "
                    "snapshots) — kv_compress= + paged= + prefill_chunk=: "
                    "block-granular sharing needs the block pool's ref "
                    "counts, slot snapshots restore clustered summaries "
                    "and recurrent state only, and prefix-pure "
                    "registration points only exist on the chunked "
                    "admission schedule")
        self._slo = scfg.scheduler
        if self._slo is not None:
            if (self._paged is None or scfg.kv_compress is None
                    or set(cfg.layer_pattern) - set("GMR")
                    or scfg.engine != "continuous"):
                raise ValueError(
                    "scheduler= (SLO-aware preemption) requires the "
                    "paged clustered continuous engine over snapshot-"
                    "coverable layers ('G' clustered rings plus 'M'/'R' "
                    "recurrent state; 'L' window rings are not in "
                    "snapshots) — kv_compress= + paged=: swap snapshots "
                    "restore clustered summaries and recurrent state "
                    "only, and preemption frees pool blocks — the dense "
                    "and exact engines have nothing to swap")
        self._chunk = scfg.prefill_chunk
        if self._chunk:
            if scfg.engine != "continuous":
                raise ValueError("chunked prefill requires the continuous "
                                 "engine")
            report = cfg.serving_gate_report()
            if report is not None:
                raise ValueError("chunked prefill: " + report)
            if (scfg.kv_compress is not None
                    and self._chunk > scfg.kv_compress.keep_recent):
                raise ValueError(
                    "prefill_chunk must fit the exact tail ring "
                    "(<= kv_compress.keep_recent): a chunk's K/V lands in "
                    "the ring before absorb_chunk can cover it")
        self._rules: Optional[Rules] = None
        self._n_data_shards = 1
        if scfg.mesh is not None:
            if scfg.engine != "continuous":
                raise ValueError("mesh serving requires the continuous "
                                 "engine (static batches are per-device)")
            mesh = scfg.mesh
            self._rules = Rules(mesh, default_table("pod" in mesh.axis_names))
            # param placement: MoE routed-expert banks distribute over
            # the model axis (serving_param_specs — the one family of
            # leaves whose replication cost dominates); everything else
            # replicates and the annotate/shard_map islands shard the
            # per-head compute, GSPMD propagation does the rest
            params = jax.device_put(params,
                                    serving_param_shardings(params, mesh))
            axes = self._rules.axes_for("batch", scfg.batch_size)
            if axes:
                self._n_data_shards = math.prod(
                    mesh.shape[a] for a in axes)
        self.params = params
        self.last_stats: Dict[str, float] = {}
        # typed metrics registry + lifecycle tracer: last_stats is a
        # flat view regenerated from the registry at the end of every
        # serve, so per-serve dynamic keys (template_cluster*,
        # slot_waste_shard*, sched_*) from a previous serve or mesh
        # shape can never leak into the next serve's stats
        self.metrics = tele_mod.MetricsRegistry()
        self._tele = scfg.telemetry or TelemetryConfig()
        self.tracer = (tele_mod.Tracer(self._tele.max_events)
                       if self._tele.trace else None)
        self.last_trace: List[dict] = []
        # host spans on the profiler clock (telemetry.TRACE_NAMES)
        self._annot = (tele_mod.annotation if self._tele.jax_profiler
                       else tele_mod.no_annotation)
        # programs built from here on: programs_built_total and
        # program_build_s_total count from this mark
        self._builds = tele_mod.program_builds()
        self._builds0 = (self._builds.n, self._builds.seconds)
        # cross-serve template persistence: the pool (host tables/refs)
        # and the device engine cache that carry the store's pinned
        # blocks between serve() calls.  The config epoch stamps every
        # input a registered snapshot depends on — a store rebound under
        # a different model/KV config/geometry or different weight BYTES
        # invalidates instead of adopting stale state.  The weight stamp
        # is a content hash, not object identity, so reloaded identical
        # params (a new pytree with the same bytes) keep a warm store.
        # Only a store reads it: hashing copies every weight byte to host.
        self._tmpl_pool: Optional[kv_pool.BlockPool] = None
        self._tmpl_cache = None
        self._store_epoch = (repr(cfg), repr(scfg.kv_compress),
                             repr(scfg.paged), scfg.prefill_chunk,
                             scfg.max_seq, scfg.batch_size,
                             self._n_data_shards,
                             self._params_digest(self.params)
                             if self._store is not None else None)
        # layer-state families (core/layer_state.py): which state each
        # layer carries per slot — ring-KV ('G'/'L', retention-governed)
        # vs fixed-size recurrent state ('M'/'R', checkpointed whole).
        # None = the pattern has kinds outside both families; every
        # engine path that consults families has already been rejected
        # by a gate for such configs.
        try:
            self._families = layer_state.families_for(cfg)
        except ValueError:
            self._families = None
        self._has_recurrent = (self._families is not None
                               and self._families.has_recurrent)
        # bucket-padded prefill is only exact for global attention (causal
        # mask + masked decode); sliding-window rings and SSM/RG-LRU state
        # absorb pad tokens, so those models admit at exact prompt length
        self._bucket = (1 if set(cfg.layer_pattern) & set("LMR")
                        else scfg.prefill_bucket)
        self._compact_templates: Dict[tuple, object] = {}
        self._resize_jits: Dict[tuple, object] = {}

        def _ctx():
            return (use_rules(self._rules) if self._rules is not None
                    else contextlib.nullcontext())

        # the step functions take the weights as an argument: a jitted
        # closure over them would embed every weight byte in the program
        # as a constant
        def _decode_fn(params, c, tk, t):
            with _ctx():
                logits, c2 = tfm.decode_step(params, cfg, c, tk, t)
                return logits, self._constrain(c2)

        def _mixed_fn(params, c, tk, t, cl):
            with _ctx():
                logits, c2 = tfm.decode_step(params, cfg, c, tk, t,
                                             chunk_len=cl)
                return logits, self._constrain(c2)

        def _prefill_fn(params, tk, lp):
            with _ctx():
                # recurrent layers prefill SEQUENTIALLY when served: the
                # parallel scan forms (ssd_chunked / associative scan)
                # are mathematically equal but not bitwise equal to
                # stepwise decode, and serving pins chunked/paged tokens
                # bit-identical to blocking one-at-a-time decode
                return tfm.prefill(params, cfg, tk,
                                   max_seq=scfg.max_seq, last_pos=lp,
                                   recurrent_mode=("sequential"
                                                   if self._has_recurrent
                                                   else "scan"))

        def _write_slot_fn(dst, src, j):
            with _ctx():
                return self._constrain(self._write_slot_impl(dst, src, j))

        def _reset_slot_fn(c, j):
            with _ctx():
                return self._constrain(self._reset_slot_impl(c, j))

        self._decode = jax.jit(_decode_fn)
        self._mixed = jax.jit(_mixed_fn)
        self._prefill = jax.jit(_prefill_fn)
        # donate the engine cache: admission updates one slot in place
        # instead of copying every layer's KV
        self._write_slot = jax.jit(_write_slot_fn, donate_argnums=(0,))
        self._reset_slot = jax.jit(_reset_slot_fn, donate_argnums=(0,))
        ccfg = scfg.kv_compress

        def _absorb_fn(c, j, lengths, target):
            with _ctx():
                return self._constrain(
                    self._absorb_impl(c, j, lengths, target, ccfg))

        self._absorb = jax.jit(_absorb_fn, donate_argnums=(0,))

        if self._paged is not None:
            blk = self._paged.block_size

            def _packed_fn(params, c, tk, rs, rp, rtw, rcidx, bt, width):
                with _ctx():
                    logits, c2, *routes = tfm.decode_step_packed(
                        params, cfg, c, tk, rs, rp, rtw, rcidx, bt,
                        block_size=blk, width=width)
                    return (logits, self._constrain(c2), *routes)

            def _write_slot_paged_fn(dst, src, j, bt_row):
                with _ctx():
                    return self._constrain(
                        self._write_slot_paged_impl(dst, src, j, bt_row,
                                                    blk))

            def _absorb_paged_fn(c, j, lengths, target, bt_row):
                with _ctx():
                    return self._constrain(self._absorb_paged_impl(
                        c, j, lengths, target, bt_row, ccfg))

            def _compact_paged_fn(c, lengths, bt):
                with _ctx():
                    return self._constrain(
                        self._compact_paged_impl(c, lengths, bt, ccfg))

            def _snap_fn(c, j):
                with _ctx():
                    return tfm.clustered_slot_state(c, j)

            def _restore_fn(c, snap, j):
                with _ctx():
                    return self._constrain(
                        tfm.restore_clustered_slot_state(c, snap, j))

            def _cow_fn(c, src, dst):
                with _ctx():
                    return self._constrain(self._cow_impl(c, src, dst))

            def _swap_out_fn(c, j, bt_row):
                with _ctx():
                    return (tfm.clustered_slot_state(c, j),
                            self._gather_swap_tails(c, bt_row))

            def _swap_in_fn(c, snap, tails, j, bt_row):
                with _ctx():
                    c2 = tfm.restore_clustered_slot_state(c, snap, j)
                    return self._constrain(
                        self._scatter_swap_tails(c2, tails, bt_row))

            # ``width`` (max chunk index + 1, sequencing sliding-window
            # ring commits) is static: exactly two traces — the mixed
            # shape (width = prefill_chunk) and pure decode (width = 1)
            self._decode_packed = jax.jit(_packed_fn, donate_argnums=(1,),
                                          static_argnums=(8,))
            self._write_slot_paged = jax.jit(_write_slot_paged_fn,
                                             donate_argnums=(0,))
            self._absorb_paged = jax.jit(_absorb_paged_fn,
                                         donate_argnums=(0,))
            self._compact_paged = jax.jit(_compact_paged_fn,
                                          donate_argnums=(0,))
            self._snap_slot = jax.jit(_snap_fn)
            self._restore_slot_state = jax.jit(_restore_fn,
                                               donate_argnums=(0,))
            self._cow = jax.jit(_cow_fn, donate_argnums=(0,))
            # preemption swap: out gathers one slot's clustered snapshot
            # plus its full tail-ring block row (the host keeps only the
            # mapped blocks' bytes meaningful; unmapped rows gather the
            # shard-base alias garbage the masks already exclude); in
            # restores the snapshot and scatters ONLY freshly-allocated
            # blocks back (re-adopted blocks may be shared — writing
            # them, even with identical bytes, would break the COW
            # protocol — and their payloads are provably unchanged)
            self._swap_out = jax.jit(_swap_out_fn)
            self._swap_in = jax.jit(_swap_in_fn, donate_argnums=(0,))

    def _constrain(self, cache):
        """Pin engine-cache leaves to their mesh layout inside traced fns
        (slots over data, kv heads over model) so decode/admission outputs
        keep stable shardings across steps."""
        if self._rules is None:
            return cache
        return constrain_cache(cache, self._rules)

    # ------------------------------------------------------------------
    # entry
    # ------------------------------------------------------------------

    def serve(self, requests: Sequence[Request],
              prompts: Dict[int, np.ndarray]) -> List[Completion]:
        """prompts: uid -> token array.  Returns completions per request."""
        with self._annot("serve"):
            if self.scfg.engine != "continuous":
                return self._serve_static(requests, prompts)
            with self._builds.watch(self._trace_build
                                    if self.tracer is not None else None):
                return self._serve_continuous(requests, prompts)

    def _trace_build(self, fun_name: str, seconds: float) -> None:
        """A program built inside a traced serve: which step recompiled."""
        self.tracer.event("program_built", tid="engine", fun_name=fun_name,
                          compile_s=seconds)

    def export_trace(self, path: str, fmt: str = "chrome") -> None:
        """Write the last serve's lifecycle trace (requires
        ``ServerConfig.telemetry.trace``): ``fmt="chrome"`` emits Chrome
        trace-event JSON loadable in Perfetto (one process per data
        shard, spans nested under slot threads, last_stats embedded for
        offline reconciliation); ``fmt="jsonl"`` emits the raw event
        log, one JSON object per line."""
        if fmt == "chrome":
            tele_mod.write_chrome_trace(self.last_trace, path,
                                        n_shards=self._n_data_shards,
                                        stats=self.last_stats)
        elif fmt == "jsonl":
            tele_mod.write_jsonl(
                self.last_trace, path,
                meta={"n_shards": self._n_data_shards,
                      "last_stats": {k: float(v)
                                     for k, v in self.last_stats.items()}})
        else:
            raise ValueError(f"unknown trace format {fmt!r} "
                             "(expected 'chrome' or 'jsonl')")

    def invalidate_templates(self) -> None:
        """Explicitly drop every persistent template entry, releasing
        the pool blocks the store pinned across serves — afterwards the
        pool is fully drained (``allocated() == 0``; there are no other
        block holders between serves).  The warmed device cache is
        dropped too: with no pins its template payloads are unreachable
        and the next serve starts cold."""
        if self._store is not None:
            self._store.invalidate()
        if self._tmpl_pool is not None:
            assert self._tmpl_pool.allocated() == 0, \
                "template pins released but pool still holds blocks"
        self._tmpl_pool = None
        self._tmpl_cache = None

    def _plan(self, requests: Sequence[Request]) -> BatchPlan:
        scfg = self.scfg
        if scfg.use_clustered_batching:
            return plan_batches(requests, scfg.batch_size,
                                scfg.n_request_clusters)
        return plan_fifo(requests, scfg.batch_size)

    # ------------------------------------------------------------------
    # continuous-batching engine
    # ------------------------------------------------------------------

    def _serve_continuous(self, requests, prompts) -> List[Completion]:
        cfg, scfg = self.cfg, self.scfg
        if cfg.is_encdec:
            raise NotImplementedError(
                "continuous engine serves decoder-only models")
        t0_serve = time.perf_counter()
        # per-serve registry window: every non-persist metric from the
        # previous serve (including dynamic per-cluster / per-shard /
        # sched_* keys) is dropped here; lifetime *_total metrics survive
        reg = self.metrics
        reg.begin_serve()
        tr = self.tracer
        _annot = self._annot
        spans = tele_mod.StepSpans(_annot)
        builds0 = (self._builds.n, self._builds.seconds)
        ccfg = scfg.kv_compress
        # the cache LAYOUT (clustered leaves + tail ring geometry) is
        # distinct from the retention policy served on top of it: ccfg ⇒
        # FrontierRetention, paged-sans-ccfg ⇒ QuotaRetention over the
        # same leaf shapes with a full-depth ring
        layout = self._kv_layout
        chunk = self._chunk
        n = scfg.batch_size
        plan = self._plan(requests)
        order = [u for b in plan.batches for u in b]
        by_uid = {r.uid: r for r in requests}
        if (self.scfg.scheduler is not None
                and self.scfg.scheduler.priority_admission):
            # admission control: the protected class admits ahead of
            # best-effort work regardless of queue position (stable
            # within a class, so the batcher's padding-minimal order
            # survives inside each class).  Tokens are unaffected —
            # per-slot state is a function of the slot's own stream —
            # only who waits.
            order.sort(key=lambda uid: -by_uid[uid].priority)

        # data-shard bookkeeping: NamedSharding partitions the slot axis
        # contiguously, so logical slot j lives on data shard
        # j // per_shard at within-shard index j % per_shard.  The cache
        # physically holds shards * bucket rows (bucketed launches):
        # logical j maps to physical row shard*bucket + idx, valid while
        # idx < bucket.  Admission fills the emptiest shard's lowest index
        # first, keeping buckets tight; a drained shard's dead high slots
        # are sliced away (their content is dead state).
        shards = self._n_data_shards
        per_shard = max(n // max(shards, 1), 1)
        bucket = per_shard
        shard_of = lambda j: min(j // per_shard, shards - 1)  # noqa: E731
        idx_of = lambda j: j % per_shard                      # noqa: E731

        def phys(j):
            return shard_of(j) * bucket + idx_of(j)

        if tr is not None:
            tr.begin_serve(t0_serve, max(shards, 1))
            if self._families is not None:
                # name the layer-state families this serve runs with so
                # offline trace consumers can segment span populations
                # (swap_out spans carry state_bytes, engine steps advance
                # recurrent state inside the same launch) by family mix
                tr.event("state_families", tid="engine", t=t0_serve,
                         ring="".join(sorted(self._families.ring.kinds)),
                         recurrent="".join(
                             sorted(self._families.recurrent.kinds)))
            for qpos, quid in enumerate(order):
                qr = by_uid[quid]
                tr.event("queued", tid="queue", uid=quid, t=t0_serve,
                         queue_pos=qpos, priority=qr.priority,
                         prompt_len=qr.prompt_len)

        # paged memory manager: tail rings live in a per-shard block pool
        # behind per-slot block tables; the launch bucket never shrinks
        # (packed rows already make compute ∝ real tokens, so the slot
        # axis stays at one traced shape)
        paged = self._paged
        pool = None
        pcache = None
        cache = None
        store = self._store
        if paged is not None:
            parked = store.parked if store is not None else None
            if (parked is not None and parked[2] == self._store_epoch
                    and parked[3] == max(shards, 1)):
                # warm cross-serve start: the parked pool and device
                # cache carry the store's pinned template blocks.  The
                # canonical copy lives on the STORE keyed by epoch, so
                # a different Server instance under the same epoch
                # (weights content-hashed — a reloaded identical pytree
                # counts) adopts it too.  Ownership is taken eagerly
                # (the slot is nulled) so a serve that dies mid-flight
                # can never leave a half-donated cache behind — the
                # next serve comes up cold and bind() invalidates the
                # orphaned entries.
                pool, cache = parked[0], parked[1]
                store.parked = None
                self._tmpl_pool = self._tmpl_cache = None
                pool.reset_peaks()
            else:
                pool = kv_pool.BlockPool(n, layout.keep_recent, paged,
                                         n_shards=max(shards, 1),
                                         slots_per_shard=per_shard,
                                         full_tail_resident=ccfg is not None)
            if store is not None:
                # epoch-checked attach: a store warmed under any other
                # config/model/pool is invalidated here, never adopted
                store.bind(self._store_epoch, max(shards, 1), pool)
                pcache = store
            elif self._pshare is not None:
                pcache = prefix_mod.PrefixCache(self._pshare,
                                                max(shards, 1), pool)
        if cache is None:
            cache = tfm.init_cache(
                cfg, n, scfg.max_seq,
                kv_mode="clustered" if layout else "exact",
                kv_clusters=layout.n_clusters if layout else 512,
                kv_tail=layout.keep_recent if layout else 256,
                kv_pool_blocks=pool.n_blocks if pool else 0,
                kv_block_size=paged.block_size if paged else 0)
            if self._rules is not None:
                # slot state becomes mesh-sharded arrays: slots over the
                # data axis, kv heads over model (divisibility-aware per
                # leaf; the paged pool's block axis shards over data
                # like slots)
                cache = shard_cache(cache, self._rules)
        # per-serve stats are deltas against these marks: a persistent
        # store carries lifetime hit/alloc counters across serves, and
        # reporting the raw totals would double-count every serve after
        # the first (the lifetime view stays available as template_*)
        hits0 = pcache.hits if pcache is not None else 0
        reused0 = pcache.tokens_reused if pcache is not None else 0
        pool_mark = ((pool.n_allocs, pool.n_frees, pool.n_retains,
                      pool.n_cow) if pool is not None else (0, 0, 0, 0))
        # SLO scheduler: one per serve — the swap backlog never outlives
        # the request stream (every parked request resumes or sheds
        # before the serve returns), so cross-serve template state is
        # untouched by preemption
        slo_cfg = self._slo
        slo = SLOScheduler(slo_cfg, n) if slo_cfg is not None else None

        pos = np.zeros(n, np.int32)       # cache valid length per slot
        cur = np.zeros(n, np.int32)       # pending (unfed) token per slot
        active = np.zeros(n, bool)        # decoding
        admitting = np.zeros(n, bool)     # chunked prefill in flight
        fed = np.zeros(n, np.int32)       # prompt tokens streamed so far
        # retention policies — WHAT each layer's cache retains, decoupled
        # from where the bytes live (core/retention.py):
        #   fr     'G' layers, clustered: retire behind the coverage
        #          frontier (owns the host cov mirror, kept in lockstep
        #          with the device cov by replaying the same formulas)
        #   quota  'G' layers, exact paged: retire nothing mid-flight;
        #          a per-slot block budget reserved at admission
        #   wr     'L' layers: retire behind the sliding window (virtual
        #          — the dense ring overwrite reclaims storage — but it
        #          drives the kv_retired_window accounting)
        #   rr     'M'/'R' layers: fixed-size recurrent state folds every
        #          position — nothing retires, a named no-op whose
        #          diagnostics keep the kv_retired_recurrent invariant
        #          explicit
        fr = (retention.FrontierRetention(n, ccfg)
              if ccfg is not None else None)
        quota = (retention.QuotaRetention(paged.block_size,
                                          pool.blocks_per_slot)
                 if pool is not None and ccfg is None else None)
        wr = (retention.WindowRetention(cfg.sliding_window, n)
              if "L" in cfg.layer_pattern and cfg.sliding_window else None)
        rr = (retention.RecurrentRetention(
                  tuple(sorted(self._families.recurrent.kinds)))
              if self._has_recurrent else None)
        sweep_policy = fr if fr is not None else quota
        cov_of = fr.frontier if fr is not None else (lambda j: 0)
        kv_retired = {"frontier": 0, "window": 0, "quota": 0}
        slot_uid = [-1] * n
        prompt_np: Dict[int, np.ndarray] = {}
        toks: Dict[int, List[int]] = {}
        pre_ms: Dict[int, float] = {}
        token_t: Dict[int, List[float]] = {}
        # queue waits: when each request first got a slot, and when the
        # launch carrying its first prompt chunk (or its blocking
        # prefill) was dispatched
        slot_t: Dict[int, float] = {}
        launch_t: Dict[int, float] = {}
        # tracer tenancy bookkeeping: one "run" span per (slot, tenancy)
        # segment — admit/resume opens it, finish/shed/preempt closes it.
        # Token deltas across a uid's segments sum to its final count, so
        # validate_trace can reconcile run spans against gen_tokens.
        seg: List[Optional[tuple]] = [None] * n

        def slot_tid(j):
            return f"slot{idx_of(j)}"

        def tr_open(j, uid, t, how, p0=0):
            if tr is None:
                return
            seg[j] = (t, how, uid, len(toks.get(uid, ())), int(p0))

        def tr_close(j, t, why):
            """Close slot j's tenancy span.  Called BEFORE the slot's
            blocks are freed so blocks_held reflects the tenancy."""
            if tr is None or seg[j] is None:
                return
            t0s, how, uid, tok0, p0 = seg[j]
            seg[j] = None
            held = (pool.mapped_blocks(j) if pool is not None else 0)
            tr.span("run", t0s, t, pid=shard_of(j), tid=slot_tid(j),
                    uid=uid, start=how, end=why,
                    tokens=len(toks.get(uid, ())) - tok0, pos0=p0,
                    pos1=int(max(int(fed[j]), int(pos[j]))),
                    blocks_held=held)

        def tr_brownout(rung, why, **args):
            """Instant event naming the brownout rung taken and WHY —
            which headroom/pool check failed, which victim was chosen."""
            if tr is not None:
                tr.event("brownout", tid="engine", rung=rung, why=why,
                         **args)

        qi = 0
        decode_steps = wasted_slots = 0
        rows_launched = 0
        pad_toks = useful_toks = 0
        n_chunks = n_absorbs = n_compacts = n_bad_logits = 0
        # compaction work: slot rows launched, slots due, due slots whose
        # frontier advanced, streams whose next gap holds a launched
        # pass, passes whose launch was skipped (nothing to fold)
        compact_rows = compact_due = compact_folded = compact_gaps = 0
        compact_skipped = 0
        # compaction cadence is per-slot decode progress, not engine
        # steps: a slot's ring only advances when that slot decodes, so
        # chunk-feed steps for OTHER slots must not inflate the schedule
        # (the eviction-safety invariant is per slot: cov >= t - R +
        # refresh after at most ``refresh`` of its own tokens)
        since_tok = np.zeros(n, np.int32)
        dec_s = 0.0
        R = layout.keep_recent if layout else 0
        shard_busy_steps = np.zeros(max(shards, 1), np.int64)
        shard_steps = 0
        # packed-launch accounting: real (slot, position) pairs fed vs
        # rows×width actually launched — the dense bucketed path pays
        # slots × chunk on mixed steps, the paged packed path only its
        # per-shard row bucket
        launch_real = launch_padded = 0
        # recurrent layers: one-token steps launched vs real rows among
        # them; MoE layers: the packed program's (held, all) route counts
        rec_steps = rec_rows = 0
        moe_routes = []
        # paged clustered attention ('G' layers): slot runs the kernel
        # staged and scored, each a maximal stretch of packed rows of
        # one slot (a chunk's rows, or one decode row)
        attn_runs = 0
        paged_attn = ("G" in cfg.layer_pattern
                      or bool(self.params.get("prefix")))
        # KV-allocation accounting (clustered serving): live ring tokens
        # vs allocated ring capacity, so paged and dense runs report
        # comparable occupancy / fragmentation / peak-bytes numbers
        kv_live_sum = kv_alloc_sum = 0
        kv_alloc_peak = 0
        # prefix sharing: peak count of extra logical block mappings —
        # blocks-worth of tail KV that sharing avoided materializing
        kv_shared_peak = 0
        tail_bpt = self._tail_bytes_per_token(cache) if layout else 0
        # recurrent-family byte price: the whole fixed-size state one
        # slot carries — constant over the stream, swapped whole, never
        # pool-backed — added to every victim's cost and swap payload
        rec_state_b = (layer_state.recurrent_state_bytes(cache, n)
                       if self._has_recurrent else 0)

        def resize_to(nb):
            nonlocal cache, bucket
            if nb == bucket:
                return
            cache = self._resize_cache(cache, bucket, nb)
            bucket = nb

        bt_cache = [None]

        def bt_device():
            """Device copy of the block table, re-uploaded only when the
            allocator mutated it since the last launch (steady-state
            decode reuses the cached array)."""
            if bt_cache[0] is None or pool.dirty:
                arr = jnp.asarray(pool.table_for_read())
                if self._rules is not None:
                    arr = place_block_tables(arr, self._rules)
                bt_cache[0] = arr
                pool.dirty = False
            return bt_cache[0]

        def occupancy():
            occ = np.zeros(max(shards, 1), np.int32)
            for j in range(n):
                if active[j] or admitting[j]:
                    occ[shard_of(j)] += 1
            return occ

        def sweep_covered(s):
            """Give back every block shard ``s``'s retention policy has
            already retired (idempotent: under FrontierRetention,
            absorb/compaction normally do this the moment ``cov``
            advances, so a sweep only recovers blocks under pool
            pressure; under QuotaRetention nothing retires mid-flight and
            the sweep is a no-op by construction).  Each slot's UPCOMING
            write blocks are protected — mid-step they may be allocated
            but not yet written (stale claims look dead), and freeing one
            would only make ``ensure`` re-allocate it and the reclaim
            loop spin."""
            freed = 0
            for j in range(n):
                if shard_of(j) != s:
                    continue
                if admitting[j]:
                    plen = len(prompt_np[slot_uid[j]])
                    cl = int(min(chunk, plen - fed[j])) if chunk else 0
                    sweep_policy.protect_write(j, kv_pool.write_blocks(
                        int(fed[j]), max(cl, 1), R, paged.block_size))
                    freed += pool.free_retired(j, int(fed[j]),
                                               sweep_policy)
                    sweep_policy.clear_protection(j)
                elif active[j]:
                    sweep_policy.protect_write(j, kv_pool.write_blocks(
                        int(pos[j]), 1, R, paged.block_size))
                    freed += pool.free_retired(j, int(pos[j]),
                                               sweep_policy)
                    sweep_policy.clear_protection(j)
            return freed

        def reclaim_all():
            """Last-resort pool reclaim: sweep every shard's covered
            blocks and drain the prefix cache entirely.  Returns the
            number of blocks freed — the zero-forward-progress raise
            paths fire only after this comes back empty twice."""
            held = pool.allocated()
            for s in range(max(shards, 1)):
                sweep_covered(s)
                while pcache is not None and pcache.evict_lru(s):
                    pass
            return held - pool.allocated()

        def try_ensure(j, blocks, pairs):
            """``pool.ensure`` with pool-pressure reclaim: on exhaustion,
            sweep covered blocks, then evict prefix-cache entries (LRU)
            — blocks pinned by the cache are an optimization, never an
            obligation — and retry.  Returns False when the shard
            genuinely cannot supply the blocks right now (the caller
            defers the slot and retries after the next compaction
            give-back instead of killing the whole batch).

            ``pairs`` MUST be the step's shared COW accumulator: a swap
            performed before a mid-list PoolExhausted is not re-emitted
            on retry (the fresh block is exclusively owned by then), so
            pairs recorded by failed attempts still need their payload
            copy this step — even when the slot ends up stalling."""
            while True:
                try:
                    pool.ensure(j, blocks, pairs)
                    return True
                except kv_pool.PoolExhausted:
                    s = shard_of(j)
                    if sweep_covered(s):
                        continue
                    if pcache is not None and pcache.evict_lru(s):
                        continue
                    return False

        def apply_cow(pairs):
            """Run the device block copies for this step's COW swaps
            (padded to a pow2 bucket with a repeated real pair so traced
            shapes stay bounded)."""
            nonlocal cache
            m = _pow2ceil(len(pairs))
            pad = pairs + [pairs[0]] * (m - len(pairs))
            src = jnp.asarray([p[0] for p in pad], jnp.int32)
            dst = jnp.asarray([p[1] for p in pad], jnp.int32)
            cache = self._cow(cache, src, dst)

        def ensure_row(j):
            """Re-grow the launch bucket so logical slot j has a physical
            row.  Under the current policy this never fires — shrink only
            happens after the queue drains and admissions only happen
            while it hasn't — but it guards the phys-row invariant if the
            shrink policy ever loosens."""
            if idx_of(j) >= bucket:
                resize_to(min(per_shard, _pow2ceil(idx_of(j) + 1)))

        # ---- SLO preemption / swap / brownout (runtime/scheduler.py) --
        # All of these run at clean step boundaries only (admission
        # phase, post-step pass, zero-progress backstops): mid-step a
        # victim's COW payload copies may not have been applied yet and
        # a swap-out gather would read uninitialized fresh blocks.
        # Victims are always ACTIVE (decoding) slots — an admitting slot
        # mid-prefill may hold an in-flight prefix-cache pin
        # (lookup→restore window), and interrupting it would break the
        # pin protocol; admitting slots use the existing defer machinery
        # instead.

        def victim_candidates(shard=None):
            """(priority, swap_cost_bytes, slot) for every active slot
            (optionally one shard's — blocks are shard-local, so pool
            pressure needs a same-shard victim).  Cheapest-first victim
            selection prices heterogeneous per-layer state: ring-family
            cost is the slot's mapped tail blocks (bytes), recurrent
            state adds its fixed per-slot byte price — for all-ring
            patterns this is a monotone transform of the old mapped-
            block count, so victim choices are unchanged."""
            out = []
            for j in range(n):
                if not active[j]:
                    continue
                if shard is not None and shard_of(j) != shard:
                    continue
                out.append((by_uid[slot_uid[j]].priority,
                            pool.mapped_blocks(j) * paged.block_size
                            * tail_bpt + rec_state_b, j))
            return out

        @tele_mod.spanned(_annot, "sched_preempt")
        def preempt(j):
            """Swap slot ``j`` out to host memory: gather its slot
            snapshot (clustered summaries + any recurrent state — the
            recurrent family's whole checkpoint rides the same opaque
            snapshot format) + tail-ring block payloads, release its
            blocks (remembering (gid, generation) for re-adoption), park
            the request on the swap backlog.  Bit-identity on resume
            comes for free: each slot's state is a deterministic function
            of its own token stream (per-slot compaction cadence), and
            the swap round-trips that state exactly."""
            nonlocal cache
            uid = slot_uid[j]
            r = by_uid[uid]
            bt_read = pool.row_for_read(j)
            t_sw0 = time.perf_counter()
            snap, tails = self._swap_out(cache, jnp.int32(phys(j)),
                                         jnp.asarray(bt_read))
            snap, tails = jax.device_get((snap, tails))
            held = pool.release_slot(j)
            rec = SwapRecord(
                uid=uid, priority=r.priority, pos=int(pos[j]),
                cur=int(cur[j]), fed=int(fed[j]),
                since_tok=int(since_tok[j]), cov=int(cov_of(j)),
                max_new_tokens=r.max_new_tokens,
                deadline_ms=r.deadline_ms, held=held, snap=snap,
                tails=tails, epoch=self._store_epoch, seq=0,
                n_blocks_swapped=len(held), state_bytes=rec_state_b)
            slo.record_swap(rec)
            slo.swap_bytes += (len(held) * paged.block_size * tail_bpt
                               + rec_state_b)
            if tr is not None:
                t_now = time.perf_counter()
                tr.span("swap_out", t_sw0, t_now, pid=shard_of(j),
                        tid=slot_tid(j), uid=uid, blocks=len(held),
                        pos=int(pos[j]), state_bytes=rec_state_b)
                tr_close(j, t_now, "preempt")
            active[j] = False
            slot_uid[j] = -1
            since_tok[j] = 0
            return rec

        @tele_mod.spanned(_annot, "sched_resume")
        def resume_swapped(j, rec) -> bool:
            """Re-admit a parked request mid-stream into slot ``j``
            (possibly a different slot/shard than it was preempted from
            — the host payload is slot-agnostic).  Blocks that stayed
            live with an unchanged generation re-adopt without a
            re-upload; the rest re-allocate and scatter back from the
            host copy.  False = the pool cannot back it right now
            (caller defers the resume, nothing half-restored)."""
            nonlocal cache
            assert rec.epoch == self._store_epoch, (
                "swap record from another config epoch — a parked "
                "request cannot outlive the serve that preempted it")
            # headroom gate: a resume that consumes the shard's last
            # free blocks re-creates the very starvation that parked
            # requests in the first place (the freed blocks bounce
            # straight back and the engine thrashes swap-out/swap-in
            # without decoding).  Only resume when the shard can absorb
            # the re-upload AND still hand one write block to the
            # resumed slot and each surviving active slot.  The demand
            # counts only truly-fresh blocks — held blocks whose
            # (gid, gen) survived untouched re-adopt for free, so a
            # mostly-readoptable resume is not rejected for the size of
            # its whole ring.
            s = shard_of(j)
            t_r0 = time.perf_counter()
            headroom = 1 + sum(1 for jj in range(n)
                               if active[jj] and shard_of(jj) == s)
            fresh_demand = pool.resume_demand(j, rec.held)
            if pool.free_blocks(s) < fresh_demand + headroom:
                slo.deferrals += 1
                tr_brownout("defer", "resume_headroom", uid=rec.uid,
                            free=pool.free_blocks(s), fresh=fresh_demand,
                            held=len(rec.held), headroom=headroom)
                return False
            pool.free_slot(j)   # recycle any previous occupant's blocks
            readopted = []
            fresh = []
            for bi, (gid, gen) in rec.held.items():
                if pool.readopt(j, bi, gid, gen):
                    readopted.append(bi)
                else:
                    fresh.append(bi)
            if fresh and not try_ensure(j, fresh, []):
                pool.free_slot(j)       # drop the re-adoptions too
                slo.deferrals += 1
                tr_brownout("defer", "resume_alloc", uid=rec.uid,
                            fresh=len(fresh))
                return False
            slo.readopted_blocks += len(readopted)
            slo.reuploaded_blocks += len(fresh)
            ensure_row(j)
            row = np.full(pool.blocks_per_slot, pool.n_blocks, np.int32)
            for bi in fresh:
                row[bi] = pool.table[j, bi]
            snap, tails = rec.snap, rec.tails
            if self._rules is not None:
                snap = place_prefix_snapshot(snap, self._rules)
                tails = place_swap_payload(tails, self._rules)
            cache = self._swap_in(cache, snap, tails,
                                  jnp.int32(phys(j)), jnp.asarray(row))
            pos[j] = rec.pos
            cur[j] = rec.cur
            fed[j] = rec.fed
            since_tok[j] = rec.since_tok
            active[j] = True
            slot_uid[j] = rec.uid
            fr.set_frontier(j, rec.cov)
            slo.pop_record(rec)
            slo.swap_bytes -= (rec.n_blocks_swapped * paged.block_size
                               * tail_bpt + rec.state_bytes)
            if tr is not None:
                t_now = time.perf_counter()
                tr_open(j, rec.uid, t_r0, "resume", p0=rec.pos)
                tr.span("resume", t_r0, t_now, pid=shard_of(j),
                        tid=slot_tid(j), uid=rec.uid,
                        readopted=len(readopted), reuploaded=len(fresh),
                        demand=fresh_demand)
            return True

        def shed_active(j):
            """Drop an in-flight best-effort request outright (partial
            tokens already in ``toks`` are returned, blocks freed)."""
            uid = slot_uid[j]
            slo.shed_uid(uid, by_uid[uid].priority)
            if tr is not None:
                t_now = time.perf_counter()
                tr.event("shed", pid=shard_of(j), tid=slot_tid(j),
                         uid=uid, t=t_now, where="active",
                         why="brownout")
                tr_close(j, t_now, "shed")
            active[j] = False
            admitting[j] = False
            slot_uid[j] = -1
            since_tok[j] = 0
            pool.free_slot(j)

        def brownout_shed() -> bool:
            """Last brownout rung before PoolExhausted: shed best-effort
            work so the engine regains forward progress.  Cheapest
            first — a parked record (its blocks are already free), then
            the unadmittable queue head, then an active slot.  Never
            sheds the protected class: False means only high-class work
            remains and the exhaustion is real."""
            nonlocal qi
            if not slo_cfg.shed_on_exhaustion:
                return False
            rec = slo.pick_shed()
            if rec is not None:
                slo.shed_record(rec)
                slo.swap_bytes -= (rec.n_blocks_swapped
                                   * paged.block_size * tail_bpt
                                   + rec.state_bytes)
                tr_brownout("shed", "parked_record", uid=rec.uid)
                if tr is not None:
                    tr.event("shed", tid="engine", uid=rec.uid,
                             where="parked", why="pool_exhausted")
                return True
            if qi < len(order):
                r = by_uid[order[qi]]
                if not slo.is_high(r.priority):
                    slo.shed_uid(r.uid, r.priority)
                    tr_brownout("shed", "queue_head", uid=r.uid)
                    if tr is not None:
                        tr.event("shed", tid="queue", uid=r.uid,
                                 where="queue", why="pool_exhausted")
                    qi += 1
                    return True
            v = slo.pick_victim(victim_candidates(), slo_cfg.high_class)
            if v is not None:
                tr_brownout("shed", "active_victim", victim=int(v))
                shed_active(v)
                return True
            return False

        def brownout_reclaim() -> bool:
            """Zero-progress brownout: preempt the lowest-priority
            active slot when a strictly-higher-priority one needs its
            blocks (swap rung), else shed (final rung).  At zero
            forward progress ONLY, within-class preemption is allowed
            too: when every active slot is the same class and all are
            block-starved, swapping the cheapest one out lets the rest
            advance and it resumes bit-identically once capacity
            returns — strictly better than raising on all of them.
            (Needs >= 2 actives: swapping the only active would just
            resume into the same wall.)"""
            cands = victim_candidates()
            if cands and slo.can_swap():
                v = slo.pick_victim(cands, max(c[0] for c in cands))
                within_class = v is None
                if within_class and len(cands) >= 2:
                    v = slo.pick_victim(cands,
                                        max(c[0] for c in cands) + 1)
                if v is not None:
                    if tr is not None:
                        vp, vcost, _ = next(c for c in cands if c[2] == v)
                        tr_brownout("preempt", "zero_progress",
                                    victim=int(v), victim_priority=vp,
                                    victim_cost_bytes=int(vcost),
                                    within_class=within_class)
                    rec = preempt(v)
                    # hold until real tokens decode again, else the
                    # freed blocks bounce straight back (live-lock)
                    rec.hold = within_class
                    return True
            return brownout_shed()

        # per-request candidate digests, hashed once (admission steering
        # re-consults the prefix maps every engine step while a request
        # queues — only the map lookups need repeating, not the hashing).
        # The memo is keyed by uid for O(1) reuse but the prompt's
        # identity is VERIFIED before every reuse: a uid recycled for a
        # different prompt (duplicates in one stream, or uid reuse
        # against a long-lived server) must never steer or adopt with
        # the old prompt's digests.  Cluster assignment (template store)
        # happens here too — once per (uid, prompt), on first hashing.
        dig_by_uid: Dict[int, tuple] = {}
        cid_by_uid: Dict[int, int] = {}

        def prefix_digests(uid):
            po = prompts[uid]
            memo = dig_by_uid.get(uid)
            if memo is not None and (memo[0] is po or np.array_equal(
                    np.asarray(memo[0]), np.asarray(po))):
                return memo[1]
            p = np.asarray(po, np.int32)[-scfg.max_seq:]
            d = pcache.prefix_digests(p, chunk)
            dig_by_uid[uid] = (po, d)
            if store is not None:
                cid_by_uid[uid] = store.assign(p, d)
            return d

        def start_admission(j, uid) -> bool:
            nonlocal cache
            p = np.asarray(prompts[uid], np.int32)[-scfg.max_seq:]
            prompt_np[uid] = p
            if pool is not None:
                pool.free_slot(j)   # recycle the previous occupant's blocks
            if quota is not None:
                # QuotaRetention admission contract: reserve the whole
                # block budget up front — admitted ⇒ completable (nothing
                # retires mid-flight under an exact-KV policy, so a
                # mid-decode shortage could only deadlock) — and defer
                # the request back to the queue on shortage
                if not try_ensure(j, range(quota.admit_blocks(
                        len(p), by_uid[uid].max_new_tokens)), []):
                    pool.free_slot(j)
                    return False
            ensure_row(j)
            admitting[j] = True
            fed[j] = 0
            if fr is not None:
                fr.set_frontier(j, 0)
            if wr is not None:
                wr.on_slot_free(j)
            slot_uid[j] = uid
            hit = (pcache.lookup(shard_of(j), p, chunk,
                                 digests=prefix_digests(uid))
                   if pcache is not None else None)
            if hit is not None:
                # prefix-sharing fast path: adopt the registered tail
                # blocks (ref-counted; any divergent write COWs) and
                # restore the absorbed prompt centroids + coverage
                # frontier — admission resumes at fed = hit.fed instead
                # of re-streaming the shared prefix through the model
                for bi, gid in hit.blocks.items():
                    pool.adopt(j, bi, gid)
                cache = self._restore_slot_state(cache, hit.snap,
                                                 jnp.int32(phys(j)))
                fed[j] = hit.fed
                fr.set_frontier(j, hit.cov)
                # the slot now holds its own refs on every adopted
                # block — release the in-flight pin lookup() took so
                # pool-pressure eviction may reclaim the entry again
                pcache.adoption_done(hit)
            elif layout is not None or self._has_recurrent:
                # the slot's previous occupant left stale centroids and/or
                # recurrent state; ring entries are hidden by the position
                # mask, but stale counts would unmask stale centroids and
                # recurrent leaves have no mask at all — the fixed-size
                # state feeds straight into the next step (on a prefix hit
                # the restore overwrites all of this state instead)
                cache = self._reset_slot(cache, jnp.int32(phys(j)))
            slot_t.setdefault(uid, time.perf_counter())
            if tr is not None:
                tr_open(j, uid, time.perf_counter(), "admit",
                        p0=int(fed[j]))
            return True

        def admit_blocking(j, uid) -> bool:
            nonlocal cache, pad_toks, useful_toks, n_bad_logits
            r = by_uid[uid]
            p = np.asarray(prompts[uid], np.int32)[-scfg.max_seq:]
            plen = len(p)
            cov0 = fr.target(plen) if fr is not None else 0
            if pool is not None and r.max_new_tokens > 1:
                # allocation on admission — BEFORE the prefill compute,
                # so an exhausted pool defers the request back to the
                # queue (retried after the next give-back) instead of
                # wasting a prefill or killing the batch.  Under
                # FrontierRetention only the blocks holding live
                # (uncovered) prompt positions are claimed —
                # centroid-covered offsets stay unmapped and the scatter
                # drops them; under QuotaRetention the request's whole
                # block budget is reserved (admitted ⇒ completable:
                # nothing retires mid-flight)
                pool.free_slot(j)
                # a freshly freed slot has no shared mappings, so no COW
                # pairs can arise here (blocking admission and prefix
                # sharing are mutually exclusive by validation)
                need = (range(quota.admit_blocks(plen, r.max_new_tokens))
                        if quota is not None else
                        kv_pool.live_blocks(plen, cov0, R,
                                            paged.block_size))
                if not try_ensure(j, need, []):
                    pool.free_slot(j)
                    return False
            bkt = min(scfg.max_seq,
                      -(-plen // self._bucket) * self._bucket)
            padded = np.zeros((1, bkt), np.int32)
            padded[0, :plen] = p
            t0 = time.perf_counter()
            slot_t.setdefault(uid, t0)
            launch_t.setdefault(uid, t0)
            logits1, c1 = self._prefill(self.params, jnp.asarray(padded),
                                        jnp.int32(plen - 1))
            nxt1, bad = _greedy(logits1)
            first = int(nxt1[0])
            n_bad_logits += bad
            now = time.perf_counter()
            pre_ms[uid] = (now - t0_serve) * 1e3        # TTFT
            tr_open(j, uid, t0, "admit", p0=0)
            toks[uid] = [first]
            token_t[uid] = [now]
            if tr is not None:
                tr.span("prefill", t0, now, pid=shard_of(j),
                        tid=slot_tid(j), uid=uid, prompt_len=plen)
                tr.event("first_token", pid=shard_of(j), tid=slot_tid(j),
                         uid=uid, t=now, ttft_ms=pre_ms[uid])
            pad_toks += bkt - plen
            useful_toks += plen
            if r.max_new_tokens <= 1:
                if tr is not None:
                    t_done = time.perf_counter()
                    tr.event("finish", pid=shard_of(j), tid=slot_tid(j),
                             uid=uid, t=t_done)
                    tr_close(j, t_done, "finish")
                if pool is not None:
                    pool.free_slot(j)   # done at prefill; slot stays free
                return True
            if layout is not None:
                c1 = self._clusterize(c1, cache, plen, layout)
            if self._rules is not None:
                # admission placement: kv heads shard over the model axis
                # (admission_spec) instead of the old replicate-everything
                # P() — the data-axis copy is unavoidable for a B=1 cache
                # (one device assignment per jit); the chunked admission
                # path removes the B=1 cache entirely
                c1 = place_admission(c1, self._rules)
            ensure_row(j)
            if fr is not None:
                fr.set_frontier(j, cov0)
                kv_retired["frontier"] += cov0
            if wr is not None:
                wr.on_slot_free(j)
                kv_retired["window"] += wr.advance(j, plen)
            if pool is not None:
                bt_row = jnp.asarray(pool.row_for_write(j))
                cache = self._write_slot_paged(cache, c1, jnp.int32(phys(j)),
                                               bt_row)
            else:
                cache = self._write_slot(cache, c1, jnp.int32(phys(j)))
            cur[j], pos[j] = first, plen
            active[j] = True
            since_tok[j] = 0
            slot_uid[j] = uid
            return True

        idle_retries = stall_retries = 0
        while True:
            spans.step()
            spans.phase("sched_admit")
            # ---- admission ------------------------------------------------
            # next slot: the emptiest data shard's lowest free index
            # (recomputed per admission so a burst spreads across shards
            # AND keeps within-shard indices low for tight launch buckets;
            # with prefix sharing, occupancy ties prefer the shard already
            # holding the longest matching prefix entry — block ids are
            # shard-local, so reuse can't cross shards); chunked mode
            # starts at most one in-flight prefill per shard
            while True:
                # a parked (preempted) request resumes ahead of any
                # fresh admission of equal or lower priority — it
                # already paid its admission once
                rec = slo.peek_resume() if slo is not None else None
                if (rec is not None and qi < len(order)
                        and by_uid[order[qi]].priority > rec.priority):
                    rec = None
                if rec is None and qi >= len(order):
                    break
                occ = occupancy()
                if rec is not None:
                    rcands = []
                    for s in range(max(shards, 1)):
                        slots = range(s * per_shard,
                                      min((s + 1) * per_shard, n))
                        free = [j for j in slots
                                if not (active[j] or admitting[j])]
                        if free:
                            rcands.append((occ[s], s, free[0]))
                    if rcands:
                        if resume_swapped(min(rcands)[-1], rec):
                            continue
                        break   # pool-deferred resume: retry later
                    # slot pressure on a resume: preempt a strictly
                    # lower-priority active slot to make room
                    v = (slo.pick_victim(victim_candidates(),
                                         rec.priority)
                         if slo.can_swap() else None)
                    if v is not None:
                        tr_brownout("preempt", "resume_slot_pressure",
                                    victim=int(v), for_uid=rec.uid)
                        preempt(v)
                        continue
                    break
                uid = order[qi]
                p_next = (np.asarray(prompts[uid], np.int32)[-scfg.max_seq:]
                          if pcache is not None else None)
                cands = []
                for s in range(max(shards, 1)):
                    slots = range(s * per_shard, min((s + 1) * per_shard, n))
                    if chunk and any(admitting[j] for j in slots):
                        continue
                    free = [j for j in slots
                            if not (active[j] or admitting[j])]
                    if free:
                        match = (pcache.match_len(
                            s, p_next, chunk,
                            digests=prefix_digests(uid))
                                 if pcache is not None else 0)
                        # template-store steering: among equal direct
                        # matches, prefer the shard holding this
                        # request's traffic cluster — same-cluster
                        # requests land back-to-back where their
                        # entries (and pinned blocks) already live
                        aff = (store.shard_affinity(
                            s, cid_by_uid.get(uid, -1))
                               if store is not None else 0)
                        cands.append((occ[s], -match, -aff, s, free[0]))
                if not cands:
                    # slot pressure: a higher-priority head preempts
                    # the cheapest strictly-lower-priority active slot
                    # on an admissible shard (chunked mode: a shard
                    # already feeding a prefill can't admit even with a
                    # free slot, so its victims don't help)
                    if slo is not None and slo.can_swap():
                        adm = [s for s in range(max(shards, 1))
                               if not (chunk and any(
                                   admitting[j] for j in range(
                                       s * per_shard,
                                       min((s + 1) * per_shard, n))))]
                        v = slo.pick_victim(
                            [c for c in victim_candidates()
                             if shard_of(c[2]) in adm],
                            by_uid[uid].priority)
                        if v is not None:
                            tr_brownout("preempt", "slot_pressure",
                                        victim=int(v), for_uid=uid)
                            preempt(v)
                            continue
                    break
                j = min(cands)[-1]
                ok = (start_admission(j, uid) if chunk
                      else admit_blocking(j, uid))
                if ok:
                    qi += 1
                    continue
                # pool-deferred admission: count it, then walk the
                # brownout ladder — shed a best-effort request already
                # past its TTFT deadline (it can no longer meet its
                # SLO; its blocks serve requests that still can), or
                # preempt a lower-priority slot on the target shard
                if slo is not None:
                    slo.deferrals += 1
                    r = by_uid[uid]
                    if (not slo.is_high(r.priority)
                            and r.deadline_ms > 0
                            and (time.perf_counter() - t0_serve) * 1e3
                            > r.deadline_ms):
                        slo.shed_uid(uid, r.priority)
                        if tr is not None:
                            tr.event("shed", tid="queue", uid=uid,
                                     where="queue", why="deadline")
                        qi += 1
                        continue
                    if slo.can_swap():
                        v = slo.pick_victim(
                            victim_candidates(shard_of(j)), r.priority)
                        if v is not None:
                            tr_brownout("preempt", "admission_pool",
                                        victim=int(v), for_uid=uid)
                            preempt(v)
                            continue
                tr_brownout("defer", "admission_pool", uid=uid)
                break   # pool-deferred: retry after a give-back

            if not (active.any() or admitting.any()):
                if qi >= len(order) and (slo is None
                                         or slo.backlog_size() == 0):
                    break
                # admission (or a parked request's resume) deferred on
                # an idle engine: reclaim covered blocks + prefix-cache
                # pins and retry; then the brownout ladder sheds
                # best-effort work; only a genuinely unservable
                # protected request surfaces PoolExhausted
                freed = reclaim_all()
                idle_retries += 1
                if idle_retries > 1 and freed == 0:
                    if slo is not None and brownout_reclaim():
                        idle_retries = 0
                        continue
                    raise kv_pool.PoolExhausted(
                        "zero forward progress: an idle engine cannot "
                        "admit the next request even with every "
                        "reclaimable block returned — raise pool_blocks "
                        "(one slot's live window no longer fits)")
                continue
            idle_retries = 0

            # ---- bucketed launch: shrink to live occupancy ----------------
            # only once the queue has drained AND no prefill is in flight:
            # mid-stream occupancy dips are transient (a freed slot
            # readmits next step), every new physical shape costs a fresh
            # trace of the decode/compaction jits, and keeping admissions
            # at the full shape means the mixed-launch and absorb traces
            # exist at exactly one batch size.  The end-of-stream tail is
            # where shrinking pays, and its shapes ({per_shard,
            # per_shard/2, ..., 1}) are shared across serves so the
            # decode-only traces amortize
            if pool is None and qi >= len(order) and not admitting.any():
                busy_idx = [idx_of(j) for j in range(n)
                            if active[j] or admitting[j]]
                desired = min(per_shard, _pow2ceil(max(busy_idx) + 1))
                if desired < bucket:
                    resize_to(desired)
            bp = max(shards, 1) * bucket

            # ---- chunked admission: pre-step absorb (make ring room) ------
            spans.phase("kv_absorb")
            step_chunks = {}            # logical j -> chunk len this step
            if chunk:
                for j in np.nonzero(admitting)[0]:
                    plen = len(prompt_np[slot_uid[j]])
                    cl = int(min(chunk, plen - fed[j]))
                    step_chunks[int(j)] = cl
                    if (fr is not None
                            and fed[j] + cl - fr.frontier(j) > R):
                        target = int(np.clip(
                            fed[j] + cl - R + ccfg.refresh, 0, fed[j]))
                        kv_retired["frontier"] += target - fr.frontier(j)
                        t_ab0 = time.perf_counter()
                        if pool is not None:
                            cache = self._absorb_paged(
                                cache, jnp.int32(phys(j)),
                                jnp.int32(fed[j]), jnp.int32(target),
                                jnp.asarray(pool.row_for_read(j)))
                            fr.set_frontier(int(j), target)
                            pool.free_retired(int(j), int(fed[j]), fr)
                        else:
                            cache = self._absorb(cache, jnp.int32(phys(j)),
                                                 jnp.int32(fed[j]),
                                                 jnp.int32(target))
                            fr.set_frontier(int(j), target)
                        n_absorbs += 1
                        if tr is not None:
                            tr.span("absorb", t_ab0, time.perf_counter(),
                                    pid=shard_of(int(j)),
                                    tid=slot_tid(int(j)),
                                    uid=slot_uid[int(j)],
                                    target=int(target))

            # ---- build the launch -----------------------------------------
            mixed = bool(step_chunks)
            width = chunk if mixed else 1
            real_rows = int(active.sum()) + sum(step_chunks.values())
            stalled_decode = set()
            stalled_admit = set()
            if pool is not None:
                # paged packed launch: one row per real (slot, position)
                # pair, padded per data shard to a power-of-two row bucket
                # (bounded trace count) — compute ∝ real tokens instead of
                # slots × width.  Blocks this step's ring writes land in
                # are made WRITABLE first: unmapped blocks allocate (or
                # re-allocate after a give-back) and shared blocks
                # copy-on-write swap (prefix sharing) — the payload copies
                # run on device before any ring write.  A slot whose shard
                # cannot supply its blocks even after reclaim stalls for
                # the step (its row is simply not packed) and retries
                # after the next give-back, instead of killing the batch.
                # one shared accumulator: COW swaps performed before a
                # mid-list exhaustion (or by a slot that then stalls)
                # still get their payload copies below — the table
                # already points at the fresh blocks
                spans.phase("pool_ensure")
                cow_pairs = []
                for j in range(n):
                    if admitting[j] and j in step_chunks:
                        if not try_ensure(j, kv_pool.write_blocks(
                                int(fed[j]), step_chunks[j], R,
                                paged.block_size), cow_pairs):
                            del step_chunks[j]
                            stalled_admit.add(j)
                    elif active[j]:
                        if not try_ensure(j, kv_pool.write_blocks(
                                int(pos[j]), 1, R, paged.block_size),
                                cow_pairs):
                            stalled_decode.add(j)
                if cow_pairs:
                    apply_cow(cow_pairs)
                mixed = bool(step_chunks)
                width = chunk if mixed else 1
                real_rows = (int(active.sum()) - len(stalled_decode)
                             + sum(step_chunks.values()))
                if real_rows > 0 and slo is not None:
                    # forward progress this step: records parked by a
                    # zero-progress preemption become resumable again
                    slo.clear_holds()
                if real_rows == 0:
                    # every slot is pool-stalled: nothing can advance
                    # until blocks come back, and nothing is running to
                    # give them back — reclaim; if that yields nothing
                    # twice, no forward progress is possible
                    freed = reclaim_all()
                    stall_retries += 1
                    if stall_retries > 1 and freed == 0:
                        # brownout ahead of the raise: swap out the
                        # lowest-priority stalled slot so its blocks
                        # unstick higher ones, else shed best-effort
                        if slo is not None and brownout_reclaim():
                            stall_retries = 0
                            continue
                        raise kv_pool.PoolExhausted(
                            "zero forward progress: every slot's next "
                            "ring write needs a block and no block is "
                            "reclaimable — raise pool_blocks or shorten "
                            "refresh_every")
                    continue
                stall_retries = 0
                spans.phase("engine_pack")
                rows_by_shard = [[] for _ in range(max(shards, 1))]
                for j in range(n):
                    s = shard_of(j)
                    if admitting[j] and j in step_chunks:
                        cl = step_chunks[j]
                        p = prompt_np[slot_uid[j]]
                        for i in range(cl):
                            rows_by_shard[s].append(
                                (j, int(p[fed[j] + i]), int(fed[j]) + i,
                                 int(fed[j]) + cl, i))
                    elif active[j] and j not in stalled_decode:
                        rows_by_shard[s].append(
                            (j, int(cur[j]), int(pos[j]), int(pos[j]) + 1,
                             0))
                row_bucket = _pow2ceil(
                    max(max(len(rs) for rs in rows_by_shard), 1))
                np_rows = max(shards, 1) * row_bucket
                tokp = np.zeros(np_rows, np.int32)
                rslot = np.zeros(np_rows, np.int32)
                rpos = np.full(np_rows, -1, np.int32)
                rtw = np.zeros(np_rows, np.int32)
                # each row's index within its admission chunk (decode and
                # padding rows 0) — sequences sliding-window ring commits
                # in the 'L' sublayer's width-step loop
                rcidx = np.zeros(np_rows, np.int32)
                last_row: Dict[int, int] = {}
                for s, rs in enumerate(rows_by_shard):
                    base = s * row_bucket
                    # padding rows reference a real slot of their own
                    # shard (the shard's phys base) so the kernel's
                    # gathers stay shard-local; their qpos1 of 0 masks
                    # everything
                    rslot[base:base + row_bucket] = s * bucket
                    for i, (j, tk, p_, tw_, ci) in enumerate(rs):
                        tokp[base + i] = tk
                        rslot[base + i] = phys(j)
                        rpos[base + i] = p_
                        rtw[base + i] = tw_
                        rcidx[base + i] = ci
                        last_row[j] = base + i
                spans.phase("pool_table")
                bt_dev = bt_device()
                spans.phase("decode_packed")
                t0 = time.perf_counter()
                for j in step_chunks:
                    launch_t.setdefault(slot_uid[j], t0)
                logits, cache, *routes = self._decode_packed(
                    self.params, cache, jnp.asarray(tokp),
                    jnp.asarray(rslot), jnp.asarray(rpos),
                    jnp.asarray(rtw),
                    jnp.asarray(rcidx), bt_dev, width)
                moe_routes.extend(routes)       # summed after the serve
                if paged_attn:
                    real = rpos >= 0
                    attn_runs += int(real[0]) + int(
                        (real[1:] & ~(real[:-1]
                                      & (rslot[1:] == rslot[:-1]))).sum())
                if self._has_recurrent:
                    n_real = sum(len(rs) for rs in rows_by_shard)
                    n_later = int(((rpos >= 0) & (rcidx >= 1)).sum())
                    rec_rows += n_real
                    rec_steps += tfm.recurrent_steps(n, n_later)
                spans.phase("engine_readback")
                nxt, bad = _greedy(logits)
                nxt_of = lambda jj: nxt[last_row[jj]]      # noqa: E731
                # launch_rows_frac / launch_bucket_mean stay SLOT
                # bookkeeping (the slot axis never shrinks in paged
                # mode); the packed-row picture lives in launch_pad_frac
                # / launch_ragged_frac via compute_rows
                rows_step, compute_rows = bp, np_rows
            else:
                spans.phase("engine_pack")
                tok = np.zeros((bp, width), np.int32)
                t_vec = np.zeros(bp, np.int32)
                cl_vec = np.ones(bp, np.int32)
                for j in range(n):
                    if idx_of(j) >= bucket:
                        continue
                    pj = phys(j)
                    if admitting[j]:
                        cl = step_chunks[j]
                        p = prompt_np[slot_uid[j]]
                        tok[pj, :cl] = p[fed[j]:fed[j] + cl]
                        t_vec[pj] = fed[j]
                        cl_vec[pj] = cl
                    else:
                        tok[pj, 0] = cur[j]
                        t_vec[pj] = pos[j]

                t0 = time.perf_counter()
                for j in step_chunks:
                    launch_t.setdefault(slot_uid[j], t0)
                if mixed:
                    spans.phase("mixed_step")
                    logits, cache = self._mixed(self.params, cache,
                                                jnp.asarray(tok),
                                                jnp.asarray(t_vec),
                                                jnp.asarray(cl_vec))
                else:
                    spans.phase("decode_step")
                    logits, cache = self._decode(self.params, cache,
                                                 jnp.asarray(tok),
                                                 jnp.asarray(t_vec))
                spans.phase("engine_readback")
                nxt, bad = _greedy(logits)
                nxt_of = lambda jj: nxt[phys(jj)]          # noqa: E731
                rows_step, compute_rows = bp, bp * width
                if self._has_recurrent:
                    rec_rows += real_rows
                    rec_steps += compute_rows
            now = time.perf_counter()
            spans.phase("engine_update")
            dec_s += now - t0
            decode_steps += 1
            n_bad_logits += bad
            rows_launched += rows_step
            launch_real += real_rows
            launch_padded += compute_rows
            wasted_slots += int(n - (active | admitting).sum())
            if tr is not None:
                kind = ("decode" if not step_chunks else
                        ("mixed" if real_rows > sum(step_chunks.values())
                         else "prefill"))
                tr.span("engine_step", t0, now, tid="engine", kind=kind,
                        width=int(width), rows=int(compute_rows),
                        real_rows=int(real_rows),
                        occupancy=[int(x) for x in occupancy()],
                        pool_free=([pool.free_blocks(s)
                                    for s in range(max(shards, 1))]
                                   if pool is not None else []),
                        pool_live=(int(pool.allocated())
                                   if pool is not None else 0),
                        stalled=len(stalled_decode) + len(stalled_admit))
            advanced = active.copy()
            for j in stalled_decode:
                advanced[j] = False     # a pool-stalled slot didn't decode
            since_tok[advanced] += 1
            n_chunks += len(step_chunks)
            if shards > 1:
                shard_steps += 1
                for j in range(n):
                    if active[j] or admitting[j]:
                        shard_busy_steps[shard_of(j)] += 1
            if layout is not None:
                live = 0
                for j in range(n):
                    if admitting[j]:
                        live += min(int(fed[j]) + step_chunks.get(int(j), 0)
                                    - cov_of(j), R)
                    elif active[j]:
                        live += min(int(pos[j]) + 1 - cov_of(j), R)
                # physical blocks only: a block mapped by several slots
                # (prefix sharing) counts once — the duplicate-mapping
                # surplus is tracked separately as the sharing saving
                alloc = (pool.allocated() * paged.block_size if pool
                         else bp * R)
                kv_live_sum += live
                kv_alloc_sum += alloc
                kv_alloc_peak = max(kv_alloc_peak, alloc)
                if pool is not None:
                    kv_shared_peak = max(kv_shared_peak,
                                         pool.shared_extra())

            # ---- host update ---------------------------------------------
            for j in range(n):
                if idx_of(j) >= bucket:
                    continue
                pj = phys(j)
                uid = slot_uid[j]
                if admitting[j]:
                    if j not in step_chunks:
                        continue        # pool-stalled this step
                    cl = step_chunks[j]
                    fed[j] += cl
                    if tr is not None:
                        tr.event("prefill_chunk", pid=shard_of(j),
                                 tid=slot_tid(j), uid=uid, t=now,
                                 fed=int(fed[j]), chunk=cl)
                    if wr is not None:
                        kv_retired["window"] += wr.advance(j, int(fed[j]))
                    plen = len(prompt_np[uid])
                    useful_toks += cl
                    if fed[j] < plen:
                        # chunk-boundary state is prefix-pure — a
                        # deterministic function of tokens[:fed] alone
                        # (per-slot compaction gating keeps neighbours
                        # from perturbing it) — so register it for
                        # later same-prefix admissions
                        if (pcache is not None and fed[j] % chunk == 0
                                and fed[j] >= max(self._pshare.min_prefix,
                                                  chunk)):
                            blocks = {
                                bi: int(pool.table[j, bi])
                                for bi in kv_pool.live_blocks(
                                    int(fed[j]), cov_of(j), R,
                                    paged.block_size)
                                if pool.table[j, bi] >= 0}
                            snap = self._snap_slot(cache, jnp.int32(pj))
                            if self._rules is not None:
                                snap = place_prefix_snapshot(
                                    snap, self._rules)
                            pcache.register(shard_of(j), prompt_np[uid],
                                            int(fed[j]), cov_of(j),
                                            blocks, snap,
                                            cluster=cid_by_uid.get(
                                                uid, -1))
                        continue
                    # final chunk landed: its last row's logits are the
                    # request's first generated token
                    if fr is not None:
                        target_end = fr.target(plen)
                        if fr.frontier(j) < target_end:
                            kv_retired["frontier"] += (target_end
                                                       - fr.frontier(j))
                            t_ab0 = time.perf_counter()
                            with _annot("kv_absorb"):
                                if pool is not None:
                                    cache = self._absorb_paged(
                                        cache, jnp.int32(pj),
                                        jnp.int32(plen),
                                        jnp.int32(target_end),
                                        jnp.asarray(pool.row_for_read(j)))
                                    fr.set_frontier(j, target_end)
                                    pool.free_retired(j, plen, fr)
                                else:
                                    cache = self._absorb(
                                        cache, jnp.int32(pj),
                                        jnp.int32(plen),
                                        jnp.int32(target_end))
                                    fr.set_frontier(j, target_end)
                            n_absorbs += 1
                            if tr is not None:
                                tr.span("absorb", t_ab0,
                                        time.perf_counter(),
                                        pid=shard_of(j), tid=slot_tid(j),
                                        uid=uid, target=int(target_end))
                    first = int(nxt_of(j))
                    toks[uid] = [first]
                    token_t[uid] = [now]
                    pre_ms[uid] = (now - t0_serve) * 1e3    # TTFT
                    if tr is not None:
                        tr.event("first_token", pid=shard_of(j),
                                 tid=slot_tid(j), uid=uid, t=now,
                                 ttft_ms=pre_ms[uid])
                    admitting[j] = False
                    if by_uid[uid].max_new_tokens <= 1:
                        if tr is not None:
                            tr.event("finish", pid=shard_of(j),
                                     tid=slot_tid(j), uid=uid, t=now)
                            tr_close(j, now, "finish")
                        slot_uid[j] = -1
                        if pool is not None:
                            if quota is not None:
                                kv_retired["quota"] += (
                                    int((pool.table[j] >= 0).sum())
                                    * paged.block_size)
                            pool.free_slot(j)   # recycling on early exit
                    else:
                        active[j] = True
                        since_tok[j] = 0
                        pos[j] = plen
                        cur[j] = first
                elif active[j] and j not in stalled_decode:
                    toks[uid].append(int(nxt_of(j)))
                    token_t[uid].append(now)
                    pos[j] += 1
                    if wr is not None:
                        kv_retired["window"] += wr.advance(j, int(pos[j]))
                    cur[j] = nxt_of(j)
                    if len(toks[uid]) >= by_uid[uid].max_new_tokens:
                        active[j] = False
                        since_tok[j] = 0
                        if tr is not None:
                            tr.event("finish", pid=shard_of(j),
                                     tid=slot_tid(j), uid=uid, t=now)
                            tr_close(j, now, "finish")
                        if pool is not None:
                            if quota is not None:
                                # an exact-KV slot retires its whole
                                # footprint in one go at request exit
                                kv_retired["quota"] += (
                                    int((pool.table[j] >= 0).sum())
                                    * paged.block_size)
                            pool.free_slot(j)   # recycling on early exit

            # ---- compaction: per-slot cadence -----------------------------
            # a slot is due after ``refresh`` of its OWN decode tokens;
            # one batched call refreshes every due slot (others pass
            # length 0 and recompact_clustered's per-slot gate keeps
            # their summaries bit-identical).  Per-slot triggering —
            # rather than the old global since_tok reset — makes each
            # slot's compaction schedule a function of its own stream
            # alone, so admission timing (bursts, prefix-shared fast
            # paths, pool stalls) can never shift a neighbour's
            # compaction points and change its tokens.  A pass in which
            # no due slot's frontier advances would return every row
            # unchanged: its launch is skipped, its bookkeeping kept
            spans.phase("kv_compact")
            due = [j for j in range(n)
                   if ccfg is not None and active[j]
                   and since_tok[j] >= ccfg.refresh and idx_of(j) < bucket]
            if due:
                t_c0 = time.perf_counter()
                launch = _frontier_advances(due, pos, fr)
                if launch:
                    lengths = np.zeros(bp, np.int32)
                    for j in due:
                        lengths[phys(j)] = pos[j]
                    if pool is not None:
                        cache = self._compact_paged(
                            cache, jnp.asarray(lengths), bt_device())
                    else:
                        cache = self.compact_kv(cache, lengths, ccfg)
                        if self._rules is not None:
                            # eviction/compaction rebuilt the clustered
                            # leaves outside the constrained decode jit —
                            # put them back on their mesh layout before
                            # the next step
                            cache = shard_cache(cache, self._rules)
                    n_compacts += 1
                    compact_rows += bp
                    compact_gaps += int(active.sum())
                else:
                    compact_skipped += 1
                # host frontier mirror (recompact_clustered's formula) —
                # compaction is when the paged engine returns retired
                # blocks to the pool
                for j in due:
                    newc = max(fr.frontier(j), fr.target(int(pos[j])))
                    kv_retired["frontier"] += newc - fr.frontier(j)
                    compact_folded += newc > fr.frontier(j)
                    fr.set_frontier(j, newc)
                    if pool is not None:
                        pool.free_retired(j, int(pos[j]), fr)
                    since_tok[j] = 0
                compact_due += len(due)
                if launch and tr is not None:
                    tr.span("compact", t_c0, time.perf_counter(),
                            tid="engine", slots=[int(j) for j in due])
            spans.end_phase()

            # ---- post-step priority pass -----------------------------
            # a pool-stalled slot (decode or admission) whose priority
            # strictly exceeds a neighbour's gets that neighbour's
            # blocks next step: swap the shard's cheapest lower-priority
            # active slot out (one victim per shard per step — a clean
            # boundary, every COW copy of this step already applied)
            if slo is not None and (stalled_decode or stalled_admit):
                for s in range(max(shards, 1)):
                    sp = [by_uid[slot_uid[j]].priority
                          for j in (stalled_decode | stalled_admit)
                          if shard_of(j) == s]
                    if not sp or not slo.can_swap():
                        continue
                    v = slo.pick_victim(victim_candidates(s), max(sp))
                    if v is not None:
                        preempt(v)
        spans.close()

        if pcache is not None:
            if store is None:
                # entries are a per-serve cache: release every pinned
                # block so the pool drains to zero with the request
                # stream
                pcache.clear()
            else:
                # persistent template store: entries and their pinned
                # blocks survive the drain — the pool and the device
                # cache park on the store (epoch-keyed, so any Server
                # under the same epoch can adopt) with mirror attrs on
                # the server for introspection.  Drain accounting
                # weakens from allocated()==0 to
                # allocated()==pinned_blocks(); anything beyond the
                # pins is a leak and shows up in pool_blocks_end.
                self._tmpl_pool, self._tmpl_cache = pool, cache
                pcache.parked = (pool, cache, self._store_epoch,
                                 max(shards, 1))
        wall = time.perf_counter() - t0_serve
        gen_total = sum(len(v) for v in toks.values())
        dec_ms_tok = dec_s * 1e3 / max(gen_total, 1)
        ttfts = [pre_ms[u] / 1e3 for u in pre_ms]
        itls: List[float] = []
        for ts in token_t.values():
            itls.extend(b - a for a, b in zip(ts, ts[1:]))
        # ---- publish into the typed metrics registry -----------------
        # last_stats is regenerated from the registry (flat_view) so
        # every historical key keeps working while the keys themselves
        # become typed, documented metrics (see reg.reference_table())
        reg.counter("decode_steps",
                    "engine launches this serve").add(decode_steps)
        reg.gauge("slot_waste", "idle slot-steps / total slot-steps"
                  ).set(wasted_slots / max(decode_steps * n, 1))
        reg.gauge("prefill_pad_frac",
                  "prompt pad tokens / all prefill tokens"
                  ).set(pad_toks / max(pad_toks + useful_toks, 1))
        reg.counter("gen_tokens", "tokens generated this serve"
                    ).add(gen_total)
        reg.gauge("decode_s", "seconds inside engine launches"
                  ).set(dec_s)
        reg.gauge("wall_s", "end-to-end serve wall seconds").set(wall)
        reg.gauge("tokens_per_s_wall", "all tokens per wall second"
                  ).set(gen_total / max(wall, 1e-9))
        ht = reg.histogram("ttft", "wall-clock time to first token",
                           quantiles=(50, 95, 99), scale=1e3,
                           suffix="_ms")
        for v in ttfts:
            ht.observe(v)
        hi = reg.histogram("itl", "inter-token latency",
                           quantiles=(50, 95, 99), scale=1e3,
                           suffix="_ms")
        for v in itls:
            hi.observe(v)
        reg.gauge("launch_rows_frac", "launched slot rows / slots×steps"
                  ).set(rows_launched / max(decode_steps * n, 1))
        reg.gauge("launch_bucket_mean", "mean launch bucket per shard"
                  ).set(rows_launched
                        / max(decode_steps * max(shards, 1), 1))
        # padded-compute waste: launched rows × width that carried no
        # real (slot, position) pair — the number the packed ragged
        # launch exists to shrink — and its complement, the fraction
        # of launched compute rows that were real tokens
        reg.gauge("launch_pad_frac",
                  "launched compute rows carrying no real token"
                  ).set(1.0 - launch_real / max(launch_padded, 1))
        reg.gauge("launch_ragged_frac",
                  "real tokens / launched compute rows"
                  ).set(launch_real / max(launch_padded, 1))
        reg.counter("prefill_chunks",
                    "prompt chunks fed through mixed launches"
                    ).add(n_chunks)
        reg.counter("recurrent_row_steps",
                    "one-token recurrent steps launched per recurrent "
                    "sublayer, summed over launches (0 without recurrent "
                    "layers)").add(rec_steps)
        reg.counter("recurrent_rows",
                    "real rows among the launched recurrent steps"
                    ).add(rec_rows)
        reg.counter("attn_runs",
                    "slot runs the paged clustered-decode kernel staged "
                    "and scored, summed over launches: maximal stretches "
                    "of packed rows of one slot (a prompt chunk's rows, or "
                    "one decode row); 0 without that kernel"
                    ).add(attn_runs)
        # read back after the serve: one small copy per launch, and no
        # program whose shape follows the launch count
        routes = np.sum(jax.device_get(moe_routes), axis=0,
                        dtype=np.int64) if moe_routes else np.zeros(2)
        reg.counter("moe_routes_held",
                    "top-k routes of fed rows that landed on an expert "
                    "this device holds, summed over MoE layers (packed "
                    "engine)").add(int(routes[0]))
        reg.counter("moe_routes",
                    "top-k routes of fed rows, summed over MoE layers "
                    "(packed engine)").add(int(routes[1]))
        reg.counter("kv_absorbs", "streaming absorb_chunk calls"
                    ).add(n_absorbs)
        reg.counter("kv_compactions", "batched compaction passes launched"
                    ).add(n_compacts)
        reg.counter("kv_compact_slot_rows",
                    "slot rows launched into compaction passes"
                    ).add(compact_rows)
        reg.counter("kv_compact_slots_due",
                    "slots due for compaction, summed over passes "
                    "(launched or skipped)"
                    ).add(compact_due)
        reg.counter("kv_compact_slots_folded",
                    "due slots whose coverage frontier advanced"
                    ).add(compact_folded)
        reg.counter("kv_compact_gaps",
                    "streams still decoding at a launched compaction pass "
                    "(each has its next inter-token gap stretched by it)"
                    ).add(compact_gaps)
        reg.counter("kv_compact_passes_skipped",
                    "passes with due slots whose launch was skipped: no "
                    "due slot's coverage frontier advanced"
                    ).add(compact_skipped)
        reg.counter("queue_slot_wait_s",
                    "seconds from serve start to slot assignment, summed "
                    "over requests").add(sum(t - t0_serve
                                             for t in slot_t.values()))
        reg.counter("queue_wait_s",
                    "seconds from serve start to the dispatch of the "
                    "launch carrying a request's first prompt chunk (or "
                    "its blocking prefill), summed over requests"
                    ).add(sum(t - t0_serve for t in launch_t.values()))
        reg.counter("logits_nonfinite",
                    "NaN/inf logit values in engine launches this serve"
                    ).add(n_bad_logits)
        # positions each retention policy retired this serve —
        # FrontierRetention counts coverage-frontier advancement
        # (absorbs + compactions + admission clusterize, dense and
        # paged alike), WindowRetention positions that aged out of
        # 'L' layers' sliding windows, QuotaRetention block-backed
        # positions released at request exit.  Always present so
        # benchmark schemas stay stable across engine modes
        reg.counter("kv_retired_frontier",
                    "positions retired behind the coverage frontier"
                    ).add(kv_retired["frontier"])
        reg.counter("kv_retired_window",
                    "positions aged out of sliding windows"
                    ).add(kv_retired["window"])
        reg.counter("kv_retired_quota",
                    "block-backed positions released at request exit"
                    ).add(kv_retired["quota"])
        # recurrent family: the retirement counter is identically zero
        # by construction (fixed-size state folds every position) — the
        # explicit key comes from RecurrentRetention.diagnostics so the
        # invariant is published, not silently omitted
        reg.counter("kv_retired_recurrent",
                    "positions retired from recurrent state (0 by "
                    "construction: fixed-size state folds every position)"
                    ).add(rr.diagnostics()["kv_retired_recurrent"]
                          if rr is not None else 0)
        # per-family state-byte picture (core/layer_state.py): dense
        # per-slot bytes each family carries — ring centroid summaries /
        # window rings (pool-backed tail blocks are priced separately in
        # the kv_bytes_* metrics) vs the recurrent family's fixed-size
        # whole-state price.  Always present so benchmark schemas stay
        # stable across layer patterns
        reg.gauge("state_bytes_ring",
                  "dense ring-family state bytes per slot (tails excluded)"
                  ).set(float(layer_state.ring_state_bytes(
                      cache, max(shards, 1) * bucket)))
        reg.gauge("state_bytes_recurrent",
                  "recurrent-family state bytes per slot"
                  ).set(float(rec_state_b))
        if layout is not None:
            # KV-allocation picture, comparable across paged and dense:
            # dense "allocates" every launched slot's full tail ring
            reg.gauge("kv_frag",
                      "1 - live ring tokens / allocated ring capacity"
                      ).set(1.0 - kv_live_sum / max(kv_alloc_sum, 1))
            reg.gauge("kv_alloc_tokens_peak",
                      "peak allocated ring tokens"
                      ).set(float(kv_alloc_peak))
            if pool is not None:
                # physical blocks only: shared blocks count once
                # (kv_shared_blocks/kv_bytes_saved carry the surplus);
                # alloc/free/retain/cow are per-serve deltas vs the
                # serve-start mark (a persistent pool carries lifetime
                # counters)
                pool.publish(reg, pool_mark,
                             paged.block_size * tail_bpt)
                reg.gauge("kv_shared_blocks",
                          "peak logical mappings beyond physical blocks"
                          ).set(float(kv_shared_peak))
                reg.gauge("kv_bytes_saved",
                          "tail KV bytes prefix sharing avoided"
                          ).set(float(kv_shared_peak * paged.block_size
                                      * tail_bpt))
                # every request completed → every block recycled, minus
                # what the template store deliberately pins across
                # serves (0 = no leak in both modes)
                reg.gauge("pool_blocks_end",
                          "blocks live beyond store pins (>0 = leak)"
                          ).set(float(pool.allocated()
                                      - (store.pinned_blocks()
                                         if store is not None else 0)))
                if pcache is not None:
                    # per-serve deltas (the counters are lifetime-
                    # cumulative on the cache object; raw totals would
                    # double-count every serve after the first)
                    reg.counter("prefix_hits",
                                "prefix-cache adoptions this serve"
                                ).add(pcache.hits - hits0)
                    reg.counter("prefix_tokens_reused",
                                "prompt tokens adopted this serve"
                                ).add(pcache.tokens_reused - reused0)
                if store is not None:
                    # lifetime store view (persist=True counters survive
                    # begin_serve) + per-cluster traffic picture
                    store.publish(reg, paged.block_size * tail_bpt)
            else:
                reg.gauge("kv_bytes_peak_per_shard",
                          "peak live tail-KV bytes on the busiest shard"
                          ).set(float(per_shard * R * tail_bpt))
                reg.gauge("pool_occupancy_peak",
                          "peak live blocks / capacity").set(1.0)
        if slo is not None:
            # brownout ladder accounting (sched_shed_high must be 0:
            # the protected class is never shed, only raised on)
            slo.publish(reg)
        if shards > 1:
            reg.gauge("n_data_shards", "data shards this serve"
                      ).set(float(shards))
            for s in range(shards):
                reg.gauge(f"slot_waste_shard{s}",
                          f"idle slot-step fraction on data shard {s}"
                          ).set(1.0 - shard_busy_steps[s]
                                / (shard_steps * per_shard)
                                if shard_steps else 0.0)
        b = self._builds
        reg.counter("programs_built",
                    "programs built this serve (compiled, or loaded from "
                    "the persistent cache)").add(b.n - builds0[0])
        reg.counter("program_build_s",
                    "seconds tracing, lowering and compiling programs "
                    "this serve").add(b.seconds - builds0[1])
        reg.counter("programs_built_total",
                    "programs built since the server was constructed",
                    persist=True).set_to(b.n - self._builds0[0])
        reg.counter("program_build_s_total",
                    "seconds building programs since the server was "
                    "constructed",
                    persist=True).set_to(b.seconds - self._builds0[1])
        self.last_stats = reg.flat_view()
        if tr is not None:
            self.last_trace = tr.finish()
        shed_uids = slo.shed_uids if slo is not None else ()
        return [Completion(uid=r.uid, tokens=toks.get(r.uid, []),
                           prefill_ms=pre_ms.get(r.uid, 0.0),
                           decode_ms=dec_ms_tok
                           * len(toks.get(r.uid, [])),
                           shed=r.uid in shed_uids)
                for r in requests]

    @staticmethod
    def _params_digest(params) -> str:
        """Content hash of the parameter pytree: leaf paths, shapes,
        dtypes, and raw bytes.  The template-store epoch stamps this
        instead of ``id(params)`` so reloaded identical weights (a new
        pytree object, same bytes) keep a warm store, while any real
        weight change still invalidates every snapshot."""
        h = hashlib.blake2b(digest_size=16)
        flat, _ = jax.tree_util.tree_flatten_with_path(params)
        for kp, leaf in flat:
            arr = np.asarray(leaf)
            h.update("/".join(_key_name(k) for k in kp).encode())
            h.update(repr((arr.shape, str(arr.dtype))).encode())
            h.update(arr.tobytes())
        return h.hexdigest()

    @staticmethod
    def _tail_bytes_per_token(cache) -> int:
        """Bytes one ring position costs across every tail leaf of the
        stack (k+v, all layers) — same accounting for the dense per-slot
        ring and the paged block pool, so their peak-KV stats compare."""
        total = 0
        flat, _ = jax.tree_util.tree_flatten_with_path(cache)
        for kp, leaf in flat:
            if _key_name(kp[-1]) not in ("k_tail", "v_tail"):
                continue
            stacked = _key_name(kp[0]) == "scan"
            h, dh = leaf.shape[-2], leaf.shape[-1]
            lyr = leaf.shape[0] if stacked else 1
            total += lyr * h * dh * leaf.dtype.itemsize
        return total

    # ------------------------------------------------------------------
    # bucketed launches: slot-axis resize
    # ------------------------------------------------------------------

    def _resize_cache(self, cache, ob: int, nb: int):
        """Resize every cache leaf's slot axis from shards*ob to
        shards*nb physical rows (jitted per (ob, nb) pair, donated).
        Dead high slots hold no live request state, so shrink drops them
        and grow zero-fills."""
        fn = self._resize_jits.get((ob, nb))
        if fn is None:
            shards = max(self._n_data_shards, 1)

            def impl(c):
                flat, treedef = jax.tree_util.tree_flatten_with_path(c)
                out = []
                for kp, leaf in flat:
                    name = _key_name(kp[-1])
                    if name in ("k_scale", "v_scale"):  # per-head, no slots
                        out.append(leaf)
                        continue
                    axis = 1 if _key_name(kp[0]) == "scan" else 0
                    out.append(_slot_resize(leaf, axis, shards, ob, nb))
                res = jax.tree_util.tree_unflatten(treedef, out)
                return self._constrain(res)

            fn = jax.jit(impl, donate_argnums=(0,))
            self._resize_jits[(ob, nb)] = fn
        return fn(cache)

    # ------------------------------------------------------------------
    # chunked admission: slot reset + streaming absorb
    # ------------------------------------------------------------------

    def _reset_slot_impl(self, cache, j):
        """Zero one slot's clustered bookkeeping (counts + cov) and its
        recurrent state ahead of a fresh chunked admission.  Ring/centroid
        payloads need no wipe: ring entries are hidden by the position
        mask until the chunk stream overwrites them, and zero-count
        centroids are masked.  Recurrent leaves have no mask — the whole
        fixed-size state IS live input to the next step — so the previous
        occupant's (conv, ssm) / (conv, h) must be zeroed outright."""
        def walk(node):
            if _is_clustered_kv(node):
                out = dict(node)
                if node["k_cents"].ndim == 5:            # scan-stacked
                    out["counts"] = node["counts"].at[:, j].set(0.0)
                    out["cov"] = node["cov"].at[:, j].set(0)
                else:
                    out["counts"] = node["counts"].at[j].set(0.0)
                    out["cov"] = node["cov"].at[j].set(0)
                return out
            if layer_state.is_recurrent_leaf(node):
                if layer_state.recurrent_leaf_stacked(node):
                    return {k: v.at[:, j].set(0) for k, v in node.items()}
                return {k: v.at[j].set(0) for k, v in node.items()}
            if isinstance(node, dict):
                return {k: walk(v) for k, v in node.items()}
            if isinstance(node, list):
                return [walk(v) for v in node]
            return node

        return walk(cache)

    def _absorb_impl(self, cache, j, lengths, target, ccfg):
        """Advance slot j's coverage frontier to ``target`` by folding its
        aged ring entries into centroids (kv_compress.absorb_chunk),
        touching only that slot — mid-decode neighbours must stay
        bit-identical.  ``lengths`` = ring positions written so far."""
        def leaf(node):
            stacked = node["k_cents"].ndim == 5
            ax = 1 if stacked else 0
            sub = {k: jax.lax.dynamic_slice_in_dim(v, j, 1, axis=ax)
                   for k, v in node.items()}
            if stacked:
                lyr = node["k_cents"].shape[0]
                flat = {k: v.reshape((lyr,) + v.shape[2:])
                        for k, v in sub.items()}
                got = kv_compress.absorb_chunk(
                    flat, jnp.full((lyr,), lengths, jnp.int32),
                    jnp.full((lyr,), target, jnp.int32), ccfg)
                got = {k: v[:, None] for k, v in got.items()}
            else:
                got = kv_compress.absorb_chunk(
                    sub, jnp.full((1,), lengths, jnp.int32),
                    jnp.full((1,), target, jnp.int32), ccfg)
            return {k: jax.lax.dynamic_update_slice_in_dim(
                node[k], got[k].astype(node[k].dtype), j, axis=ax)
                for k in node}

        def walk(node):
            if _is_clustered_kv(node):
                return leaf(node)
            if isinstance(node, dict):
                return {k: walk(v) for k, v in node.items()}
            if isinstance(node, list):
                return [walk(v) for v in node]
            return node

        return walk(cache)

    # ------------------------------------------------------------------
    # paged path: pool gathers/scatters around the same compaction math
    #
    # Every paged op gathers a slot's tail blocks into the dense ring
    # layout, runs the UNCHANGED kv_compress routine, and writes back
    # only centroids/counts/cov (compaction never rewrites tail bytes).
    # Offsets whose blocks are unmapped read garbage from the sanitized
    # alias block — they are strictly outside [cov, t), so they carry
    # weight 0 in the clustering and are masked in attention, and the
    # results stay bit-identical to the dense engine.
    # ------------------------------------------------------------------

    @staticmethod
    def _gather_tail_rows(pool_arr, bt):
        """Dense ring view of a paged tail pool.  pool (nb, bs, H, Dh) +
        bt (..., T) → (..., T*bs, H, Dh); stacked pool (L, nb, bs, H, Dh)
        → (L, ..., T*bs, H, Dh)."""
        stacked = pool_arr.ndim == 5
        h, dh = pool_arr.shape[-2], pool_arr.shape[-1]
        if stacked:
            lyr = pool_arr.shape[0]
            got = pool_arr[:, bt]          # (L, ..., T, bs, H, Dh)
            return got.reshape((lyr,) + bt.shape[:-1] + (-1, h, dh))
        got = pool_arr[bt]                 # (..., T, bs, H, Dh)
        return got.reshape(bt.shape[:-1] + (-1, h, dh))

    def _write_slot_paged_impl(self, dst, src, j, bt_row, blk: int):
        """Paged twin of ``_write_slot_impl``: clustered leaves write
        centroids/counts/cov densely at slot j and scatter the B=1 dense
        tail ring into the slot's freshly-allocated pool blocks
        (``bt_row`` (T,), unmapped = covered offsets pointing out of
        range so mode='drop' skips them); all other leaves take the
        dense slot write."""
        def upd(axis):
            def f(d, s):
                idx = (0,) * axis + (j,) + (0,) * (d.ndim - axis - 1)
                return jax.lax.dynamic_update_slice(d, s.astype(d.dtype),
                                                    idx)
            return f

        def leaf(dnode, snode, axis):
            out = {}
            for key in ("k_cents", "v_cents", "counts", "cov"):
                out[key] = upd(axis)(dnode[key], snode[key])
            for key in ("k_tail", "v_tail"):
                pool_arr, srct = dnode[key], snode[key]
                if axis == 1:              # scan-stacked: src (L, 1, R, …)
                    lyr = srct.shape[0]
                    blocks = srct.reshape(lyr, -1, blk, srct.shape[-2],
                                          srct.shape[-1])
                    out[key] = pool_arr.at[:, bt_row].set(
                        blocks.astype(pool_arr.dtype), mode="drop")
                else:                      # src (1, R, H, Dh)
                    blocks = srct.reshape(-1, blk, srct.shape[-2],
                                          srct.shape[-1])
                    out[key] = pool_arr.at[bt_row].set(
                        blocks.astype(pool_arr.dtype), mode="drop")
            return out

        def walk(dnode, snode, axis):
            if _is_clustered_kv(dnode):
                return leaf(dnode, snode, axis)
            if isinstance(dnode, dict):
                return {k: walk(dnode[k], snode[k], axis) for k in dnode}
            if isinstance(dnode, list):
                return [walk(d, s, axis) for d, s in zip(dnode, snode)]
            return upd(axis)(dnode, snode)

        out = dict(dst)
        for key in ("prefix", "tail"):
            out[key] = [walk(dc, sc, 0) for dc, sc in zip(dst[key],
                                                          src[key])]
        if "scan" in dst:
            out["scan"] = walk(dst["scan"], src["scan"], 1)
        return out

    @staticmethod
    def _gather_swap_tails(cache, bt_row):
        """Swap-out gather: every clustered leaf's tail blocks for one
        slot, in ring-block order.  ``bt_row`` is the slot's (T,)
        read-sanitized table row (unmapped → shard base: those rows
        gather alias garbage the cov/position masks already exclude, and
        swap-in never scatters them back).  Non-clustered nodes yield
        None — the swap protocol, like the prefix snapshot it extends,
        is defined only for FrontierRetention (clustered) state."""
        def leaf(node):
            out = {}
            for key in ("k_tail", "v_tail"):
                p = node[key]
                if p.ndim == 5:            # scan-stacked (L, nb, bs, H, Dh)
                    out[key] = p[:, bt_row]
                else:                      # (nb, bs, H, Dh)
                    out[key] = p[bt_row]
            return out

        def walk(node):
            if _is_clustered_kv(node):
                return leaf(node)
            if isinstance(node, dict):
                return {k: walk(v) for k, v in node.items()}
            if isinstance(node, list):
                return [walk(v) for v in node]
            return None

        return walk(cache)

    @staticmethod
    def _scatter_swap_tails(cache, tails, bt_row):
        """Swap-in scatter: write a resuming slot's host tail payloads
        into its freshly-allocated pool blocks.  ``bt_row`` is (T,) with
        ONLY fresh allocations holding real ids — re-adopted blocks and
        never-mapped ring blocks point out of range (``n_blocks``) so
        mode='drop' skips them: a re-adopted block may be shared
        (ref > 1) and its device bytes provably equal the host copy
        already, so writing it would violate the COW protocol for zero
        information."""
        def leaf(node, tl):
            out = dict(node)
            for key in ("k_tail", "v_tail"):
                p = node[key]
                if p.ndim == 5:
                    out[key] = p.at[:, bt_row].set(
                        tl[key].astype(p.dtype), mode="drop")
                else:
                    out[key] = p.at[bt_row].set(
                        tl[key].astype(p.dtype), mode="drop")
            return out

        def walk(node, tl):
            if _is_clustered_kv(node):
                return leaf(node, tl)
            if isinstance(node, dict):
                return {k: walk(v, tl[k]) for k, v in node.items()}
            if isinstance(node, list):
                return [walk(v, t2) for v, t2 in zip(node, tl)]
            return node

        return walk(cache, tails)

    def _cow_impl(self, cache, src, dst):
        """Device half of copy-on-write (prefix sharing): copy pool
        blocks ``src`` → ``dst`` ((m,) global ids, same shard per pair)
        in every clustered tail leaf.  The allocator already swapped the
        writing slot's table entry to ``dst`` (kv_pool.ensure), so this
        copy must land before the step's ring writes — the engine threads
        the cache through this jit first.  Padding pairs repeat a real
        pair; the duplicate scatter writes identical values, so the
        result is deterministic."""
        def leaf(node):
            out = dict(node)
            for key in ("k_tail", "v_tail"):
                p = node[key]
                if p.ndim == 5:            # scan-stacked (L, nb, bs, H, Dh)
                    out[key] = p.at[:, dst].set(p[:, src])
                else:                      # (nb, bs, H, Dh)
                    out[key] = p.at[dst].set(p[src])
            return out

        def walk(node):
            if _is_clustered_kv(node):
                return leaf(node)
            if isinstance(node, dict):
                return {k: walk(v) for k, v in node.items()}
            if isinstance(node, list):
                return [walk(v) for v in node]
            return node

        return walk(cache)

    def _absorb_paged_impl(self, cache, j, lengths, target, bt_row, ccfg):
        """Paged twin of ``_absorb_impl``: gather slot j's tail blocks
        into ring order, fold the aged entries into its centroids, write
        back centroids/counts/cov only (the pool bytes are untouched —
        absorb never moves tail data)."""
        keys = attn.CLUSTERED_SLOT_KEYS

        def leaf(node):
            stacked = node["k_cents"].ndim == 5
            ax = 1 if stacked else 0
            with tele_mod.scope("compact_gather"):
                sub = {k: jax.lax.dynamic_slice_in_dim(node[k], j, 1,
                                                       axis=ax)
                       for k in keys}
                kt = self._gather_tail_rows(node["k_tail"], bt_row)
                vt = self._gather_tail_rows(node["v_tail"], bt_row)
            if stacked:
                lyr = node["k_cents"].shape[0]
                flat = {k: v.reshape((lyr,) + v.shape[2:])
                        for k, v in sub.items()}
                flat["k_tail"], flat["v_tail"] = kt, vt
                got = kv_compress.absorb_chunk(
                    flat, jnp.full((lyr,), lengths, jnp.int32),
                    jnp.full((lyr,), target, jnp.int32), ccfg)
                got = {k: got[k][:, None] for k in keys}
            else:
                sub["k_tail"], sub["v_tail"] = kt[None], vt[None]
                got = kv_compress.absorb_chunk(
                    sub, jnp.full((1,), lengths, jnp.int32),
                    jnp.full((1,), target, jnp.int32), ccfg)
            with tele_mod.scope("compact_write"):
                return dict(node, **{
                    k: jax.lax.dynamic_update_slice_in_dim(
                        node[k], got[k].astype(node[k].dtype), j, axis=ax)
                    for k in keys})

        def walk(node):
            if _is_clustered_kv(node):
                return leaf(node)
            if isinstance(node, dict):
                return {k: walk(v) for k, v in node.items()}
            if isinstance(node, list):
                return [walk(v) for v in node]
            return node

        return walk(cache)

    def _compact_paged_impl(self, cache, lengths, bt, ccfg):
        """Paged twin of ``compact_kv``'s recompaction: gather every
        slot's tail blocks into the dense ring layout through the block
        table (B, T), re-compact incrementally, keep the pool bytes and
        write back centroids/counts/cov.  The engine then returns blocks
        whose positions the new frontier covers to the free list (host
        side — the give-back is bookkeeping, not data movement)."""
        keys = attn.CLUSTERED_SLOT_KEYS

        def leaf(node):
            stacked = node["k_cents"].ndim == 5
            with tele_mod.scope("compact_gather"):
                kt = self._gather_tail_rows(node["k_tail"], bt)
                vt = self._gather_tail_rows(node["v_tail"], bt)
            if stacked:
                lyr, b = node["k_cents"].shape[:2]
                flat = {k: node[k].reshape((lyr * b,) + node[k].shape[2:])
                        for k in keys}
                flat["k_tail"] = kt.reshape((lyr * b,) + kt.shape[2:])
                flat["v_tail"] = vt.reshape((lyr * b,) + vt.shape[2:])
                ln = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32),
                                      (lyr, b)).reshape(-1)
                got = kv_compress.recompact_clustered(flat, ln, ccfg)
                got = {k: got[k].reshape((lyr, b) + got[k].shape[1:])
                       for k in keys}
            else:
                b = node["k_cents"].shape[0]
                dense = {k: node[k] for k in keys}
                dense["k_tail"], dense["v_tail"] = kt, vt
                ln = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
                got = kv_compress.recompact_clustered(dense, ln, ccfg)
            with tele_mod.scope("compact_write"):
                return dict(node, **{k: got[k].astype(node[k].dtype)
                                     for k in keys})

        def walk(node):
            if _is_clustered_kv(node):
                return leaf(node)
            if isinstance(node, dict):
                return {k: walk(v) for k, v in node.items()}
            if isinstance(node, list):
                return [walk(v) for v in node]
            return node

        return walk(cache)

    # admission-time conversion of a fresh (B=1) exact prefill cache into
    # the engine's clustered layout; ``template`` marks which leaves are
    # clustered (G layers) vs exact (sliding-window rings, SSM state, ...)
    def _clusterize(self, c1, template, plen: int, ccfg):
        C, R = ccfg.n_clusters, ccfg.keep_recent

        def leaf(src, tpl):
            if not (_is_clustered_kv(tpl) and _is_exact_kv(src)):
                return src
            k, v = src["k"], src["v"]
            stacked = k.ndim == 5            # (L, 1, S, H, Dh) scan region
            if stacked:
                l = k.shape[0]
                k = k.reshape((l,) + k.shape[2:])
                v = v.reshape((l,) + v.shape[2:])
            b = k.shape[0]
            # the tail-only (cov=0) form is loss-free only while every
            # prompt position survives in the ring until the first global
            # compaction, which may be up to ``refresh`` steps away —
            # longer prompts must build centroids at admission
            if plen <= R - ccfg.refresh:
                if k.shape[1] < R:
                    # quota layouts size the ring at max_seq; a prefill
                    # cache shorter than that (bucketed prompt) zero-pads
                    # up — the extra offsets sit outside [0, plen) and
                    # stay masked until decode writes them
                    pad = [(0, 0)] * k.ndim
                    pad[1] = (0, R - k.shape[1])
                    k = jnp.pad(k, pad)
                    v = jnp.pad(v, pad)
                dt = k.dtype
                h, dh = k.shape[2], k.shape[3]
                out = {
                    "k_cents": jnp.zeros((b, C, h, dh), dt),
                    "v_cents": jnp.zeros((b, C, h, dh), dt),
                    "counts": jnp.zeros((b, C, h), jnp.float32),
                    # positions 0..plen-1 sit at ring slots 0..plen-1
                    "k_tail": k[:, :R],
                    "v_tail": v[:, :R],
                    "cov": jnp.zeros((b,), jnp.int32),
                }
            else:
                lengths = jnp.full((b,), plen, jnp.int32)
                out = kv_compress.compress_cache_batched(k, v, lengths, ccfg)
            if stacked:
                out = {kk: vv[:, None] for kk, vv in out.items()}
            return out

        def walk(src, tpl):
            if _is_clustered_kv(tpl):
                return leaf(src, tpl)
            if isinstance(src, dict):
                return {kk: walk(vv, tpl[kk]) for kk, vv in src.items()}
            if isinstance(src, list):
                return [walk(vv, tt) for vv, tt in zip(src, tpl)]
            return src

        return walk(c1, template)

    # scatter one (B=1) request cache into engine slot j.  prefix/tail
    # leaves carry batch on axis 0, scan-stacked leaves on axis 1.
    def _write_slot_impl(self, dst, src, j):
        def upd(axis):
            def f(d, s):
                idx = (0,) * axis + (j,) + (0,) * (d.ndim - axis - 1)
                return jax.lax.dynamic_update_slice(d, s.astype(d.dtype), idx)
            return f

        out = dict(dst)
        for key in ("prefix", "tail"):
            out[key] = [jax.tree.map(upd(0), dc, sc)
                        for dc, sc in zip(dst[key], src[key])]
        if "scan" in dst:
            out["scan"] = jax.tree.map(upd(1), dst["scan"], src["scan"])
        return out

    # ------------------------------------------------------------------
    # memory management: batched clustered-KV compaction
    # ------------------------------------------------------------------

    def compact_kv(self, cache, t, ccfg: "kv_compress.KVCompressConfig"):
        """Compress every global-attention layer's KV into clustered form
        (median centroids + counts + exact tail ring) in single jitted
        vmap-over-(batch ⊕ head) calls — no Python loop over batch, head,
        or stacked layer.  Exact leaves are compressed from scratch;
        already-clustered leaves are incrementally re-compacted with
        warm-started centroids (streaming update between decode bursts).
        ``t`` is a scalar length or a per-slot (B,) vector.

        Only leaves that a clustered-mode cache would hold in clustered
        form (global-attention layers) are touched — sliding-window ring
        buffers, SSM/RG-LRU state, and int8 caches pass through, guided
        by a structural template (shapes only, nothing allocated)."""
        tkey = (ccfg.n_clusters, ccfg.keep_recent)
        template = self._compact_templates.get(tkey)
        if template is None:
            template = jax.eval_shape(
                lambda: tfm.init_cache(
                    self.cfg, 1, self.scfg.max_seq, kv_mode="clustered",
                    kv_clusters=ccfg.n_clusters, kv_tail=ccfg.keep_recent))
            self._compact_templates[tkey] = template

        def lengths_for(b):
            return jnp.broadcast_to(jnp.asarray(t, jnp.int32), (b,))

        def compress_exact(node):
            k, v = node["k"], node["v"]
            if k.shape[-3] <= ccfg.n_clusters + ccfg.keep_recent:
                return node  # not worth compressing
            stacked = k.ndim == 5            # (L, B, S, H, Dh) scan region
            if stacked:
                l, b = k.shape[:2]
                lengths = jnp.broadcast_to(lengths_for(b), (l, b)).reshape(-1)
                out = kv_compress.compress_cache_batched(
                    k.reshape((l * b,) + k.shape[2:]),
                    v.reshape((l * b,) + v.shape[2:]), lengths, ccfg)
                return {kk: vv.reshape((l, b) + vv.shape[1:])
                        for kk, vv in out.items()}
            return kv_compress.compress_cache_batched(
                k, v, lengths_for(k.shape[0]), ccfg)

        def recompact(node):
            stacked = node["k_cents"].ndim == 5
            if stacked:
                l, b = node["k_cents"].shape[:2]
                flat = {kk: vv.reshape((l * b,) + vv.shape[2:])
                        for kk, vv in node.items()}
                lengths = jnp.broadcast_to(lengths_for(b), (l, b)).reshape(-1)
                out = kv_compress.recompact_clustered(flat, lengths, ccfg)
                return {kk: vv.reshape((l, b) + vv.shape[1:])
                        for kk, vv in out.items()}
            return kv_compress.recompact_clustered(
                node, lengths_for(node["k_cents"].shape[0]), ccfg)

        def walk(node, tpl):
            if _is_clustered_kv(tpl):
                if _is_clustered_kv(node):
                    return recompact(node)
                if _is_exact_kv(node) and node["k"].ndim in (4, 5):
                    return compress_exact(node)
                return node
            if isinstance(node, dict) and isinstance(tpl, dict):
                return {kk: walk(vv, tpl.get(kk)) for kk, vv in node.items()}
            if isinstance(node, list) and isinstance(tpl, list):
                return [walk(vv, tt) for vv, tt in zip(node, tpl)]
            return node

        return walk(cache, template)

    # ------------------------------------------------------------------
    # static batch-at-a-time path (baseline for the serve benchmark)
    # ------------------------------------------------------------------

    def _serve_static(self, requests, prompts) -> List[Completion]:
        plan = self._plan(requests)
        by_uid = {r.uid: r for r in requests}
        out: List[Completion] = []
        for batch_uids in plan.batches:
            out.extend(self._serve_batch(batch_uids, by_uid, prompts))
        self.metrics.begin_serve()
        self.metrics.gauge(
            "plan_waste", "padding waste of the static batch plan"
        ).set(plan.waste)
        self.last_stats = self.metrics.flat_view()
        return out

    def _serve_batch(self, uids, by_uid, prompts) -> List[Completion]:
        cfg, scfg = self.cfg, self.scfg
        reqs = [by_uid[u] for u in uids]
        plen = max(r.prompt_len for r in reqs)
        gen = max(r.max_new_tokens for r in reqs)
        b = len(reqs)
        toks = np.zeros((b, plen), np.int32)
        for i, r in enumerate(reqs):
            p = prompts[r.uid][-plen:]
            toks[i, plen - len(p):] = p  # left-pad

        t0 = time.perf_counter()
        logits, cache = self._prefill(self.params, jnp.asarray(toks),
                                      jnp.int32(plen - 1))
        jax.block_until_ready(logits)
        t1 = time.perf_counter()

        new = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        gen_toks = [new]
        for i in range(gen - 1):
            logits, cache = self._decode(self.params, cache, new,
                                         jnp.int32(plen + i))
            new = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            gen_toks.append(new)
        jax.block_until_ready(new)
        t2 = time.perf_counter()

        gen_arr = np.concatenate([np.asarray(g) for g in gen_toks], axis=1)
        outs = []
        for i, r in enumerate(reqs):
            outs.append(Completion(
                uid=r.uid,
                tokens=gen_arr[i, :r.max_new_tokens].tolist(),
                prefill_ms=(t1 - t0) * 1e3 / b,
                decode_ms=(t2 - t1) * 1e3 / b))
        return outs
