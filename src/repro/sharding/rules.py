"""Logical-axis sharding rules (MaxText-style), divisibility-aware.

Models annotate intermediates with *logical* axis names via ``annotate``;
a rules context (installed by the launcher around tracing) maps logical
names to mesh axes and applies ``with_sharding_constraint``.  Outside a
context ``annotate`` is a no-op, so model code never depends on a mesh.

Parameter partition specs are derived from leaf *names* + shapes
(``param_spec``) with the same divisibility rule: a dimension is sharded
only when its size divides evenly; otherwise it is replicated (never
crash — small models on big meshes degrade gracefully to partial TP).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import re
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass
class Rules:
    mesh: Mesh
    table: dict                      # logical axis -> mesh axis tuple | None
    fsdp: bool = False               # shard params/opt-state over data axis

    def axes_for(self, logical: Optional[str], dim: int):
        if logical is None:
            return None
        axes = self.table.get(logical)
        if not axes:
            return None
        total = math.prod(self.mesh.shape[a] for a in axes)
        if dim % total != 0:
            # try a prefix of the axes (e.g. batch over ("pod","data") but
            # dim only divisible by pod count)
            for cut in range(len(axes) - 1, 0, -1):
                sub = axes[:cut]
                t = math.prod(self.mesh.shape[a] for a in sub)
                if dim % t == 0:
                    return tuple(sub)
            return None
        return tuple(axes)


_ACTIVE: list = []


@contextlib.contextmanager
def use_rules(rules: Rules):
    _ACTIVE.append(rules)
    try:
        yield rules
    finally:
        _ACTIVE.pop()


def current_rules() -> Optional[Rules]:
    return _ACTIVE[-1] if _ACTIVE else None


def annotate(x, *logical_axes):
    """Constrain intermediate ``x`` (ndim == len(logical_axes)) if a rules
    context is active; otherwise identity.  A mesh axis may appear at most
    once — the first (leftmost) logical axis that claims it wins (e.g. the
    MoE expert dim takes ``model`` and the expert-FFN dim then replicates)."""
    r = current_rules()
    if r is None:
        return x
    assert x.ndim == len(logical_axes), (x.shape, logical_axes)
    used = set()
    dims = []
    for ax, d in zip(logical_axes, x.shape):
        res = r.axes_for(ax, d)
        tup = (res,) if isinstance(res, str) else tuple(res or ())
        if not tup or any(a in used for a in tup):
            dims.append(None)
        else:
            used.update(tup)
            dims.append(res)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(r.mesh, P(*dims)))


def annotate_prio(x, logical_axes, priority):
    """Like ``annotate`` but resolves logical axes in ``priority`` order
    (indices into logical_axes), so e.g. the MoE expert dim claims the
    (model, data) axes before the dispatch-shard dim claims data."""
    r = current_rules()
    if r is None:
        return x
    assert x.ndim == len(logical_axes), (x.shape, logical_axes)
    used = set()
    dims = [None] * x.ndim
    order = list(priority) + [i for i in range(x.ndim) if i not in priority]
    for i in order:
        ax = logical_axes[i]
        if ax is None:
            continue
        res = r.axes_for(ax, x.shape[i])
        tup = (res,) if isinstance(res, str) else tuple(res or ())
        if not tup or any(a in used for a in tup):
            continue
        used.update(tup)
        dims[i] = res
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(r.mesh, P(*dims)))


# ---------------------------------------------------------------------------
# Mesh-axis tables
# ---------------------------------------------------------------------------


def default_table(multi_pod: bool, *, seq_shard: bool = False) -> dict:
    batch = ("pod", "data") if multi_pod else ("data",)
    model = ("model",)
    t = {
        "batch": batch,
        "seq": None,
        "kvseq": batch if seq_shard else None,  # sequence-parallel KV (SP)
        "d_model": None,
        "heads": model,
        "kv_heads": model,
        "ff": model,
        "vocab": model,
        # full expert parallelism: spread experts over model×data when the
        # count divides (DeepSeek 256 → 1 expert/chip; axes_for falls back
        # to ("model",) then replication for awkward counts like Qwen2's 60)
        "experts": ("model", "data"),
        "expert_ff": model,
        "expert_cap": batch,
        "lru": model,
        "ssm_heads": model,
        "state": None,
        "head_dim": None,
    }
    return t


# ---------------------------------------------------------------------------
# Parameter partition specs (name-based)
# ---------------------------------------------------------------------------

# rule: regex on the leaf path -> logical axes for the TRAILING dims
_PARAM_RULES = [
    # MoE expert banks: (E, d, f) / (E, f, d)
    (re.compile(r"moe/(w_gate|w_up)$"), ("experts", "fsdp", "expert_ff")),
    (re.compile(r"moe/w_down$"), ("experts", "expert_ff", "fsdp")),
    (re.compile(r"moe/router$"), (None, None)),
    (re.compile(r"moe/bias$"), (None,)),
    # embeddings / heads
    (re.compile(r"embed/table$"), ("vocab", "fsdp")),
    (re.compile(r"embed/head$"), ("fsdp", "vocab")),
    # attention projections
    (re.compile(r"(wq|wk|wv|wuq|wukv)$"), ("fsdp", "model_out")),
    (re.compile(r"(wdq|wdkv|wkr)$"), ("fsdp", None)),
    (re.compile(r"wo$"), ("model_out", "fsdp")),
    # mlp
    (re.compile(r"(w_gate|w_up)$"), ("fsdp", "ff")),
    (re.compile(r"w_down$"), ("ff", "fsdp")),
    # recurrent / ssm
    (re.compile(r"(wx|wg|wa_gate|wi_gate)$"), ("fsdp", "lru")),
    (re.compile(r"rg_out$"), ("lru", "fsdp")),
    (re.compile(r"in_proj$"), ("fsdp", "ssm_ch")),
    (re.compile(r"out_proj$"), ("ssm_ch", "fsdp")),
    (re.compile(r"frontend/proj$"), (None, "fsdp")),
]


def param_spec(path: str, shape: Sequence[int], rules: Rules) -> P:
    """Partition spec for parameter leaf ``path`` with ``shape``.

    Trailing dims follow the matched rule; extra leading dims (layer-stacking
    from scan) are unsharded.  ``fsdp`` resolves to the data axis when the
    rules enable it (ZeRO-style), else replicated.  ``model_out``/``ff`` etc.
    resolve to the model axis when divisible.
    """
    logical = None
    for rx, ax in _PARAM_RULES:
        if rx.search(path):
            logical = ax
            break
    if logical is None:
        return P()  # norms, biases, conv kernels, A_log… replicated

    def resolve(name, dim):
        if name is None:
            return None
        if name == "fsdp":
            if not rules.fsdp:
                return None
            axes = rules.table.get("batch") or ()
            # fsdp uses the data axis only (not pod — pods replicate params
            # unless fsdp spans pods; keep intra-pod to bound cross-pod
            # traffic, cross-pod handled by gradient compression)
            axes = tuple(a for a in axes if a == "data")
            total = math.prod(rules.mesh.shape[a] for a in axes) if axes else 0
            return axes if axes and dim % total == 0 else None
        if name == "experts":
            return rules.axes_for("experts", dim)
        if name in ("model_out", "ff", "expert_ff", "vocab", "lru",
                    "ssm_ch", "heads"):
            axes = ("model",)
            total = rules.mesh.shape["model"]
            return axes if dim % total == 0 else None
        axes = rules.table.get(name)
        if not axes:
            return None
        total = math.prod(rules.mesh.shape[a] for a in axes)
        return tuple(axes) if dim % total == 0 else None

    trailing = [resolve(n, d) for n, d in zip(logical, shape[-len(logical):])]
    lead = [None] * (len(shape) - len(logical))
    used = set()
    final = list(lead)
    # a mesh axis may appear at most once in a spec; drop duplicates (e.g.
    # fsdp=data colliding with expert_cap) keeping the first occurrence
    for ax in trailing:
        if ax is None:
            final.append(None)
            continue
        tup = (ax,) if isinstance(ax, str) else tuple(ax)
        if any(a in used for a in tup):
            final.append(None)
        else:
            used.update(tup)
            final.append(ax)
    return P(*final)


# ---------------------------------------------------------------------------
# Serving-cache partition specs (name-based, like params)
# ---------------------------------------------------------------------------

# KV-cache leaf name -> head-axis position counted from the END of the shape
_CACHE_HEAD_AXIS = {
    "k": 2, "v": 2,                                   # (…, S, H, Dh)
    "k_cents": 2, "v_cents": 2,                       # (…, C, H, Dh)
    "k_tail": 2, "v_tail": 2,                         # (…, R, H, Dh)
    "counts": 1,                                      # (…, C, H)
}


def cache_spec(path: str, shape: Sequence[int], rules: Rules) -> P:
    """Partition spec for one serving-cache leaf.

    Decode slots (the engine batch axis — axis 0, or axis 1 under the
    scan-stacked leading layer dim) partition over the rules' ``batch``
    mesh axes; KV head dims partition over the model axis.  Divisibility-
    aware like ``param_spec``: a dim that doesn't divide is replicated, so
    small models on big meshes degrade to partial parallelism instead of
    crashing.  Non-KV state (MLA latents, SSM/RG-LRU state, int8 scales)
    gets slot sharding only.

    Paged tail pools (runtime/kv_pool.py) flow through the same rule: a
    pool leaf ``k_tail (n_blocks, block_size, H, Dh)`` shards its leading
    block axis over the ``batch`` mesh axes — the pool is sized
    ``shards × pool_blocks``, NamedSharding partitions the axis
    contiguously, and the allocator hands each shard's slots only that
    shard's block-id range, so the pool shards over ``data`` exactly like
    the slots it backs (same for the scan-stacked ``(L, n_blocks, …)``
    form via the layer-dim shift).

    Retention-policy state (core/retention.py) needs no rules of its
    own: the device ``cov`` leaf FrontierRetention mirrors is batch-only
    (slot per data shard, like every per-slot scalar here), sliding-
    window 'L' rings are ordinary dense ``k``/``v`` ring leaves (window-
    sized, never pool-backed) that shard via ``_CACHE_HEAD_AXIS``, the
    per-row ``wlo`` window floors ship with the launch over ``data``
    like ``cov`` (kernels' shard_map specs), and WindowRetention /
    QuotaRetention bookkeeping is host-side numpy that never touches the
    mesh.
    """
    parts = path.split("/")
    name = parts[-1]
    stacked = parts[0] == "scan"
    dims: list = [None] * len(shape)
    used: set = set()

    def put(axis_pos: int, logical: str):
        if not 0 <= axis_pos < len(shape):
            return
        res = rules.axes_for(logical, shape[axis_pos])
        tup = (res,) if isinstance(res, str) else tuple(res or ())
        if tup and not any(a in used for a in tup):
            used.update(tup)
            dims[axis_pos] = res

    if name in ("k_scale", "v_scale"):                # (…, H) — no slot dim
        put(len(shape) - 1, "kv_heads")
        return P(*dims)
    put(1 if stacked else 0, "batch")
    head_off = _CACHE_HEAD_AXIS.get(name)
    if head_off is not None and len(shape) - head_off > (1 if stacked else 0):
        put(len(shape) - head_off, "kv_heads")
    return P(*dims)


def block_table_spec(shape: Sequence[int], rules: Rules) -> P:
    """Partition spec for the paged engine's block table ``(slots, T)``:
    rows follow the slots over the ``batch`` mesh axes (each shard sees
    only its own slots' rows — entries hold global block ids that the
    shard_map island rebases locally), ring-block columns replicated."""
    dims: list = [None] * len(shape)
    res = rules.axes_for("batch", shape[0])
    if res:
        dims[0] = res
    return P(*dims)


def place_block_tables(bt, rules: Rules):
    """Host-side mesh placement for the block table pushed each launch."""
    return jax.device_put(
        bt, NamedSharding(rules.mesh, block_table_spec(bt.shape, rules)))


def admission_spec(path: str, shape: Sequence[int], rules: Rules) -> P:
    """Partition spec for a B=1 admission-prefill cache leaf.

    A single request's cache can't shard its slot dim (size 1) and an
    array can't live on a strict subset of the jit's device set (jax
    requires one device assignment per computation), so the data-axis
    copy is unavoidable for the *blocking* admission path — but the
    kv-head dims CAN shard over the model axis, cutting the admission
    transfer volume by the model-parallel factor versus the old
    replicate-everything ``P()`` placement.  The chunked admission path
    removes the B=1 cache entirely (prompt KV streams into the already-
    sharded engine slots), which is the complete fix.
    """
    name = path.split("/")[-1]
    dims: list = [None] * len(shape)
    head_off = _CACHE_HEAD_AXIS.get(name)
    if name in ("k_scale", "v_scale"):
        head_off = 1
    if head_off is not None and len(shape) >= head_off:
        res = rules.axes_for("kv_heads", shape[len(shape) - head_off])
        if res:
            dims[len(shape) - head_off] = res
    return P(*dims)


def place_prefix_snapshot(snap, rules: Rules):
    """Mesh placement for a prefix-cache snapshot (one slot's clustered
    summary rows, ``transformer.clustered_slot_state``).

    The snapshot's slot dim is 1 so it cannot shard over ``data`` — the
    B=1 admission argument applies (one device assignment per jit) — but
    kv-head dims shard over ``model`` exactly like the admission specs,
    so a pinned snapshot costs ``1/model``-th of a dense slot row per
    device.  Note the asymmetry with the blocks the snapshot rides with:
    physical block ids are meaningful ONLY on the data shard that owns
    them (``block_table_spec`` partitions tables by slot, and the
    shard_map island rebases ids per shard), so the host-side prefix
    maps are kept strictly per data shard and an admission can only
    adopt entries registered by slots of its own shard — the snapshot is
    the one piece that crosses shards, and only because it is
    slot-agnostic summary state."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(snap)
    placed = [
        jax.device_put(leaf, NamedSharding(
            rules.mesh, admission_spec(_leaf_path(kp), leaf.shape, rules)))
        for kp, leaf in flat
    ]
    return jax.tree_util.tree_unflatten(treedef, placed)


def place_swap_payload(payload, rules: Rules):
    """Mesh placement for a swapped-out slot's host round-trip at resume
    time (runtime/scheduler.py): the clustered snapshot plus the
    gathered tail-ring block payloads.

    Tail payload leaves are ``(n_mapped_blocks, block_size, H, Dh)``
    (or layer-stacked with one extra leading axis) — the leading block
    axis indexes the *specific* blocks being scattered back, which land
    on whatever data shard the resuming slot lives on, so it cannot
    shard over ``data`` (same one-device-assignment argument as the B=1
    admission path).  Head dims shard over ``model`` exactly like
    ``admission_spec``, so the resume transfer costs ``1/model``-th of
    the payload per device — and a resume may land on a *different*
    shard than the swap-out (the payload is slot- and shard-agnostic
    host bytes; only pool block ids are shard-local, and those are
    re-allocated at resume)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(payload)
    placed = [
        jax.device_put(leaf, NamedSharding(
            rules.mesh, admission_spec(_leaf_path(kp), leaf.shape, rules)))
        for kp, leaf in flat
    ]
    return jax.tree_util.tree_unflatten(treedef, placed)


def place_admission(cache, rules: Rules):
    """Place a B=1 admission-prefill cache on the mesh with
    ``admission_spec`` layouts (model-sharded heads, minimal replication)
    before the donated slot-write scatters it into the engine cache."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    placed = [
        jax.device_put(leaf, NamedSharding(
            rules.mesh, admission_spec(_leaf_path(kp), leaf.shape, rules)))
        for kp, leaf in flat
    ]
    return jax.tree_util.tree_unflatten(treedef, placed)


def _leaf_path(kp) -> str:
    return "/".join(_key_str(k) for k in kp)


def shard_cache(cache, rules: Rules):
    """Place a serving cache onto the rules' mesh (host side: engine init
    and post-compaction re-placement use ``jax.device_put``)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    placed = [
        jax.device_put(leaf, NamedSharding(
            rules.mesh, cache_spec(_leaf_path(kp), leaf.shape, rules)))
        for kp, leaf in flat
    ]
    return jax.tree_util.tree_unflatten(treedef, placed)


def constrain_cache(cache, rules: Rules):
    """``with_sharding_constraint`` twin of ``shard_cache`` for use inside
    traced functions (decode / slot-write outputs keep stable layouts)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    out = [
        jax.lax.with_sharding_constraint(leaf, NamedSharding(
            rules.mesh, cache_spec(_leaf_path(kp), leaf.shape, rules)))
        for kp, leaf in flat
    ]
    return jax.tree_util.tree_unflatten(treedef, out)


def tree_param_specs(params, rules: Rules):
    """PartitionSpec pytree for a parameter pytree (path-aware)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for kp, leaf in flat:
        path = "/".join(_key_str(k) for k in kp)
        specs.append(param_spec(path, leaf.shape, rules))
    return jax.tree_util.tree_unflatten(treedef, specs)


# serve-time placement: only the leaves whose replication cost dominates
# are distributed; everything else replicates (the serving engine's
# annotate/shard_map islands shard the COMPUTE, and small replicated
# weights keep every decode launch free of parameter collectives)
_SERVING_DISTRIBUTED = re.compile(r"moe/(w_gate|w_up|w_down)$")


def serving_param_specs(params, rules: Rules):
    """PartitionSpec pytree for serve-time parameter placement.

    MoE routed-expert banks — by far the largest leaves in an MoE config
    (Qwen2-MoE: 60 experts × (d, f) per projection per layer) — are
    placed by ``param_spec``, which puts the expert dim on the ``model``
    axis (spilling onto ``data`` when the count divides, prefix-falling
    back to ``model`` alone for awkward counts like 60 on a 4-wide
    axis).  Every other leaf replicates, exactly as serving always did:
    attention/MLP weights are small enough that replication beats the
    gather traffic GSPMD would synthesize into each decode step.  Pure
    placement — no cache change, no compute change (ROADMAP item 5)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for kp, leaf in flat:
        path = "/".join(_key_str(k) for k in kp)
        specs.append(param_spec(path, leaf.shape, rules)
                     if _SERVING_DISTRIBUTED.search(path) else P())
    return jax.tree_util.tree_unflatten(treedef, specs)


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def shardings_from_specs(mesh: Mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))


def serving_param_shardings(params, mesh: Mesh):
    """NamedSharding pytree the serving engine places ``params`` with on
    ``mesh``: ``serving_param_specs`` under the mesh's default rules.
    ``params`` may be shapes (``jax.eval_shape``), so an initializer can
    build the weights in place."""
    rules = Rules(mesh, default_table("pod" in mesh.axis_names))
    return shardings_from_specs(mesh, serving_param_specs(params, rules))
