"""Layer-state families: *what* state a layer carries per slot, decoupled
from the ring-KV plumbing that stores it.

The serving tower grew up assuming every layer's per-slot state is a
position-indexed KV ring — dense, quantized, or clustered-with-paged-tails
— so admission, chunked absorb, compaction cadence, swap payloads, and the
end-of-serve invariants all reached straight into ring mechanics.  That
welded the engine to attention layers and rejected ``mamba2_2_7b`` /
``recurrentgemma_9b`` at the gate even though their model code exists.

This module names the distinction the same way :mod:`repro.core.retention`
named "what the cache retains":

* :class:`RingKVState` — position-indexed KV rings ('G' global attention,
  clustered/exact/quantized, optionally paged into pool blocks; 'L'
  sliding-window dense rings).  Grows with the stream; positions retire
  under a :class:`~repro.core.retention.RetentionPolicy`; tail bytes may
  live in shared pool blocks tracked by the block table.
* :class:`RecurrentState` — fixed-size running state per slot ('M' Mamba2
  SSD ``(conv, ssm)``; 'R' RG-LRU ``(conv, h)``).  Advanced inside the
  same mixed prefill+decode launch, one token at a time; nothing ever
  retires (see :class:`~repro.core.retention.RecurrentRetention`); never
  pool-backed, so block tables skip it entirely and its swap/prefix
  payload is the whole (small) state, checkpointed at chunk boundaries
  through the same opaque slot-snapshot format the clustered summaries
  use.

The engine asks families three questions: which kinds they cover
(:func:`family_of_kind`, :func:`families_for`), which cache leaves belong
to them (:func:`is_ring_leaf`, :func:`is_recurrent_leaf`), and how many
bytes a slot's state costs (:func:`recurrent_state_bytes`,
:func:`ring_tail_bytes_per_token`) — the Mettu–Plaxton cheapest-first
victim selection prices heterogeneous slots as
``mapped_blocks · block_bytes ⊕ recurrent_state_bytes``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

#: Per-leaf dict keys identifying a recurrent-state cache leaf.  Mamba2
#: carries {"conv", "ssm"}; RG-LRU carries {"conv", "h"}.  Snapshot /
#: restore / swap move *every* key of the leaf (the whole state is the
#: checkpoint — there is no tail to leave behind in pool blocks).
RECURRENT_LEAF_KEYS: Tuple[Tuple[str, ...], ...] = (("conv", "ssm"),
                                                    ("conv", "h"))

RING_KINDS = frozenset("GL")
RECURRENT_KINDS = frozenset("MR")


def family_of_kind(kind: str) -> str:
    """'ring' | 'recurrent' for a layer_pattern kind character."""
    if kind in RING_KINDS:
        return "ring"
    if kind in RECURRENT_KINDS:
        return "recurrent"
    raise ValueError(f"unknown layer kind {kind!r}")


@dataclass(frozen=True)
class RingKVState:
    """Ring-family descriptor: position-indexed KV, retention-governed."""

    kinds: frozenset
    family = "ring"
    pool_backed = True      # clustered tails / quota blocks live in the pool
    fixed_size = False      # state grows with the stream
    retirable = True        # positions retire behind a RetentionPolicy


@dataclass(frozen=True)
class RecurrentState:
    """Recurrent-family descriptor: fixed-size running state per slot."""

    kinds: frozenset
    family = "recurrent"
    pool_backed = False     # never in pool blocks; block tables skip it
    fixed_size = True       # (conv, ssm) / (conv, h) — constant per slot
    retirable = False       # nothing to retire; checkpoint, don't ring


@dataclass(frozen=True)
class LayerStateFamilies:
    """Which state families a config's layer pattern instantiates."""

    ring: RingKVState
    recurrent: RecurrentState

    @property
    def has_ring(self) -> bool:
        return bool(self.ring.kinds)

    @property
    def has_recurrent(self) -> bool:
        return bool(self.recurrent.kinds)

    @property
    def mixed(self) -> bool:
        return self.has_ring and self.has_recurrent


def families_for(cfg) -> LayerStateFamilies:
    """Classify a :class:`~repro.models.config.ModelConfig`'s layers.

    The unrolled MoE prefix layers (DeepSeek-style) are always global
    attention, so any ``moe.n_dense_layers > 0`` forces the ring family
    on even when the repeating pattern itself is attention-free.
    """
    kinds = set(cfg.layer_pattern)
    if cfg.moe is not None and cfg.moe.n_dense_layers > 0:
        kinds.add("G")
    unknown = kinds - RING_KINDS - RECURRENT_KINDS
    if unknown:
        raise ValueError(f"unknown layer kinds {sorted(unknown)!r} in "
                         f"pattern {cfg.layer_pattern!r}")
    return LayerStateFamilies(
        ring=RingKVState(kinds=frozenset(kinds & RING_KINDS)),
        recurrent=RecurrentState(kinds=frozenset(kinds & RECURRENT_KINDS)),
    )


# ---------------------------------------------------------------------------
# cache-leaf classification (shared by the engine's pytree walks)
# ---------------------------------------------------------------------------


def is_recurrent_leaf(node) -> bool:
    """A recurrent-state cache leaf: {"conv", "ssm"} or {"conv", "h"}."""
    return (isinstance(node, dict) and "conv" in node
            and ("ssm" in node or "h" in node))


def is_ring_leaf(node) -> bool:
    """A ring-family cache leaf: exact {"k","v"(,scales)}, clustered
    {"k_cents", ...}, or a window ring (same exact layout)."""
    return isinstance(node, dict) and ("k" in node or "k_cents" in node)


def recurrent_leaf_stacked(node) -> bool:
    """True when the leaf carries a leading ``lax.scan`` layer dim.

    Unstacked conv buffers are (B, k-1, C) / (B, 3, W) — 3 axes; the
    scan-stacked variant prepends the repeat dim.
    """
    return node["conv"].ndim == 4


# ---------------------------------------------------------------------------
# per-family byte accounting
# ---------------------------------------------------------------------------


def _walk_leaves(cache, pred):
    # an explicit stack, not a recursive closure: a closure that calls
    # itself is a reference cycle, which would keep the leaves (and their
    # device buffers) alive after the call until the cyclic collector runs
    out, stack = [], [cache]
    while stack:
        node = stack.pop()
        if isinstance(node, dict):
            if pred(node):
                out.append(node)
            else:
                stack.extend(reversed(list(node.values())))
        elif isinstance(node, (list, tuple)):
            stack.extend(reversed(node))
    return out


def recurrent_state_bytes(cache, n_slots: int) -> int:
    """Total bytes of recurrent state one slot carries across all layers.

    Every recurrent leaf is slot-major (slot axis 0 unstacked, axis 1
    under a scan-stacked layer dim), so per-slot bytes are exactly
    ``total_bytes / n_slots``.  This is the swap/victim price of the
    recurrent family: the whole state moves, every time, and never
    shrinks.
    """
    total = 0
    for leaf in _walk_leaves(cache, is_recurrent_leaf):
        for k in leaf:
            a = leaf[k]
            total += int(a.size) * int(a.dtype.itemsize)
    return total // max(int(n_slots), 1)


def ring_state_bytes(cache, n_slots: int) -> int:
    """Bytes of dense ring-family state one slot carries (centroid
    summaries, dense/window rings, scales) — excludes pool-backed tail
    blocks, which are priced per mapped block by the engine."""
    total = 0
    for leaf in _walk_leaves(cache, is_ring_leaf):
        for k, a in leaf.items():
            if k in ("k_tail", "v_tail"):
                # tail payloads are priced separately: paged tails are
                # pool-global (no slot axis, priced per mapped block by
                # the engine); dense tails ride the ring ceiling
                continue
            total += int(a.size) * int(a.dtype.itemsize)
    return total // max(int(n_slots), 1)
