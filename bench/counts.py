"""Operations and bytes the work needs, and the chip's published peaks.

Counts come from shapes and the request schedule alone, never from what a
kernel's grid happens to launch, so they read the same work whatever
implements it.  The model's sizes come from its architecture module's
record (``bench/arch/__init__.py``): ``n_heads``, ``n_kv_heads``,
``head_dim`` and ``attn_layers`` of the layers that read the paged
clustered kernel, and ``matmul_params`` per token.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Peaks(NamedTuple):
    flops: float       # bf16 FLOP/s per chip
    hbm_bw: float      # HBM bytes/s per chip
    hbm_bytes: float   # HBM capacity per chip


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 16 GB of HBM
# at 819 GB/s per chip.  Keyed by jax.devices()[0].device_kind.
PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9),
}


def peaks_for(device_kind: str) -> Peaks:
    """Published peaks of ``device_kind``; a kind not in the table raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None


def request_rows(events: np.ndarray, state_of: np.ndarray, clusters: int,
                 prompt_len: int, chunk: int) -> np.ndarray:
    """(qpos1, cov, live centroids, reads) of every position a request
    feeds, from its schedule (``reference.schedule``).  Live centroids are
    counted as min(clusters, cov): a fold seeds every row from a point and
    never leaves more rows alive than positions it covers.  ``reads`` is 1
    on the row that reads its launch's keys for the request: the last row
    of each ``chunk``-row prompt piece (the piece's rows share one memory
    state, and the last attends the union of what they attend) and every
    decode row."""
    covs = np.concatenate([[0], events[:, 2]]).astype(np.int64)
    cov = covs[state_of]
    pos = np.arange(len(state_of))
    reads = ((pos >= prompt_len - 1) | ((pos + 1) % chunk == 0))
    return np.stack([pos + 1, cov, np.minimum(cov, clusters),
                     reads.astype(np.int64)], axis=1)


def attended(rows: np.ndarray) -> np.ndarray:
    """Keys each row attends: its live centroids plus the exact positions
    from its frontier to itself.  Padding rows (qpos1 == 0) attend none."""
    qpos1, cov, live = rows[:, 0], rows[:, 1], rows[:, 2]
    return np.where(qpos1 > 0, live + np.maximum(qpos1 - cov, 0), 0)


def paged_decode_work(rows: np.ndarray, m, kv_bytes: int = 2,
                      act_bytes: int = 2) -> tuple:
    """(FLOPs, bytes) attention needs for ``rows`` in every layer that
    reads the paged clustered kernel (``m.attn_layers``): every
    real row's query heads against the keys it attends; the K and V of
    those keys at the cache dtype read once per request and launch (on
    the row that ``reads``), plus each query head's q in and output out."""
    n_keys = attended(rows)
    real = rows[:, 0] > 0
    flops = 4.0 * m.n_heads * m.head_dim * n_keys.sum()
    kv = 2.0 * m.n_kv_heads * m.head_dim * kv_bytes * (n_keys * rows[:, 3]).sum()
    qo = 2.0 * m.n_heads * m.head_dim * act_bytes * real.sum()
    return m.attn_layers * flops, m.attn_layers * (kv + qo)


def step_flops(rows: np.ndarray, m) -> float:
    """Model FLOPs of the rows: 2 x ``m.matmul_params`` per real row plus
    the attention over the positions each row attends."""
    real = int((rows[:, 0] > 0).sum())
    attn, _ = paged_decode_work(rows, m)
    return 2.0 * m.matmul_params * real + attn


def roofline_seconds(flops: float, nbytes: float, peaks: Peaks) -> tuple:
    """(least seconds, which bound sets it)."""
    tf, tb = flops / peaks.flops, nbytes / peaks.hbm_bw
    return (tf, "flops") if tf >= tb else (tb, "bytes")
