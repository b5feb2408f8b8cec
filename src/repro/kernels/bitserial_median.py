"""Pallas TPU kernel: VMEM-resident bit-serial grouped median.

This is the paper's in-situ accelerator mapped to the TPU memory hierarchy:
the fixed-point tile is read from HBM into VMEM **once** and the whole
B-bit majority scan runs against the resident tile — the analogue of the
RRAM arrays computing the majority vote in place instead of streaming the
operands to the core B times.

Layout (per grid instance):
  u      (N, TD)  int32   — unsigned-ordered fixed-point bits, full point
                            axis resident (the paper's "limited-size array";
                            the VMEM capacity plays the role of the array
                            size limit; ops.py falls back to the two-level
                            reduction-tree path above the VMEM limit)
  assign (N, 1)   int32   — cluster ids (the paper's P/I inclusion predicate)
  w      (N, 1)   f32     — per-point weights (mask / merge counts)
  med    (K, TD)  int32   — per-cluster median bits (output)

Grid: (D // TD,).  K is a compile-time constant.  Per bit the vote count is
a one-hot matmul (MXU): cnt1[k, d] = Σ_i onehot[i, k] · eff[i, d]; the
broadcast of the majority decision back to the points is a second matmul
(avoids dynamic gather, which Mosaic dislikes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(u_ref, assign_ref, w_ref, med_ref, *, k: int, bits: int):
    u = u_ref[...]                      # (N, TD) int32 bit pattern
    assign = assign_ref[...]            # (N, 1) int32
    w = w_ref[...]                      # (N, 1) f32
    n = u.shape[0]

    kids = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)       # (1, K)
    onehot01 = (assign == kids).astype(jnp.float32)             # (N, K)
    onehot = onehot01 * w                                       # weighted votes
    total = jnp.sum(onehot, axis=0)                             # (K,)

    active0 = jnp.ones(u.shape, jnp.float32)
    forced0 = jnp.zeros(u.shape, jnp.float32)
    med0 = jnp.zeros(med_ref.shape, jnp.int32)

    def body(i, carry):
        active, forced, med = carry
        b = jnp.int32(bits - 1) - i
        # logical shift of the int32 bit pattern == the uint32 scan; the
        # 0/1 plane goes through int32 (Mosaic has no uint32 → f32 cast)
        bit = (jax.lax.shift_right_logical(u, b) & 1).astype(jnp.float32)
        eff = active * bit + (1.0 - active) * forced
        # vote count: (K, N) x (N, TD) on the MXU
        cnt1 = jax.lax.dot_general(
            onehot, eff, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                  # (K, TD)
        mbit = (cnt1 * 2.0 > total[:, None]).astype(jnp.float32)  # (K, TD)
        med = med | jnp.where(mbit > 0.5, jax.lax.shift_left(jnp.int32(1), b),
                              jnp.int32(0))
        # broadcast decision back to points: (N, K) x (K, TD)
        mper = jax.lax.dot_general(
            onehot01, mbit, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                  # (N, TD)
        dev = active * jnp.abs(bit - mper)                       # 1 where minority
        forced = dev * bit + (1.0 - dev) * forced
        active = active * (1.0 - dev)
        return active, forced, med

    _, _, med = jax.lax.fori_loop(0, bits, body, (active0, forced0, med0))
    med_ref[...] = med


def grouped_median_pallas(u, assign, weights, k: int, *, bits: int = 32,
                          d_block: int = 128, interpret: bool = False):
    """u (N, D) uint32, assign (N,) int32, weights (N,) f32 → (k, D) uint32.

    The full point axis is VMEM-resident; the grid tiles D only.  Callers
    above the VMEM budget use the two-level reduction-tree path in ops.py.
    The kernel scans the int32 view of ``u``'s bits, which is bitwise the
    uint32 scan.
    """
    n, d = u.shape
    pad_d = (-d) % d_block
    if pad_d:
        u = jnp.pad(u, ((0, 0), (0, pad_d)))
    dp = d + pad_d
    u32 = jax.lax.bitcast_convert_type(u.astype(jnp.uint32), jnp.int32)
    assign2 = assign.reshape(n, 1).astype(jnp.int32)
    w2 = weights.reshape(n, 1).astype(jnp.float32)

    grid = (dp // d_block,)
    out = pl.pallas_call(
        functools.partial(_kernel, k=k, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, d_block), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((n, 1), lambda j: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((n, 1), lambda j: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((k, d_block), lambda j: (0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((k, dp), jnp.int32),
        interpret=interpret,
    )(u32, assign2, w2)
    return jax.lax.bitcast_convert_type(out[:, :d], jnp.uint32)
