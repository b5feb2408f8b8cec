"""Mosaic compile rehearsals: every Pallas kernel compiles for a described
TPU v5e at qwen3-4b widths (Hq=32, Hkv=8, Dh=128; C=32 centroids, a
128-position tail ring in 16-position blocks; bf16 KV).

Interpret mode (the CPU tests) checks what a kernel computes; only the
TPU compiler checks block tiling, SMEM/VMEM placement and lowering rules.
The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and the xdist workers all import
this file.  Nothing here runs a kernel."""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.bitserial_median import grouped_median_pallas
from repro.kernels.clustered_decode import clustered_decode_pallas
from repro.kernels.distance_argmin import distance_argmin_pallas
from repro.kernels.paged_clustered_decode import paged_clustered_decode_pallas

HQ, HKV, DH, C, R, BS = 32, 8, 128, 32, 128, 16
B = 8          # decode slots
L = 64         # prefill chunk rows in a mixed launch
N = 128        # packed rows: one chunk + every decode slot, pow2 bucket


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep it out entirely
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _decode_shapes(q_shape):
    bf = jnp.bfloat16
    return [(q_shape, bf), ((B, C, HKV, DH), bf), ((B, C, HKV, DH), bf),
            ((B, C, HKV), jnp.float32), ((B, R, HKV, DH), bf),
            ((B, R, HKV, DH), bf), ((B,), jnp.int32), ((B,), jnp.int32),
            ((B,), jnp.int32)]


@pytest.mark.parametrize("q_shape", [(B, HQ, DH), (B, L, HQ, DH)],
                         ids=["decode", "mixed"])
def test_clustered_decode(chip, q_shape):
    fn = functools.partial(clustered_decode_pallas, scale=DH ** -0.5,
                           interpret=False)
    _compile(fn, chip, *_decode_shapes(q_shape))


@pytest.mark.parametrize("n,width", [(32, 1), (N, L)],
                         ids=["decode", "mixed"])
def test_paged_clustered_decode(chip, n, width):
    # the engine's two launch shapes: a pure-decode bucket of 32 rows, and
    # a mixed bucket of 128 rows scoring chunks of up to 64 rows
    bf = jnp.bfloat16
    nb = B * (R // BS) + 1
    fn = functools.partial(paged_clustered_decode_pallas, scale=DH ** -0.5,
                           width=width, interpret=False)
    _compile(fn, chip, ((n, HQ, DH), bf), ((B, C, HKV, DH), bf),
             ((B, C, HKV, DH), bf), ((B, C, HKV), jnp.float32),
             ((nb, BS, HKV, DH), bf), ((nb, BS, HKV, DH), bf),
             ((n,), jnp.int32), ((n, R // BS), jnp.int32),
             ((n,), jnp.int32), ((n,), jnp.int32), ((n,), jnp.int32),
             ((n,), jnp.int32))


def test_distance_argmin(chip):
    # one compaction head: C centroids ⊕ R ring entries as points
    fn = functools.partial(distance_argmin_pallas, metric="l2",
                           n_block=C + R, interpret=False)
    _compile(fn, chip, ((C + R, DH), jnp.float32), ((C, DH), jnp.float32))


@pytest.mark.parametrize("n,bits", [(C + R, 16), (ops.MAX_KERNEL_POINTS, 32)],
                         ids=["compaction", "max-points"])
def test_bitserial_median(chip, n, bits):
    fn = functools.partial(grouped_median_pallas, k=C, bits=bits,
                           interpret=False)
    _compile(fn, chip, ((n, DH), jnp.uint32), ((n,), jnp.int32),
             ((n,), jnp.float32))
