"""Per-kernel validation: Pallas (interpret=True on CPU) vs ref.py oracles,
swept across shapes and dtypes per the deliverable requirements."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantizer
from repro.kernels import ops, ref
from repro.kernels.bitserial_median import grouped_median_pallas
from repro.kernels.distance_argmin import distance_argmin_pallas


def _to_u(ints):
    return quantizer.to_unsigned_order(jnp.asarray(ints, jnp.int32))


class TestBitserialMedianKernel:
    @pytest.mark.parametrize("n,d,k", [
        (5, 1, 1), (8, 3, 2), (33, 7, 4), (64, 130, 3), (100, 12, 16),
    ])
    def test_sweep_shapes(self, n, d, k):
        rng = np.random.default_rng(n * d * k)
        x = rng.integers(-(2**20), 2**20, size=(n, d)).astype(np.int32)
        assign = rng.integers(0, k, size=(n,)).astype(np.int32)
        w = np.ones((n,), np.float32)
        med_u = grouped_median_pallas(_to_u(x), jnp.asarray(assign),
                                      jnp.asarray(w), k, interpret=True)
        med = np.asarray(quantizer.from_unsigned_order(med_u))
        expect, counts = ref.grouped_median_ref(x, assign, k)
        for c in range(k):
            if counts[c] > 0:
                np.testing.assert_array_equal(med[c], expect[c],
                                              err_msg=f"cluster {c}")

    @pytest.mark.parametrize("bits", [16, 32])
    def test_bit_widths(self, bits):
        rng = np.random.default_rng(bits)
        lim = 2 ** (bits - 2)
        x = rng.integers(-lim, lim, size=(17, 4)).astype(np.int32)
        assign = rng.integers(0, 3, size=(17,)).astype(np.int32)
        w = np.ones((17,), np.float32)
        u = quantizer.to_unsigned_order(jnp.asarray(x), bits=bits)
        med_u = grouped_median_pallas(u, jnp.asarray(assign),
                                      jnp.asarray(w), 3, bits=bits,
                                      interpret=True)
        med = np.asarray(quantizer.from_unsigned_order(med_u, bits=bits))
        expect, counts = ref.grouped_median_ref(x, assign, 3)
        for c in range(3):
            if counts[c] > 0:
                np.testing.assert_array_equal(med[c], expect[c])

    def test_weighted(self):
        rng = np.random.default_rng(7)
        x = rng.integers(-50, 50, size=(12, 5)).astype(np.int32)
        w = rng.integers(1, 4, size=(12,)).astype(np.float32)
        assign = np.zeros((12,), np.int32)
        med_u = grouped_median_pallas(_to_u(x), jnp.asarray(assign),
                                      jnp.asarray(w), 1, interpret=True)
        med = np.asarray(quantizer.from_unsigned_order(med_u))
        expect = ref.weighted_lower_median_ref(x.astype(np.float64), w)
        np.testing.assert_array_equal(med[0].astype(np.float64), expect)

    def test_matches_pure_jax_path(self):
        # ops-level consistency: kernel path == reduction-tree fallback path
        from repro.core import bitserial
        rng = np.random.default_rng(11)
        x = rng.integers(-(2**10), 2**10, size=(40, 9)).astype(np.int32)
        assign = rng.integers(0, 5, size=(40,)).astype(np.int32)
        u = _to_u(x)
        med_k, tot_k = ops.grouped_median_bits(u, jnp.asarray(assign), 5,
                                               interpret=True)
        med_j, tot_j = bitserial.grouped_median_bits(u, jnp.asarray(assign), 5)
        np.testing.assert_array_equal(np.asarray(med_k), np.asarray(med_j))
        np.testing.assert_allclose(np.asarray(tot_k), np.asarray(tot_j))


class TestDistanceArgminKernel:
    @pytest.mark.parametrize("metric", ["l1", "l2"])
    @pytest.mark.parametrize("n,d,k", [
        (7, 2, 2), (32, 12, 5), (100, 3, 16), (257, 8, 4),
    ])
    def test_sweep(self, metric, n, d, k):
        rng = np.random.default_rng(n + d + k)
        x = rng.normal(size=(n, d)).astype(np.float32)
        c = rng.normal(size=(k, d)).astype(np.float32)
        a, m = distance_argmin_pallas(jnp.asarray(x), jnp.asarray(c),
                                      metric=metric, n_block=64,
                                      interpret=True)
        ea, em = ref.distance_argmin_ref(x, c, metric)
        np.testing.assert_array_equal(np.asarray(a), ea)
        np.testing.assert_allclose(np.asarray(m), em, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("dtype", [np.float32, np.float16])
    def test_dtypes(self, dtype):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(16, 4)).astype(dtype)
        c = rng.normal(size=(3, 4)).astype(dtype)
        a, m = distance_argmin_pallas(jnp.asarray(x), jnp.asarray(c),
                                      metric="l2", n_block=16, interpret=True)
        ea, _ = ref.distance_argmin_ref(x.astype(np.float32),
                                        c.astype(np.float32), "l2")
        np.testing.assert_array_equal(np.asarray(a), ea)

    def test_tie_takes_first(self):
        x = np.zeros((4, 2), np.float32)
        c = np.zeros((3, 2), np.float32)  # all centroids identical
        a, _ = distance_argmin_pallas(jnp.asarray(x), jnp.asarray(c),
                                      metric="l1", n_block=4, interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.zeros((4,), np.int32))


class TestFlashDecodeKernel:
    @pytest.mark.parametrize("b,s,hq,hkv,dh,t", [
        (1, 64, 4, 2, 16, 64), (2, 128, 8, 2, 32, 100),
        (1, 96, 4, 4, 16, 1), (2, 64, 4, 1, 8, 33),
    ])
    def test_matches_decode_attention(self, b, s, hq, hkv, dh, t):
        from repro.kernels.flash_decode import flash_decode_pallas
        from repro.models.attention import decode_attention
        rng = np.random.default_rng(b + s + t)
        q = jnp.asarray(rng.normal(size=(b, hq, dh)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(b, s, hkv, dh)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(b, s, hkv, dh)).astype(np.float32))
        got = flash_decode_pallas(q, k, v, jnp.int32(t), scale=dh**-0.5,
                                  chunk=32, interpret=True)
        want = decode_attention(q, k, v, t=t, scale=dh**-0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_softcap_path(self):
        from repro.kernels.flash_decode import flash_decode_pallas
        from repro.models.attention import decode_attention
        rng = np.random.default_rng(9)
        q = jnp.asarray(rng.normal(size=(1, 4, 16)).astype(np.float32)) * 4
        k = jnp.asarray(rng.normal(size=(1, 64, 2, 16)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(1, 64, 2, 16)).astype(np.float32))
        got = flash_decode_pallas(q, k, v, jnp.int32(50), scale=0.25,
                                  softcap=20.0, chunk=16, interpret=True)
        want = decode_attention(q, k, v, t=50, scale=0.25, softcap=20.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-4, atol=3e-4)


class TestInterpretFallback:
    """``ops.interpret_default`` is the single backend-detection point for
    every Pallas wrapper; on the CPU backend it must flip all of them into
    interpret mode (a Mosaic attempt would fail outright here), on TPU
    into Mosaic, and anywhere else it refuses."""

    def test_detects_cpu(self):
        assert jax.default_backend() == "cpu"  # the tests' contract
        assert ops.interpret_default() is True

    @pytest.mark.parametrize("backend,want", [("tpu", False), ("gpu", None),
                                              ("METAL", None)])
    def test_tpu_compiles_and_other_backends_raise(self, monkeypatch,
                                                   backend, want):
        """Only 'cpu' interprets and only 'tpu' compiles; any other
        backend is refused instead of silently interpreting."""
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        if want is None:
            with pytest.raises(RuntimeError, match=backend):
                ops.interpret_default()
        else:
            assert ops.interpret_default() is want

    def test_clustered_decode_resolves_none_via_helper(self):
        """interpret=None (the default) must run on CPU — i.e. the kernel
        module resolved it through the shared helper — and match an
        explicit interpret=True call bit-for-bit."""
        from repro.kernels.clustered_decode import clustered_decode_pallas
        rng = np.random.default_rng(3)
        b, c, r, hq, hkv, dh = 2, 4, 8, 4, 2, 16
        args = (
            jnp.asarray(rng.normal(size=(b, hq, dh)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, c, hkv, dh)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, c, hkv, dh)), jnp.float32),
            jnp.asarray(rng.uniform(1, 4, size=(b, c, hkv)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, r, hkv, dh)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, r, hkv, dh)), jnp.float32),
            jnp.asarray([6, 7], jnp.int32),
            jnp.asarray([2, 3], jnp.int32),
        )
        auto = clustered_decode_pallas(*args, scale=dh**-0.5)
        explicit = clustered_decode_pallas(*args, scale=dh**-0.5,
                                           interpret=True)
        np.testing.assert_array_equal(np.asarray(auto), np.asarray(explicit))

    def test_ops_wrapper_uses_fallback_on_cpu(self):
        """The jitted ops.clustered_decode path (interpret resolved by the
        helper) executes on CPU and matches the direct kernel call."""
        from repro.kernels.clustered_decode import clustered_decode_pallas
        rng = np.random.default_rng(4)
        b, c, r, hq, hkv, dh = 1, 4, 8, 2, 1, 8
        args = (
            jnp.asarray(rng.normal(size=(b, hq, dh)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, c, hkv, dh)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, c, hkv, dh)), jnp.float32),
            jnp.asarray(rng.uniform(1, 4, size=(b, c, hkv)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, r, hkv, dh)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, r, hkv, dh)), jnp.float32),
            jnp.asarray([5], jnp.int32),
            jnp.asarray([1], jnp.int32),
        )
        got = ops.clustered_decode(*args, scale=dh**-0.5)
        want = clustered_decode_pallas(*args, scale=dh**-0.5, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
