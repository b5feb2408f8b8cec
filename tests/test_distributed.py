"""Distributed reduction-tree tests.

These run in a subprocess with XLA_FLAGS forcing 8 host devices (the main
test process must keep the default single device, per the dry-run contract),
and verify that the shard_map median/clustering path — per-bit psum of vote
counts, the paper's interconnection reduction tree — matches the
single-device result exactly.
"""

import pytest

from _subproc import run_sub


@pytest.mark.slow
def test_distributed_median_matches_single_device():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.core import bitserial, quantizer

        assert len(jax.devices()) == 8
        rng = np.random.default_rng(0)
        x = rng.integers(-2**20, 2**20, size=(128, 16)).astype(np.int32)
        assign = rng.integers(0, 4, size=(128,)).astype(np.int32)
        u = quantizer.to_unsigned_order(jnp.asarray(x))

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        f = shard_map(
            lambda uu, aa: bitserial.grouped_median_bits(uu, aa, 4,
                                                         axis_name="data"),
            mesh=mesh,
            in_specs=(P("data", None), P("data")),
            out_specs=(P(), P()),
        )
        med_d, tot_d = jax.jit(f)(u, jnp.asarray(assign))
        med_s, tot_s = bitserial.grouped_median_bits(u, jnp.asarray(assign), 4)
        np.testing.assert_array_equal(np.asarray(med_d), np.asarray(med_s))
        np.testing.assert_allclose(np.asarray(tot_d), np.asarray(tot_s))
        print("distributed median OK")
    """)


@pytest.mark.slow
def test_distributed_kmedians_fit_matches_single_device():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.core import clustering
        from repro.core.clustering import ClusterConfig

        rng = np.random.default_rng(1)
        centers = np.array([[0,0],[6,6],[-6,6]], np.float32)
        xs = np.concatenate([
            rng.normal(size=(64, 2)).astype(np.float32)*0.3 + c
            for c in centers])
        perm = rng.permutation(len(xs)); xs = xs[perm]
        x = jnp.asarray(xs)
        cfg = ClusterConfig(k=3, centroid="median", metric="l1", max_iters=20)
        init = x[:3]

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        fit_d = shard_map(
            lambda xx, ii: clustering.fit(xx, cfg, ii, use_kernel=False,
                                          axis_name="data"),
            mesh=mesh,
            in_specs=(P("data", None), P()),
            out_specs=clustering.ClusterResult(
                P(), P("data"), P(), P(), P()),
        )
        rd = jax.jit(fit_d)(x, init)
        rs = clustering.fit(x, cfg, init, use_kernel=False)
        np.testing.assert_allclose(np.asarray(rd.centroids),
                                   np.asarray(rs.centroids), atol=1e-5)
        np.testing.assert_array_equal(np.asarray(rd.assign),
                                      np.asarray(rs.assign))
        print("distributed k-medians OK")
    """)


@pytest.mark.slow
def test_distributed_weighted_compress_head_matches_single_device():
    """kv_compress.compress_head(axis_name=...) — the psum-consistent
    weighted k-medians used when recompaction points span a mesh axis —
    must produce the single-device centroids/value-sums/counts exactly
    (per-bit vote psum + value/count psum, warm-started init)."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.core import kv_compress

        rng = np.random.default_rng(2)
        S, Dh, C = 128, 16, 8
        keys = jnp.asarray(rng.normal(size=(S, Dh)), jnp.float32)
        vals = jnp.asarray(rng.normal(size=(S, Dh)), jnp.float32)
        # mixed weights: masked rows (0) and pre-aggregated summaries (>1)
        w = jnp.asarray(((rng.random(S) < 0.8)
                         * rng.integers(1, 4, size=S)).astype(np.float32))
        cfg = kv_compress.KVCompressConfig(n_clusters=C, iters=6,
                                           keep_recent=16)
        init = keys[:C]

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("model",))
        f = shard_map(
            lambda kk, vv, ww, ii: kv_compress.compress_head(
                kk, vv, cfg, weights=ww, init_centroids=ii,
                axis_name="model"),
            mesh=mesh,
            in_specs=(P("model", None), P("model", None), P("model"), P()),
            out_specs=(P(), P(), P()),
        )
        kc_d, vc_d, cnt_d = jax.jit(f)(keys, vals, w, init)
        kc_s, vc_s, cnt_s = kv_compress.compress_head(
            keys, vals, cfg, weights=w, init_centroids=init)
        np.testing.assert_allclose(np.asarray(kc_d), np.asarray(kc_s),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(vc_d), np.asarray(vc_s),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(cnt_d), np.asarray(cnt_s),
                                   rtol=1e-5)
        print("distributed weighted compress_head OK")
    """)


@pytest.mark.slow
def test_elastic_restore_onto_sharded_mesh(tmp_path):
    """Checkpoint written by a 1-host run restores onto an 8-device mesh
    with NamedShardings (elastic restart across topologies)."""
    import jax, numpy as np
    from repro.checkpoint import ckpt
    tree = {"w": np.arange(64, dtype=np.float32).reshape(8, 8),
            "b": np.ones((16,), np.float32)}
    ckpt.save(str(tmp_path), 5, tree)
    run_sub(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.checkpoint import ckpt

        assert len(jax.devices()) == 8
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        like = {{"w": jnp.zeros((8, 8)), "b": jnp.zeros((16,))}}
        sh = {{"w": NamedSharding(mesh, P("data", None)),
              "b": NamedSharding(mesh, P()) }}
        tree, step = ckpt.restore({str(tmp_path)!r}, like, shardings=sh)
        assert step == 5
        assert tree["w"].sharding.spec == P("data", None)
        np.testing.assert_array_equal(
            np.asarray(tree["w"]), np.arange(64, dtype=np.float32).reshape(8, 8))
        print("elastic restore OK")
    """)
