"""The plain reference: its schedule by hand and its k-medians against a
sort-based weighted median."""

import numpy as np
import jax.numpy as jnp

from bench import reference

MEM = reference.Memory(chunk=64, ring=128, refresh=16, clusters=32, iters=4,
                       bits=16)


def test_schedule_by_hand():
    # prompt 200: chunks 64, 64, 64, 8; the third would overrun the ring
    # (192 - 0 > 128) so ring entries [0, 80) fold first; after the prompt
    # coverage catches up to 200 - 128 + 16 = 88; then a compaction every
    # 16 decode tokens while the request still has tokens to make
    ev, so = reference.schedule(200, 40, MEM)
    assert ev.tolist() == [[1, 128, 80], [1, 200, 88], [2, 216, 104],
                           [2, 232, 120]]
    assert len(so) == 239
    assert (so[:128] == 0).all() and (so[128:200] == 1).all()
    assert (so[200:216] == 2).all()       # decode steps 1..16
    assert (so[216:232] == 3).all()       # decode steps 17..32
    assert (so[232:] == 4).all()          # decode steps 33..39
    assert reference.max_events(200, 40, MEM) >= len(ev)


def test_short_request_never_folds():
    ev, so = reference.schedule(100, 5, MEM)
    assert ev.shape == (0, 3) and (so == 0).all()


def _weighted_lower_median(v, w):
    order = np.argsort(v, kind="stable")
    cum = np.cumsum(w[order])
    return v[order][np.argmax(2 * cum >= w.sum())]


def test_kmedians_one_round_is_the_weighted_median_of_each_cluster():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(40, 3)).astype(np.float32)
    vals = rng.normal(size=(40, 3)).astype(np.float32)
    w = rng.integers(0, 4, 40).astype(np.float32)
    init = x[:4] + 0.01
    cents, vmean, counts = reference.kmedians(
        jnp.asarray(x), jnp.asarray(vals), jnp.asarray(w), jnp.asarray(init),
        iters=1, bits=16)
    live = w > 0
    absmax = np.abs(x[live]).max(0)
    scale = 2.0 ** np.floor(13 - np.log2(absmax))
    q = np.round(x * scale)
    assign = ((x[:, None] - init[None]) ** 2).sum(-1).argmin(1)
    for k in range(4):
        mk = (assign == k) & live
        assert float(counts[k]) == w[mk].sum()
        if not mk.any():
            np.testing.assert_array_equal(np.asarray(cents[k]), init[k])
            continue
        for d in range(3):
            med = _weighted_lower_median(q[mk, d], w[mk]) / scale[d]
            assert float(cents[k, d]) == med
        np.testing.assert_allclose(
            np.asarray(vmean[k]), (vals[mk] * w[mk, None]).sum(0) / w[mk].sum(),
            rtol=1e-5)


def test_seeding_picks_farthest_weighted_points():
    x = jnp.asarray([[0.0], [1.0], [5.0], [2.0]])
    cents = jnp.asarray([[0.0], [9.0], [9.0]])
    live = jnp.asarray([True, False, False])
    w = jnp.asarray([1.0, 1.0, 0.0, 1.0])       # 5.0 carries no weight
    out = reference.seed_dead(x, cents, live, w)
    assert out[:, 0].tolist() == [0.0, 2.0, 1.0]


def test_served_gaps():
    lg = np.array([[0.0, 2.0, 1.0], [3.0, 1.0, 0.5]])
    assert reference.served_gaps(lg, [1, 2]).tolist() == [0.0, 2.5]
