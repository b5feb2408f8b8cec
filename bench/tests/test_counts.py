"""Operation and byte counts against hand counts at small shapes, and
the counts of the benchmarked configuration pinned."""

import dataclasses

import numpy as np
import pytest

from bench import config, counts, reference
from bench.arch import qwen3

SMALL = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 4, "intermediate_size": 16, "num_hidden_layers": 2,
         "vocab_size": 32, "rms_norm_eps": 1e-6, "rope_theta": 1e4,
         "qk_norm": False, "tie_word_embeddings": True}
M = qwen3.reference_model(SMALL)
MEM = reference.Memory(chunk=4, ring=8, refresh=2, clusters=3, iters=2,
                       bits=16)


def test_matmul_params_by_hand():
    # per layer: q 8x16 + k 8x8 + v 8x8 + o 16x8 + gate/up/down 3 x 8x16
    per_layer = 128 + 64 + 64 + 128 + 3 * 128
    assert M.matmul_params == 2 * per_layer + 8 * 32
    assert M.attn_layers == 2


def test_qwen3_4b_counts_are_pinned():
    """The benchmarked configuration's counts, as the dense formula of
    ``bench/counts.py`` gave them before the architecture modules: rows of
    three requests' schedules (one of them folding) and padding."""
    conf = config.load_config("qwen3-4b")
    m = config.arch_for(conf).reference_model(conf)
    mem = reference.memory(conf)
    assert (m.matmul_params, m.attn_layers) == (4_022_272_000, 36)
    rows = [counts.request_rows(*reference.schedule(p, t, mem), mem.clusters,
                                p, mem.chunk)
            for p, t in ((40, 30), (300, 100), (16, 16))]
    rows = np.concatenate(rows + [np.zeros((5, 4), np.int64)])
    assert counts.paged_decode_work(rows, m) == (27152547840.0, 2906210304.0)
    assert counts.step_flops(rows, m) == 4041380003840.0


def test_attention_work_counts_only_the_layers_that_read_the_kernel():
    rows = np.array([[10, 4, 3, 1], [2, 0, 0, 1]])
    one = dataclasses.replace(M, attn_layers=1)
    flops, nbytes = counts.paged_decode_work(rows, M)
    assert counts.paged_decode_work(rows, one) == (flops / 2, nbytes / 2)
    assert counts.step_flops(rows, one) == \
        2 * M.matmul_params * 2 + flops / 2


def test_paged_decode_work_by_hand():
    rows = np.array([[10, 4, 3, 1],  # 3 centroids + positions 4..9
                     [2, 0, 0, 1],   # positions 0..1
                     [1, 0, 0, 0]])  # position 0, read by the row above
    n_keys = 9 + 2 + 1
    flops, nbytes = counts.paged_decode_work(rows, M, kv_bytes=2,
                                             act_bytes=2)
    assert flops == 2 * 4 * 4 * 4 * n_keys            # layers x 4 Hq Dh keys
    kv = 2 * 2 * 4 * 2 * (9 + 2)                      # K+V x Hkv x Dh x bytes
    qo = 2 * 4 * 4 * 2 * 3                            # q+out x Hq x Dh x bytes x rows
    assert nbytes == 2 * (kv + qo)


def test_kernel_count_ignores_padding_rows_and_row_order():
    """The count is the work attention needs: padding rows that fill a row
    bucket or a padded grid add nothing, and the order rows are packed in
    does not matter."""
    rows = np.array([[10, 4, 3, 1], [2, 0, 0, 1], [7, 0, 0, 1]])
    base = counts.paged_decode_work(rows, M)
    for bucket in (4, 8, 128):
        pad = np.zeros((bucket - len(rows), 4), np.int64)
        assert counts.paged_decode_work(np.concatenate([rows, pad]), M) == base
        assert counts.step_flops(np.concatenate([pad, rows[::-1]]), M) == \
            counts.step_flops(rows, M)


def test_step_flops_by_hand():
    rows = np.array([[3, 0, 0, 1]])
    attn, _ = counts.paged_decode_work(rows, M)
    assert counts.step_flops(rows, M) == 2 * M.matmul_params + attn


def test_request_rows_follow_the_schedule():
    ev, so = reference.schedule(10, 5, MEM)
    rows = counts.request_rows(ev, so, MEM.clusters, 10, MEM.chunk)
    assert rows.shape == (14, 4)
    assert list(rows[:, 0]) == list(range(1, 15))
    covs = np.concatenate([[0], ev[:, 2]])
    assert list(rows[:, 1]) == list(covs[so])
    assert (rows[:, 2] == np.minimum(rows[:, 1], 3)).all()
    # prompt pieces 0-3, 4-7, 8-9 are read by their last row; decode rows
    # 10-13 each read their own
    assert list(np.flatnonzero(rows[:, 3])) == [3, 7, 9, 10, 11, 12, 13]
    # rows of one piece share its memory state
    for lo, hi in ((0, 4), (4, 8), (8, 10)):
        assert len(set(rows[lo:hi, 1])) == 1


def test_peaks_table():
    p = counts.peaks_for("TPU v5 lite")
    assert (p.flops, p.hbm_bw) == (197e12, 819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        counts.peaks_for("TPU v99")


def test_roofline_bound():
    p = counts.peaks_for("TPU v5 lite")
    assert counts.roofline_seconds(197e12, 1.0, p) == (1.0, "flops")
    assert counts.roofline_seconds(1.0, 819e9, p) == (1.0, "bytes")
