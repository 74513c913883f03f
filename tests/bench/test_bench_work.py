"""Work counts behind the roofline shares, at the paper's shapes, against
numbers worked by hand."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import work  # noqa: E402

V5E = work.peaks("TPU v5 lite")

# int8 rings of depth 64 at d=384, index capacity 4,218, 32 queries,
# nprobe 8: index 4218*384*4 = 6,478,848 B, queries 32*384*4 = 49,152 B,
# one tile 64*384 + 64*4 = 24,832 B; route flops 2*32*4218*384 =
# 103,661,568, rerank flops 2*32*8*64*384 = 12,582,912.
SERVE_FLOPS = 116_244_480.0


@pytest.mark.parametrize("distinct, nbytes", [
    (256, 6_528_000 + 256 * 24_832),   # every (query, probe) its own tile
    (8, 6_528_000 + 8 * 24_832),       # every query routes the same 8
])
def test_serve_call_counts_each_distinct_tile_once(distinct, nbytes):
    flops, got = work.serve_call(32, 384, 4218, 64, 1, 8, distinct)
    assert flops == SERVE_FLOPS
    assert got == nbytes


def test_serve_call_fp32_tile():
    # fp32 depth 16: 16*384*4 + 16*4 = 24,640 B a tile, cap 4,306
    flops, nbytes = work.serve_call(1, 384, 4306, 16, 4, 8, 8)
    assert nbytes == 4306 * 384 * 4 + 384 * 4 + 8 * 24_640
    assert flops == 2 * 4306 * 384 + 2 * 8 * 16 * 384


def test_serve_is_bound_by_bytes_at_paper_shape():
    t, bound = work.min_seconds(SERVE_FLOPS, 12_884_992.0, V5E)
    assert bound == "bytes"
    assert t == pytest.approx(12_884_992 / 819e9)


def test_admit_call_at_paper_shape():
    # 2,048 docs, 4,218 centroids, 5 basis rows, d=384, int8 rows:
    # bytes 3,145,728 + 6,478,848 + 7,680 + 2048*(384+12) = 10,443,264
    # flops 2*2048*(4218+5)*384 = 6,642,204,672
    flops, nbytes = work.admit_call(2048, 4218, 5, 384, 1)
    assert nbytes == 10_443_264
    assert flops == 6_642_204_672
    t, bound = work.min_seconds(flops, nbytes, V5E)
    assert bound == "flops"
    assert t == pytest.approx(6_642_204_672 / 197e12)


def test_routed_clusters_same_and_disjoint_routes():
    d = 16
    vectors = np.eye(d)
    valid = np.ones(d, bool)
    valid[15] = False
    labels = np.arange(100, 100 + d)
    same = np.tile(np.r_[np.linspace(1, 0.5, 8), np.zeros(8)], (5, 1))
    assert work.routed_clusters(same, vectors, valid, labels, 8) == 8
    a = np.r_[np.ones(8), np.zeros(8)]
    b = np.r_[np.zeros(8), np.ones(7), 0.0]
    # b routes to its 7 rows and one more (the invalid row never counts)
    got = work.routed_clusters(np.stack([a, b]), vectors, valid, labels, 8)
    assert got == 16 - 1
    labels_dead = labels.copy()
    labels_dead[:8] = -1
    assert work.routed_clusters(same, vectors, valid, labels_dead, 8) == 0


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
