"""The ops/bytes function of the full-distance batch against a hand count,
and the bound it reports."""
import json
import os

import pytest

from wmdbench_testing import BENCH

from wmdbench import roofline

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_hand_count_at_a_small_shape():
    """Two queries of 2 and 3 words, U = 10 distinct corpus words of
    width 4, 7 document nonzeros over 3 documents, T = 2 iterations.

    Operations: cost rows 2*m*U*w + 3*m*U = 160 + 60 (m = 2) and
    240 + 90 (m = 3); iterations T*(4m + 1)*nnz = 2*9*7 = 126 and
    2*13*7 = 182; final (6m + 1)*nnz = 91 and 133. Total 1082.
    Bytes: embeddings (U + 2 + 3)*w*4 = 240; ELL 8*nnz = 56; distances
    Q*N*4 = 24. Total 320."""
    flops, nbytes = roofline.full_batch_work(
        [2, 3], nnz=7, distinct_words=10, num_docs=3, embed_dim=4, iters=2)
    assert flops == 160 + 60 + 126 + 91 + 240 + 90 + 182 + 133 == 1082
    assert nbytes == 240 + 56 + 24 == 320


def test_paper_5k_batch_is_memory_bound_at_about_49_us():
    flops, nbytes = roofline.full_batch_work(
        [19] * 8, nnz=172_149, distinct_words=32_152, num_docs=5000,
        embed_dim=300, iters=15)
    assert flops == pytest.approx(4.70e9, rel=0.01)
    assert nbytes == pytest.approx(4.03e7, rel=0.01)
    assert roofline.bound_by(flops, nbytes, PEAKS) == "memory"
    assert roofline.least_seconds(flops, nbytes, PEAKS) == \
        pytest.approx(49.2e-6, rel=0.01)


def test_peaks_table_keyed_by_device_kind():
    from wmdbench import spec
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    assert "TPU v5e" in table["source"]
    v5e = spec.peaks("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.peaks("cpu")
