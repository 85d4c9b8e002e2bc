"""Operations and bytes of one full-distance Sinkhorn-WMD batch, from its
shapes, and the least time the chip could take for it.

Counted from the algorithm and the data, not from any implementation, so
that no implementation can do less: ``m`` real words of each query (no v_r
padding), ``nnz`` real document nonzeros (no ELL padding), ``U`` distinct
words of the corpus (the only vocabulary rows any cost row needs), width
``w``, ``T`` iterations, ``Q`` queries, ``N`` documents.

Operations, per query (a multiply-add is two):
  cost rows   2 m U w            |a - b| for every query word and corpus
                                 word (as a product; the differences and
                                 squares of the plain form count the same)
              + 3 m U            sqrt, exp, K * M
  iterations  T (4 m + 1) nnz    per nonzero: the dot K^T u over the m
                                 words, one division, the update K v
  final       (6 m + 1) nnz      K^T u once more, then (K * M) v and u . it

Bytes, per batch, the traffic through HBM that no implementation avoids:
  embeddings  (U + sum_q m) w 4  every corpus word's and query word's row,
                                 read once
  ELL         8 nnz              word id and value of every nonzero, once
  distances   4 Q N              written once

An implementation that gathers K values from HBM on every pass moves far
more (about 1.8 GB a paper_5k batch): that is its own cost, not the
algorithm's, and so not part of the least time. At paper_5k (Q = 8,
m = 19, nnz = 172k, T = 15, U of about 32k) the batch is 0.040 GB,
49 us at 819 GB/s, against 4.7 GFLOP, 24 us at 197 TFLOP/s: memory
bound.
"""
from __future__ import annotations


def full_batch_work(query_words, *, nnz: int, distinct_words: int,
                    num_docs: int, embed_dim: int,
                    iters: int) -> tuple[float, float]:
    """(operations, bytes) of one batch; ``query_words`` lists the real
    word count of each query in it."""
    u, w = float(distinct_words), float(embed_dim)
    flops = 0.0
    for m in query_words:
        flops += 2.0 * m * u * w + 3.0 * m * u
        flops += iters * (4.0 * m + 1.0) * nnz + (6.0 * m + 1.0) * nnz
    nbytes = (4.0 * w * (u + sum(query_words)) + 8.0 * nnz
              + 4.0 * len(query_words) * num_docs)
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The larger of the compute time and the memory time at peak."""
    return max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def bound_by(flops: float, nbytes: float, peaks: dict) -> str:
    return ("memory" if nbytes / peaks["hbm_bytes_per_s"]
            >= flops / peaks["flops_per_s"] else "compute")
