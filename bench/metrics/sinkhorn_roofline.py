"""sinkhorn_roofline: the least time the chip could take for the window's
full-distance batches (wmdbench.roofline: operations and bytes from the
real query words, document nonzeros and corpus words, at the peaks of
bench/peaks.json for this device kind), over the solve program's device
time, in percent. The batch is memory bound (see wmdbench.roofline)."""
import os

from wmdbench import roofline, spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(ctx):
    dev_s = spec.metric_module("solve_device_ms", ROOT).solve_seconds(ctx)
    if dev_s is None or not ctx.batch_words or ctx.peaks is None:
        return None
    cfg = ctx.config
    least = 0.0
    for words in ctx.batch_words:
        flops, nbytes = roofline.full_batch_work(
            words, nnz=ctx.nnz, distinct_words=ctx.distinct_words,
            num_docs=cfg["num_docs"], embed_dim=cfg["embed_dim"],
            iters=cfg["max_iter"])
        least += roofline.least_seconds(flops, nbytes, ctx.peaks)
    return 100.0 * least / dev_s
