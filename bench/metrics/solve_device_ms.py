"""solve_device_ms: device milliseconds of the full-distance solve program
per batch: the trace's ``XLA Modules`` events of the modules below, over the
batches dispatched in the window (core/distributed.build_wmd_batch_fn).

The batched solve is ``jax.jit(jax.shard_map(per_device, ...))``, so XLA
names its module after the function it wraps. A traced window that
dispatched batches but holds none of these modules raises: the name has
moved, and the metric must not fall silent."""

MODULES = ("jit_per_device",)


def solve_seconds(ctx):
    t = ctx.trace
    if not t:
        return None
    s = sum(m["seconds"] for name, m in t["modules"].items()
            if name in MODULES)
    if not s and ctx.batches:
        raise RuntimeError(
            f"solve_device_ms: none of the modules {MODULES} in the trace "
            f"of {ctx.batches} batches; modules seen: {sorted(t['modules'])}")
    return s or None


def read(ctx):
    s = solve_seconds(ctx)
    if s is None or not ctx.batches:
        return None
    return 1e3 * s / ctx.batches
