"""solve_chunk_hbm: percent of the device's memory that one chunk of the
last full-distance batch's solve takes by the plan's own count: the
service's gauges ``wmd_solve_chunk_bytes`` over ``wmd_device_bytes_limit``
(the device's ``bytes_limit``), read after the window (program counter).
It is not a reading of the device: only a change to the plan moves it.
A program without the gauges, or a backend that reports no memory, reads
nothing."""


def read(ctx):
    chunk = ctx.registry.get("wmd_solve_chunk_bytes")
    limit = ctx.registry.get("wmd_device_bytes_limit")
    if chunk is None or not limit:
        return None
    return 100.0 * chunk / limit
