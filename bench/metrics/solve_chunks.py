"""solve_chunks: document chunks a full-distance batch is swept in: the
service's counter ``wmd_solve_chunks_total`` (each dispatch adds the
chunks of its memory plan, 1 where unchunked; warm-up is not counted),
read after the window, over the window's batches (program counter). A
program without the counter reads nothing."""


def read(ctx):
    chunks = ctx.registry.get("wmd_solve_chunks_total")
    if chunks is None or not ctx.batches:
        return None
    return chunks / ctx.batches
