"""query_slot_use: percent of the query slots swept by the window's
full-distance solve dispatches that held a real query word: the service's
counter ``wmd_query_slots_total{kind=real|pad}`` (each dispatch adds its
Q_pow2 x v_r slots; warm-up is not counted), read after the window
(program counter). A program without the counter reads nothing."""


def slot_use(registry: dict, counter: str):
    """100 x real / (real + pad) of a ``{kind=real|pad}`` slot counter,
    or None where the registry has none."""
    real = registry.get(counter + "{kind=real}")
    pad = registry.get(counter + "{kind=pad}")
    if real is None or pad is None or real + pad <= 0:
        return None
    return 100.0 * real / (real + pad)


def read(ctx):
    return slot_use(ctx.registry, "wmd_query_slots_total")
