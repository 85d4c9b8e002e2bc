"""service_host_ms: host milliseconds per ``WMDService.query_batch`` call
spent outside the wait for the device: the program's own span seconds
(histogram ``wmd_span_seconds{span=...}``, warm-up not counted) of
``wmd.query_batch`` less those of its ``wmd.fetch`` children, over the
calls (program span). A program without the spans reads nothing."""


def read(ctx):
    root = ctx.registry.get("wmd_span_seconds{span=wmd.query_batch}")
    fetch = ctx.registry.get("wmd_span_seconds{span=wmd.fetch}")
    if not root or not root["count"]:
        return None
    waited = fetch["sum"] if fetch else 0.0
    return 1e3 * (root["sum"] - waited) / root["count"]
