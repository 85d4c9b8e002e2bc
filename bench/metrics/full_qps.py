"""full_qps: full-distance queries answered in the window over the window's
seconds (host clock, from the first dispatch to the last answer)."""


def read(ctx):
    if ctx.traffic["request"]["kind"] != "full" or not ctx.window["wall_s"]:
        return None
    return ctx.queries / ctx.window["wall_s"]
