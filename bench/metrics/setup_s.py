"""setup_s: seconds from the process's start to the window's start --
imports, data generation, the service's build and upload, the warm-up and,
on a cold cache, compilation (host clock)."""


def read(ctx):
    return ctx.setup_s
