"""ell_slot_use: percent of the ELL slots swept by the window's
full-distance solve dispatches that held a real document nonzero: the
service's counter ``wmd_ell_slots_total{kind=real|pad}`` (each dispatch
adds every slot of the ELL it gathers K at; warm-up is not counted), read
after the window (program counter). A program without the counter reads
nothing."""
import os

from wmdbench import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(ctx):
    return spec.metric_module("query_slot_use", ROOT).slot_use(
        ctx.registry, "wmd_ell_slots_total")
