"""A traffic kind as a later PR would add it (tests only): the closed
loop, with one more entry in `extra`."""
from benchmarks.lib import spec

base = spec.traffic_kind("closed_loop")
CELL, clients = base.CELL, base.clients


def drive(*args):
    t0, records, extra = base.drive(*args)
    return t0, records, dict(extra, toy_loop=len(records))
