"""Per-layer metric ``pack_occupancy.train``: real tokens over row slots of the steps run in the window."""

from benchmark.harness import readers


def read(ctx):
    return readers.pack_occupancy(ctx)
