"""Per-layer metric ``model_step_mfu.serve``: model FLOPs of the prefills and decode steps the completed requests needed over window x peak bf16."""

from benchmark.harness import readers


def read(ctx):
    return readers.model_step_mfu_serve(ctx)
