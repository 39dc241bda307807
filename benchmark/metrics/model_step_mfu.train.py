"""Per-layer metric ``model_step_mfu.train``: model FLOPs per real token (forward + backward, from shapes) x tokens/s over chips x peak bf16."""

from benchmark.harness import readers


def read(ctx):
    return readers.model_step_mfu_train(ctx)
