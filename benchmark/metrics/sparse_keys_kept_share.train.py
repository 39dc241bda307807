"""Per-layer metric ``sparse_keys_kept_share.train``: mean over real query positions of the keys the indexer's selection kept over the real keys at or before the query."""

from benchmark.harness import step_counters


def read(ctx):
    return step_counters.mean_attr(ctx, "sparse_keys_kept_share")
