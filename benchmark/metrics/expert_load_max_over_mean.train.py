"""Per-layer metric ``expert_load_max_over_mean.train``: largest over the experts held of the token-expert pairs routed to it, over their mean (1 = even), mean over layers and steps."""

from benchmark.harness import step_counters


def read(ctx):
    return step_counters.mean_attr(ctx, "expert_load_max_over_mean")
