"""Per-layer metric ``expert_pairs_per_held_expert.train``: the real tokens' token-expert pairs that fall on experts held on this chip, over the experts held, a step (how far the expert layer's load is from a deployment's)."""

from benchmark.harness import step_counters


def read(ctx):
    return step_counters.mean_attr(ctx, "expert_pairs_per_held_expert")
