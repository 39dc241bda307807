"""Per-layer metric ``expert_picks_here_share.train``: the real tokens' token-expert pairs that fall on experts held on this chip, over all their pairs (held / published under uniform routing)."""

from benchmark.harness import step_counters


def read(ctx):
    return step_counters.mean_attr(ctx, "expert_picks_here_share")
