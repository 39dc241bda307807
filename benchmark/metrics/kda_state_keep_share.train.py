"""Per-layer metric ``kda_state_keep_share.train``: 100 x the mean over real tokens, heads and channels of the forget gate a_t (how much of the recurrent state a token leaves: near 100 the decay is saturated open, near 0 the state holds a token or two); a property of the gates, watched, not chased."""

from benchmark.harness import step_counters


def read(ctx):
    return step_counters.mean_attr(ctx, "kda_state_keep_share")
