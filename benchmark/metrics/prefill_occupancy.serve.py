"""Per-layer metric ``prefill_occupancy.serve``: real history positions over bucketed ones of the window's prefills (engine counters `prefill_tokens` / `prefill_token_slots`)."""

from benchmark.harness import phase_readers


def read(ctx):
    return phase_readers.counter_ratio(ctx, "prefill_tokens", "prefill_token_slots")
