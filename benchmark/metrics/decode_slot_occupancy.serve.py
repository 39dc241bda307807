"""Per-layer metric ``decode_slot_occupancy.serve``: live slots over compiled slots of the window's decode steps (engine counters `decode_live_slot_steps` / `decode_slot_steps`)."""

from benchmark.harness import phase_readers


def read(ctx):
    return phase_readers.counter_ratio(ctx, "decode_live_slot_steps", "decode_slot_steps")
