"""Counters the program's train step hands out, as the loop writes them:
attributes of its ``train_step`` spans (``packed_loop.STEP_COUNTERS``)."""

from __future__ import annotations


def mean_attr(ctx, name: str):
    """Mean of the attribute over the run's ``train_step`` spans; None where
    no span carries it (a program without the counter, or an untraced run)."""
    if ctx["kind"] != "train":
        return None
    values = [s.attrs[name] for s in ctx.get("spans", ())
              if s.name == "train_step" and name in (s.attrs or {})]
    return sum(values) / len(values) if values else None
