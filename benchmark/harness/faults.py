"""Faults planted under the timed path, for the control tests and for the
readings the limits are held against (never used by a benchmark run).

Each takes the jitted step and returns a broken one with the same signature.
"""

from __future__ import annotations


def state_unchanged(step_fn):
    """A step that returns its state unchanged (the metrics still come)."""
    import jax
    import jax.numpy as jnp

    def step(state, batch):
        kept = jax.tree_util.tree_map(jnp.copy, state)
        _, metrics = step_fn(state, batch)
        return kept, metrics

    return step


def half_batch(step_fn):
    """Half of the batch left out, the mean taken over the rest."""

    def step(state, batch):
        valid = batch["segment_valid"]
        half = valid.shape[0] // 2
        return step_fn(state, dict(batch, segment_valid=valid.at[half:].set(0)))

    return step


TRAIN_FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch}


def plant_altered_token(adapter):
    """Make ``adapter.build_serve`` hand back an engine whose head alters
    the last code of every answer's best beam where it is produced
    (``paged_finalize``); returns the undo."""
    orig = adapter.build_serve

    def build(*a, **k):
        engine, head, params, catalog = orig(*a, **k)
        finalize = head.paged_finalize
        cb = head.model.num_item_embeddings

        def altered(row, req):
            out = finalize(row, req)
            out["sem_ids"] = out["sem_ids"].copy()
            out["sem_ids"][0, -1] = (out["sem_ids"][0, -1] + 1) % cb
            return out

        head.paged_finalize = altered
        return engine, head, params, catalog

    adapter.build_serve = build
    return lambda: setattr(adapter, "build_serve", orig)


def plant_no_weight_decay(adapter):
    """Make ``adapter.make_step`` build its optimizer without the
    configuration's weight decay; returns the undo."""
    orig = adapter.make_step

    def make_step(cfg):
        return orig(dict(cfg, optimizer=dict(cfg["optimizer"], weight_decay=0.0)))

    adapter.make_step = make_step
    return lambda: setattr(adapter, "make_step", orig)


def plant(adapter, wrap):
    """Make ``adapter.build_train`` hand back an entry whose step is
    ``wrap(step)``; returns the undo."""
    orig = adapter.build_train

    def build(*a, **k):
        entry = orig(*a, **k)
        entry.step_fn = wrap(entry.step_fn)
        return entry

    adapter.build_train = build
    return lambda: setattr(adapter, "build_train", orig)
