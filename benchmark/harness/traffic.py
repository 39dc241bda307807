"""The one general traffic generator. A traffic mix is a data file of
parameters under ``benchmark/traffic/``; nothing here knows a mix by name.

Copied in spirit from the program's generators (``bench.amazon_like_lengths``
and ``genrec_tpu.fleet.traffic.zipfian_repeat_user_trace``), which later PRs
may change; the yardstick's copy lives here.

Steadiness: what fixes the AMOUNT of work is drawn from the mix's own
``base_seed`` and is the same in every run. For an open loop that is the whole
shape of the traffic, past and window: how many requests, when each is due,
which arrival revisits which user rank, whether it repeats or grows, and
every history length; ``--seed`` changes the content (which user and item ids, the weights).
Arrival order is part of the shape on purpose: near the knee a
burst two requests longer moves the median latency by several percent, and
that must not differ between the two sides of a comparison. For training,
``--seed`` also permutes the lengths (the same set of sizes in another order).
"""

from __future__ import annotations

import collections
import dataclasses

import numpy as np


def history_lengths(spec: dict, n: int, max_items: int, seed: int) -> np.ndarray:
    """``n`` history lengths in items. ``amazon_like``: users have at least
    ``min_events`` events with a geometric tail, and every position of a
    user's sequence is one sample whose history is the items before it,
    clipped to ``max_items``: short prefixes dominate. The multiset comes
    from ``base_seed``; ``seed`` permutes it."""
    if spec["generator"] != "amazon_like":
        raise ValueError(f"unknown length generator {spec['generator']!r}")
    rng = np.random.default_rng([int(spec.get("base_seed", 0)), 31])
    out: list[int] = []
    while len(out) < n:
        h = int(spec["min_events"]) + int(rng.geometric(spec["geometric_p"]))
        out.extend(min(i, max_items) for i in range(1, h))
    lens = np.asarray(out[:n], np.int64)
    return lens[np.random.default_rng([seed, 32]).permutation(n)]


@dataclasses.dataclass
class Arrival:
    due_s: float          # seconds after the window opens
    user_id: int
    history: np.ndarray   # item ids, oldest first
    repeat: bool          # identical to this user's previous request


def _zipf_ranks(rng, traffic: dict, n: int):
    n_users = int(traffic["n_users"])
    p = np.arange(1, n_users + 1, dtype=np.float64) ** -float(traffic["zipf_a"])
    return rng.choice(n_users, size=n, p=p / p.sum())


def deployment_trace(traffic: dict, seconds: float, max_items: int,
                     n_items: int, seed: int, cache_entries: int = 0,
                     window: int = 0) -> tuple[list[Arrival], list[Arrival]]:
    """(fill, arrivals): the measured window's arrivals, and before them what
    a long-running deployment's prefix cache would hold when it opens.

    Poisson arrivals at the mix's fixed ``rate_per_s`` for ``seconds``. Users
    are Zipfian over ranks; a user's first request carries a history whose
    length is drawn as above; each later request of that user either repeats
    the previous one unchanged (probability ``p_repeat``: the prefix cache's
    full-key hit) or grows the history by one item (sliding at
    ``max_items``). Exactly ``round(rate * seconds)`` requests are due inside
    the window.

    The same process runs for ``preroll_requests`` requests BEFORE the
    window, on the host only: the deployment's past (none where
    ``cache_entries`` is 0). Of it, ``fill`` keeps the newest
    ``cache_entries`` distinct (user, history) requests in the order they
    were last sent: what an LRU cache of that many entries holds at the
    open, so sending them once in that order leaves the cache as the whole
    past would have. The past depends on nothing of the window, so a sweep
    can open window 1, 2, ... (another draw of arrivals each) on the state
    that one fill left."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    n_pre = int(traffic.get("preroll_requests", 0)) if cache_entries else 0
    base = int(traffic.get("base_seed", 0))
    lengths = traffic["history_lengths"]
    n_users = int(traffic["n_users"])

    # Structure: the same in every run. The past, then the window.
    prng = np.random.default_rng([base, 23])
    pre_rank = _zipf_ranks(prng, traffic, n_pre)
    pre_repeat = prng.random(n_pre) < float(traffic["p_repeat"])
    pre_len = np.maximum(history_lengths(
        {**lengths, "base_seed": base}, len(np.unique(pre_rank)), max_items,
        base), 1)
    srng = np.random.default_rng([base, 21, window])
    gaps = srng.exponential(1.0 / rate, n)
    gaps *= seconds / gaps.sum() * (n / (n + 1.0))
    due = np.cumsum(gaps)
    win_rank = _zipf_ranks(srng, traffic, n)
    win_repeat = srng.random(n) < float(traffic["p_repeat"])
    win_len = np.maximum(history_lengths(
        {**lengths, "base_seed": base + 1 + window}, n, max_items, base), 1)

    # Content: which user and which items, from the seed.
    crng = np.random.default_rng([seed, 22])
    rank_to_user = crng.permutation(n_users)
    histories: dict[int, list] = {}

    def step(rng, r, repeat_drawn, first_len):
        h = histories.get(r)
        if h is None:
            h, repeat = list(rng.integers(0, n_items, first_len)), False
        elif repeat_drawn:
            repeat = True
        else:
            h, repeat = (h + [int(rng.integers(0, n_items))])[-max_items:], False
        histories[r] = h
        return h, repeat

    cached: collections.OrderedDict = collections.OrderedDict()
    for i in range(n_pre):
        r = int(pre_rank[i])
        first = int(pre_len[len(histories)]) if r not in histories else 0
        h, _ = step(crng, r, pre_repeat[i], first)
        key = (r, tuple(h))
        cached[key] = None
        cached.move_to_end(key)
        if len(cached) > cache_entries:
            cached.popitem(last=False)
    fill = [Arrival(0.0, int(rank_to_user[r]), np.asarray(h, np.int64), False)
            for r, h in cached]

    wrng = np.random.default_rng([seed, 24, window])
    out = []
    for i in range(n):
        r = int(win_rank[i])
        h, repeat = step(wrng, r, win_repeat[i], int(win_len[i]))
        out.append(Arrival(float(due[i]), int(rank_to_user[r]),
                           np.asarray(h, np.int64), repeat))
    return fill, out
