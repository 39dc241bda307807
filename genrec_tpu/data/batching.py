"""Static-shape batching over in-memory numpy datasets.

Replaces torch DataLoader + per-batch-max collate functions
(amazon_sasrec.py:125-161 etc.). Every batch is exactly (batch_size, ...)
— the final partial batch is padded with zero rows and reported through a
``valid`` mask so eval never counts phantom samples and jit never sees a
new shape.
"""

from __future__ import annotations

import dataclasses
from operator import itemgetter
from typing import Iterator, Mapping, Sequence

import numpy as np


def prefetch_to_device(iterator, mesh, size: int = 2, axis: str = "data"):
    """Overlap host batching with device compute.

    Wraps a (batch, valid) iterator: a background thread assembles numpy
    batches ``size`` steps ahead (the fancy-index gather + padding is the
    host cost torch DataLoader workers hide in the reference); the MAIN
    thread then places them with `shard_batch` — jax transfers are
    asynchronous, and issuing device_put from a second thread while a
    compiled program holds the devices can deadlock the CPU backend's
    collective rendezvous (observed: hard abort on the 8-device virtual
    mesh), so all device interaction stays single-threaded.
    """
    import queue
    import threading

    from genrec_tpu.parallel.mesh import shard_batch

    q: "queue.Queue" = queue.Queue(maxsize=size)
    _END = object()
    _ERR = object()
    stop = threading.Event()

    def _put(item) -> bool:
        # Bounded-wait put so the thread can't block forever if the
        # consumer abandons the loop (e.g. an iteration-cap break).
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def producer():
        try:
            for batch, valid in iterator:
                if not _put((batch, valid)):
                    return
        except BaseException as e:  # data-pipeline failures must CRASH the
            _put((_ERR, e))  # train loop, not truncate the epoch silently
            return
        _put(_END)

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    try:
        while True:
            item = q.get()
            if item is _END:
                return
            if isinstance(item, tuple) and item[0] is _ERR:
                raise item[1]
            batch, valid = item
            yield shard_batch(mesh, batch, axis=axis), valid
    finally:
        stop.set()  # unblocks + retires the producer on early exit


def prefetch_eval_batches(iterator, mesh, size: int = 2, axis: str = "data"):
    """`prefetch_to_device` for eval loops: yields (sharded, host_batch,
    valid) so metrics read targets from the EXACT numpy batch that was
    evaluated — no re-slicing of the source arrays by running offset,
    which would silently misalign if iteration order ever changed."""
    packed = ((batch, (valid, batch)) for batch, valid in iterator)
    for sharded, (valid, host) in prefetch_to_device(packed, mesh, size, axis):
        yield sharded, host, valid


def fold_valid(iterator):
    """Fold the valid mask into the batch (int32 key "valid") so it ships
    to device with the prefetching iterator — for eval steps that consume
    the mask on device."""
    for batch, valid in iterator:
        yield {**batch, "valid": valid.astype(np.int32)}, valid


def cycle(iterable_factory):
    """Infinite iterator over a re-creatable iterable (reference
    genrec/data/utils.py:7-12, which cycles a DataLoader). Takes a
    zero-arg factory so each pass re-shuffles:

        for batch, valid in cycle(lambda: batch_iterator(arrays, 64)): ...
    """
    while True:
        yield from iterable_factory()


def pad_to_batch(arrays: Mapping[str, np.ndarray], batch_size: int):
    """Pad dict-of-arrays (same leading dim) up to batch_size; returns
    (padded, valid_mask)."""
    n = next(iter(arrays.values())).shape[0]
    pad = batch_size - n
    out = {}
    for k, v in arrays.items():
        if pad > 0:
            padding = np.zeros((pad,) + v.shape[1:], v.dtype)
            out[k] = np.concatenate([v, padding], axis=0)
        else:
            out[k] = v
    valid = np.zeros((batch_size,), bool)
    valid[:n] = True
    return out, valid


# ---------------------------------------------------------------------------
# Sequence packing: first-fit-decreasing binning of variable-length examples
# into fixed-width rows with segment IDs, so attention/loss never pay for
# padding slots (the standard TPU fix for ragged batches — same padding-waste
# argument as Ragged Paged Attention on the inference side).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PackingReport:
    """Occupancy accounting for one packing pass.

    ``occupancy`` = real tokens / total slots; ``padded_rows`` is what the
    pre-packing layout would have used (one row per example), so
    ``padded_rows / n_rows`` is the step-count (and FLOP) reduction."""

    n_examples: int
    n_rows: int
    row_len: int
    real_tokens: int
    max_segments: int

    @property
    def slot_tokens(self) -> int:
        return self.n_rows * self.row_len

    @property
    def occupancy(self) -> float:
        return self.real_tokens / max(self.slot_tokens, 1)

    @property
    def padded_rows(self) -> int:
        return self.n_examples

    def as_dict(self) -> dict:
        return {
            "n_examples": self.n_examples,
            "n_rows": self.n_rows,
            "row_len": self.row_len,
            "real_tokens": self.real_tokens,
            "max_segments": self.max_segments,
            "occupancy": round(self.occupancy, 4),
            "rows_vs_padded": round(self.n_rows / max(self.padded_rows, 1), 4),
        }

    def __str__(self) -> str:
        return (
            f"packed {self.n_examples} examples into {self.n_rows} rows of "
            f"{self.row_len} (was {self.padded_rows} padded rows): "
            f"occupancy {self.occupancy:.1%}, "
            f"<= {self.max_segments} segments/row"
        )


def _ffd_layout(
    lengths: np.ndarray, capacity: int, max_segments: int | None,
) -> tuple[np.ndarray, np.ndarray, int]:
    """First-fit-decreasing in array form: ``(order, bin_of, n_bins)``,
    where ``order`` is the processing order (a stable sort by decreasing
    length) and ``bin_of[j]`` the bin of item ``order[j]``.

    Items are placed a run of equal lengths at a time. Within a run of
    length ``n``, first fit fills the open bins in index order: a bin
    takes ``min(remaining // n, max_segments - count)`` items, and one
    that stops fitting stays full for the rest of the run (its room only
    shrinks). The run's leftover items then open new bins of
    ``min(capacity // n, max_segments)`` items each. So a run is a
    cumsum, a searchsorted and a bincount over the open bins — the same
    bins the item-by-item scan makes, with no per-item Python."""
    if lengths.size and int(lengths.max()) > capacity:
        raise ValueError(
            f"example length {int(lengths.max())} exceeds row capacity {capacity}"
        )
    if (lengths <= 0).any():
        raise ValueError("every example must have at least one token")
    if max_segments is not None and max_segments < 1:
        raise ValueError(f"max_segments must be at least 1, got {max_segments}")
    # Every item holds >= 1 slot, so a bin never holds more than `capacity`.
    seg_cap = capacity if max_segments is None else max_segments
    order = np.argsort(-lengths, kind="stable")
    if not order.size:
        return order, order, 0
    ordered = lengths[order]
    starts = np.flatnonzero(np.r_[True, ordered[1:] != ordered[:-1]])
    ends = np.r_[starts[1:], len(ordered)]
    bin_of = np.empty(len(ordered), np.int64)
    remaining = np.empty(len(ordered), np.int64)  # at most one bin/item
    count = np.empty(len(ordered), np.int64)
    n_bins = 0
    for s, e in zip(starts.tolist(), ends.tolist()):
        n = int(ordered[s])
        placed = 0
        if n_bins:
            take = np.minimum(remaining[:n_bins] // n, seg_cap - count[:n_bins])
            cum = np.cumsum(take)
            placed = min(e - s, int(cum[-1]))
        if placed:
            b = np.searchsorted(cum, np.arange(placed), side="right")
            bin_of[s:s + placed] = b
            added = np.bincount(b, minlength=n_bins)
            remaining[:n_bins] -= added * n
            count[:n_bins] += added
        left = e - s - placed
        if left:
            per = min(capacity // n, seg_cap)
            new = n_bins + np.arange(left) // per
            bin_of[s + placed:e] = new
            fill = np.bincount(new - n_bins)
            remaining[n_bins:n_bins + len(fill)] = capacity - fill * n
            count[n_bins:n_bins + len(fill)] = fill
            n_bins += len(fill)
    return order, bin_of, n_bins


def first_fit_decreasing(
    lengths: Sequence[int], capacity: int, max_segments: int | None = None,
) -> list[list[int]]:
    """Greedy FFD bin packing: example indices binned into rows of
    ``capacity`` slots. Deterministic (stable sort by decreasing length);
    raises if any example exceeds the row capacity — producers truncate to
    the model window before packing.

    ``max_segments`` caps examples per row: many tiny examples in one row
    would otherwise drive the GLOBAL max-segments-per-row up, and packed
    consumers that allocate per-segment work (TIGER's per-example
    decoders) pay for that max on every row.

    The first fit places one run of equal lengths at a time, with a few
    array operations over the open bins each (`_ffd_layout`); a bin lists
    its examples in the order they were placed."""
    order, bin_of, _ = _ffd_layout(
        np.asarray(lengths, np.int64), capacity, max_segments)
    by_bin = np.argsort(bin_of, kind="stable")
    items = order[by_bin].tolist()
    bins = bin_of[by_bin].tolist()
    out: list[list[int]] = []
    for b, idx in zip(bins, items):
        if b == len(out):
            out.append([])
        out[b].append(idx)
    return out


def pack_examples(
    examples: Sequence[Mapping[str, np.ndarray]],
    row_len: int,
    *,
    segment_keys: Sequence[str] = (),
    max_segments: int | None = None,
    seed=None,
) -> tuple[dict[str, np.ndarray], PackingReport]:
    """Bin variable-length examples into fixed-width packed rows.

    Each example is a dict of equal-length 1-D token arrays (e.g.
    ``input_ids``/``targets``/``timestamps``) plus, optionally, per-example
    fixed-shape values named in ``segment_keys`` (e.g. TIGER's
    ``target_ids``). Returns ``(arrays, report)`` where arrays hold:

    - one ``(n_rows, row_len)`` array per token key, segments laid out
      contiguously from slot 0, pad value 0;
    - ``segment_ids`` ``(n_rows, row_len)`` int32 — 1-based per segment,
      0 at padding slots (the attention-mask and loss-mask source);
    - ``positions`` ``(n_rows, row_len)`` int32 — within-segment 0-based
      positions (for learned/relative position lookups);
    - per ``segment_keys`` key a ``(n_rows, max_segments, ...)`` array plus
      ``segment_valid`` ``(n_rows, max_segments)`` int32 marking real
      segments.

    ``max_segments`` (optional) caps segments per row — consumers that do
    per-segment work sized by the row MAXIMUM (TIGER's decoder batch is
    rows x max_segments) trade a little occupancy for a bounded max.

    ``seed`` (optional, any numpy Generator seed) pre-permutes the
    examples before the length-stable FFD sort, re-mixing which
    SAME-LENGTH examples co-locate in a row. Trainers re-pack each epoch
    with an epoch-varying seed so example co-batching is reshuffled like
    the padded layout's per-epoch permutation; None keeps input order
    (deterministic layout for parity tests).
    """
    if not examples:
        raise ValueError("pack_examples needs at least one example")
    n_ex = len(examples)
    perm = (np.random.default_rng(seed).permutation(n_ex)
            if seed is not None else np.arange(n_ex))
    first = examples[int(perm[0])]
    seg_keys = tuple(segment_keys)
    token_keys = [k for k in first.keys() if k not in seg_keys]
    if not token_keys:
        raise ValueError("examples carry no token arrays")

    def lengths_of(k):
        return np.fromiter(map(len, map(itemgetter(k), examples)), np.int64, n_ex)

    lengths = lengths_of(token_keys[0])
    for k in token_keys[1:]:
        if (lengths_of(k) != lengths).any():
            raise ValueError(f"token key {k!r} length mismatch within example")
    # The FFD sees the examples in permuted order.
    order, bin_of, R = _ffd_layout(lengths[perm], row_len, max_segments)

    # Lay the segments out row by row, in the order each row took them:
    # `src` is the input index of each segment in that layout.
    by_bin = np.argsort(bin_of, kind="stable")
    rows = bin_of[by_bin]
    src = perm[order[by_bin]]
    seg_len = lengths[src]
    row_start = np.searchsorted(rows, np.arange(R))  # first segment of a row
    seg = np.arange(n_ex) - row_start[rows]  # 0-based segment index
    seg_end = np.cumsum(seg_len)
    seg_start = seg_end - seg_len  # in the laid-out token stream
    # With a cap, the segment axis is pinned to it so re-packs (per-epoch
    # seeds) keep a STATIC shape — no jit recompile when the realized
    # max shifts between epochs.
    S = max_segments if max_segments is not None else int(seg.max()) + 1

    # Each key is gathered once in input order (memory order: cheaper than
    # visiting the examples shuffled), then taken into layout order by one
    # index. A row's tokens fill its first slots contiguously, so a boolean
    # mask of those slots takes the laid-out stream in one assignment.
    positions = np.arange(int(seg_end[-1])) - np.repeat(seg_start, seg_len)
    in_start = np.cumsum(lengths) - lengths  # an example's start, input order
    take = np.repeat(in_start[src], seg_len) + positions
    slots = np.arange(row_len) < np.add.reduceat(seg_len, row_start)[:, None]
    seg_slot = rows * S + seg
    # Dtypes follow the first (permuted) example's: values are cast on the
    # gather, never upcast.
    out: dict[str, np.ndarray] = {}
    for k in token_keys:
        dtype = np.asarray(first[k]).dtype
        stream = np.concatenate(
            list(map(itemgetter(k), examples)), dtype=dtype, casting="unsafe")
        out[k] = np.zeros((R, row_len), dtype)
        out[k][slots] = stream[take]
    out["segment_ids"] = np.zeros((R, row_len), np.int32)
    out["segment_ids"][slots] = np.repeat(seg + 1, seg_len)
    out["positions"] = np.zeros((R, row_len), np.int32)
    out["positions"][slots] = positions
    for k in seg_keys:
        proto = np.asarray(first[k])
        values = np.array(list(map(itemgetter(k), examples)), proto.dtype)
        flat = np.zeros((R * S,) + proto.shape, proto.dtype)
        flat[seg_slot] = values[src]
        out[k] = flat.reshape((R, S) + proto.shape)
    out["segment_valid"] = np.zeros((R, S), np.int32)
    out["segment_valid"].reshape(-1)[seg_slot] = 1
    report = PackingReport(
        n_examples=n_ex, n_rows=R, row_len=row_len,
        real_tokens=int(seg_end[-1]), max_segments=S,
    )
    return out, report


def right_align(arrays: Mapping[str, np.ndarray], *, length_key: str = "input_ids",
                keys: Sequence[str] | None = None) -> dict[str, np.ndarray]:
    """Shift left-padded rows (pad id 0 at the FRONT) to right-padded
    layout (tokens at slots 0..l-1, pad at the tail).

    Packed training teaches learned position p = "p-th event of the
    window", so eval rows must present the same indexing; callers then read
    predictions from the last VALID slot instead of slot -1. Non-sequence
    keys (different trailing shape) pass through untouched."""
    ref = np.asarray(arrays[length_key])
    lengths = (ref != 0).sum(axis=1)
    move = keys if keys is not None else [
        k for k, v in arrays.items()
        if np.asarray(v).ndim == 2 and np.asarray(v).shape == ref.shape
    ]
    out = dict(arrays)
    for k in move:
        v = np.asarray(arrays[k])
        shifted = np.zeros_like(v)
        for i, n in enumerate(lengths):
            if n:
                shifted[i, :n] = v[i, v.shape[1] - n:]
        out[k] = shifted
    return out


def batch_iterator(
    arrays: Mapping[str, np.ndarray],
    batch_size: int,
    *,
    shuffle: bool = False,
    seed: int = 0,
    drop_last: bool = False,
    epoch: int = 0,
    start_batch: int = 0,
) -> Iterator[tuple[dict, np.ndarray]]:
    """Yield (batch_dict, valid_mask) of fixed shape (batch_size, ...).

    Shuffling is deterministic in (seed, epoch) so every data-parallel
    process draws the same permutation and shards it consistently.

    ``start_batch`` skips the first N batches WITHOUT gathering them —
    the mid-epoch resume cursor (core.fault_tolerance): the permutation
    is drawn in full, so batch i of a resumed epoch is bit-identical to
    batch i of the uninterrupted one.
    """
    n = next(iter(arrays.values())).shape[0]
    idx = np.arange(n)
    if shuffle:
        idx = np.random.default_rng((seed, epoch)).permutation(n)
    for start in range(start_batch * batch_size, n, batch_size):
        sel = idx[start : start + batch_size]
        if len(sel) < batch_size and drop_last:
            return
        chunk = {k: v[sel] for k, v in arrays.items()}
        yield pad_to_batch(chunk, batch_size)
