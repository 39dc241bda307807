"""Unit tests for data/batching.py: the static-shape helpers the sequence
packer sits on (pad_to_batch / fold_valid / prefetch ordering) and the
first-fit-decreasing packer itself (layout invariants, occupancy math,
determinism)."""

import time

import numpy as np
import pytest

from genrec_tpu.data.batching import (
    batch_iterator,
    first_fit_decreasing,
    fold_valid,
    pack_examples,
    pad_to_batch,
    prefetch_to_device,
    right_align,
)


# ---------------------------------------------------------------- helpers


def test_pad_to_batch_ragged_final_batch():
    arrays = {"x": np.arange(10, dtype=np.int32).reshape(5, 2),
              "y": np.ones((5,), np.float32)}
    padded, valid = pad_to_batch(arrays, 8)
    assert padded["x"].shape == (8, 2) and padded["y"].shape == (8,)
    assert valid.tolist() == [True] * 5 + [False] * 3
    np.testing.assert_array_equal(padded["x"][:5], arrays["x"])
    assert padded["x"][5:].sum() == 0  # zero rows, original dtype
    assert padded["x"].dtype == np.int32


def test_pad_to_batch_full_batch_is_identity():
    arrays = {"x": np.arange(8, dtype=np.int64)[:, None]}
    padded, valid = pad_to_batch(arrays, 8)
    assert padded["x"] is arrays["x"]  # no copy when nothing to pad
    assert valid.all()


def test_fold_valid_keeps_targets_paired_with_batch():
    """The metric targets ride in the SAME dict as the evaluated batch, so
    iteration-order changes can never misalign them."""
    arrays = {"input_ids": np.arange(10, dtype=np.int32)[:, None],
              "targets": (np.arange(10, dtype=np.int32) * 7)[:, None]}
    for batch, valid in fold_valid(batch_iterator(arrays, 4)):
        assert batch["valid"].dtype == np.int32
        np.testing.assert_array_equal(batch["valid"].astype(bool), valid)
        # Pairing: target rows are exactly 7x their input rows wherever valid.
        sel = valid
        np.testing.assert_array_equal(
            batch["targets"][sel, 0], batch["input_ids"][sel, 0] * 7
        )


def test_prefetch_to_device_ordering_under_slow_consumer():
    """A consumer slower than the producer must still see every batch in
    order — the bounded queue blocks the producer rather than dropping or
    reordering."""
    from genrec_tpu.parallel import get_mesh

    arrays = {"x": np.arange(40, dtype=np.int32)[:, None]}
    seen = []
    for batch, _ in prefetch_to_device(batch_iterator(arrays, 8), get_mesh(), size=2):
        time.sleep(0.02)  # slower than the host-side gather
        seen.append(np.asarray(batch["x"])[:, 0].copy())
    np.testing.assert_array_equal(np.concatenate(seen), np.arange(40))


# ------------------------------------------------------------------ packer


def test_ffd_bins_are_legal_and_deterministic():
    rng = np.random.default_rng(0)
    lengths = rng.integers(1, 17, 200)
    bins = first_fit_decreasing(lengths, 16)
    placed = sorted(i for b in bins for i in b)
    assert placed == list(range(200))  # every example exactly once
    for b in bins:
        assert sum(int(lengths[i]) for i in b) <= 16  # no overflow
    assert bins == first_fit_decreasing(lengths, 16)  # deterministic


def test_ffd_max_segments_cap():
    """Capping segments per row bounds the per-row segment count (and so
    the per-segment work consumers allocate) at a small occupancy cost."""
    lengths = [2] * 30  # would otherwise pack 8 per 16-slot row
    bins = first_fit_decreasing(lengths, 16, max_segments=3)
    assert sorted(i for b in bins for i in b) == list(range(30))
    assert max(len(b) for b in bins) <= 3
    packed, rep = pack_examples(
        [{"input_ids": np.ones(2, np.int32)} for _ in range(30)],
        16, max_segments=3,
    )
    assert rep.max_segments <= 3


def test_ffd_rejects_oversized_and_empty():
    with pytest.raises(ValueError):
        first_fit_decreasing([4, 20], 16)
    with pytest.raises(ValueError):
        first_fit_decreasing([4, 0], 16)


def _examples(n=40, row=16, seed=0, with_seg_key=False):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        ln = int(rng.integers(1, row + 1))
        ids = rng.integers(1, 50, ln).astype(np.int32)
        ids[0] = 1000 + i  # token streams unique per example
        ex = {"input_ids": ids,
              "targets": rng.integers(1, 50, ln).astype(np.int32)}
        if with_seg_key:
            ex["target_ids"] = rng.integers(0, 8, 3).astype(np.int32)
        out.append(ex)
    return out


def test_pack_examples_layout_invariants():
    exs = _examples()
    packed, rep = pack_examples(exs, 16)
    seg = packed["segment_ids"]
    pos = packed["positions"]
    assert rep.n_examples == 40 and rep.n_rows == seg.shape[0]
    assert rep.real_tokens == sum(len(e["input_ids"]) for e in exs)
    assert 0 < rep.occupancy <= 1.0
    # Segments contiguous, 1-based, positions restart at 0 per segment.
    for r in range(seg.shape[0]):
        row = seg[r]
        nz = row[row != 0]
        # contiguous ascending blocks: 1,1,..,2,2,..  (never interleaved)
        assert (np.diff(nz) >= 0).all() and nz[0] == 1
        for s in np.unique(nz):
            sl = row == s
            p = pos[r][sl]
            np.testing.assert_array_equal(p, np.arange(len(p)))
        # padding tail is all-zero in every token array
        assert packed["input_ids"][r][row == 0].sum() == 0


@pytest.mark.parametrize("max_segments,seed", [
    (None, None), (4, None), (4, (7, 3)), (2, 11),
])
def test_pack_examples_relative_distance_is_slot_distance(max_segments, seed):
    """The contract TIGER's packed encoder rests on (one shared relative-
    bias grid of SLOT distances for every row, `Tiger.forward_packed`):
    a segment is ONE contiguous run of slots numbered arange(n), so for
    every same-segment pair positions[k] - positions[q] == k - q — under
    a segment cap and under the trainers' per-epoch repack seeds too."""
    exs = _examples(n=60, seed=9, with_seg_key=True)
    packed, rep = pack_examples(exs, 16, segment_keys=("target_ids",),
                                max_segments=max_segments, seed=seed)
    seg, pos = packed["segment_ids"], packed["positions"]
    slot = np.arange(seg.shape[1])
    for r in range(rep.n_rows):
        for s in range(1, int(seg[r].max()) + 1):
            run = np.flatnonzero(seg[r] == s)
            # one contiguous run, and positions count up from 0 over it
            np.testing.assert_array_equal(run, np.arange(run[0], run[0] + len(run)))
            np.testing.assert_array_equal(pos[r, run], np.arange(len(run)))
    same = (seg[:, :, None] == seg[:, None, :]) & (seg[:, :, None] != 0)
    rel_pos = pos[:, None, :] - pos[:, :, None]      # (R, q, k): pos[k] - pos[q]
    rel_slot = np.broadcast_to(slot[None, :] - slot[:, None], rel_pos.shape)
    assert same.sum() > seg.shape[0] * 2
    np.testing.assert_array_equal(rel_pos[same], rel_slot[same])


def test_pack_examples_roundtrips_every_example():
    exs = _examples(seed=3)
    packed, rep = pack_examples(exs, 16)
    # Reconstruct (input_ids, targets) multisets segment by segment.
    got = []
    for r in range(rep.n_rows):
        seg = packed["segment_ids"][r]
        for s in np.unique(seg[seg != 0]):
            sl = seg == s
            got.append((tuple(packed["input_ids"][r][sl]),
                        tuple(packed["targets"][r][sl])))
    want = [(tuple(e["input_ids"]), tuple(e["targets"])) for e in exs]
    assert sorted(got) == sorted(want)


def test_pack_examples_segment_keys_follow_their_example():
    exs = _examples(with_seg_key=True, seed=5)
    packed, rep = pack_examples(exs, 16, segment_keys=("target_ids",))
    assert packed["target_ids"].shape == (rep.n_rows, rep.max_segments, 3)
    assert packed["segment_valid"].sum() == len(exs)
    by_tokens = {tuple(e["input_ids"]): e["target_ids"] for e in exs}
    for r in range(rep.n_rows):
        seg = packed["segment_ids"][r]
        for s in np.unique(seg[seg != 0]):
            tok = tuple(packed["input_ids"][r][seg == s])
            assert packed["segment_valid"][r, s - 1] == 1
            np.testing.assert_array_equal(
                packed["target_ids"][r, s - 1], by_tokens[tok]
            )
    # Invalid segment slots are zeroed.
    inv = packed["segment_valid"] == 0
    assert packed["target_ids"][inv].sum() == 0


def test_right_align_moves_left_padded_rows():
    arrays = {
        "input_ids": np.asarray([[0, 0, 3, 4], [1, 2, 3, 4], [0, 0, 0, 9]], np.int32),
        "timestamps": np.asarray([[0, 0, 70, 80], [10, 20, 30, 40], [0, 0, 0, 90]], np.int64),
        "targets": np.asarray([[5], [6], [7]], np.int32),  # untouched (shape differs)
    }
    out = right_align(arrays)
    np.testing.assert_array_equal(
        out["input_ids"], [[3, 4, 0, 0], [1, 2, 3, 4], [9, 0, 0, 0]]
    )
    np.testing.assert_array_equal(
        out["timestamps"], [[70, 80, 0, 0], [10, 20, 30, 40], [90, 0, 0, 0]]
    )
    np.testing.assert_array_equal(out["targets"], arrays["targets"])


def test_batch_iterator_start_batch_resumes_exact_order():
    """The mid-epoch resume cursor: start_batch=k yields exactly the
    batches an uninterrupted iteration would have yielded from index k,
    under the same (seed, epoch) shuffle."""
    arrays = {"x": np.arange(37, dtype=np.int32)[:, None]}
    kw = dict(shuffle=True, seed=3, epoch=2, drop_last=True)
    full = [b["x"] for b, _ in batch_iterator(arrays, 5, **kw)]
    for k in (0, 1, 3, len(full)):
        tail = [b["x"] for b, _ in batch_iterator(arrays, 5, start_batch=k, **kw)]
        assert len(tail) == len(full) - k
        for a, b in zip(full[k:], tail):
            np.testing.assert_array_equal(a, b)


# ------------------------------------------------- packer against its oracle
#
# The packer's item-by-item scan and per-example fill, kept verbatim as the
# oracle: the run-at-a-time FFD and the columnar fill must give the same
# bins and the same arrays, dtypes included.


def _ref_first_fit_decreasing(lengths, capacity, max_segments=None):
    lengths = np.asarray(lengths, np.int64)
    if lengths.size and int(lengths.max()) > capacity:
        raise ValueError(
            f"example length {int(lengths.max())} exceeds row capacity {capacity}"
        )
    if (lengths <= 0).any():
        raise ValueError("every example must have at least one token")
    order = np.argsort(-lengths, kind="stable")
    bins = []
    n_bins = 0
    remaining = np.empty(len(lengths), np.int64)  # at most one bin/example
    for idx in order:
        n = int(lengths[idx])
        fits = np.nonzero(remaining[:n_bins] >= n)[0]
        if fits.size:
            b = int(fits[0])
            bins[b].append(int(idx))
            remaining[b] -= n
            if max_segments is not None and len(bins[b]) == max_segments:
                remaining[b] = -1  # full: no further examples
        else:
            bins.append([int(idx)])
            remaining[n_bins] = capacity - n
            if max_segments == 1:
                remaining[n_bins] = -1
            n_bins += 1
    return bins


def _ref_pack_examples(examples, row_len, *, segment_keys=(), max_segments=None,
                       seed=None):
    if not examples:
        raise ValueError("pack_examples needs at least one example")
    if seed is not None:
        perm = np.random.default_rng(seed).permutation(len(examples))
        examples = [examples[int(i)] for i in perm]
    seg_keys = tuple(segment_keys)
    token_keys = [k for k in examples[0].keys() if k not in seg_keys]
    if not token_keys:
        raise ValueError("examples carry no token arrays")
    lengths = [len(np.asarray(ex[token_keys[0]])) for ex in examples]
    for ex, n in zip(examples, lengths):
        for k in token_keys:
            if len(np.asarray(ex[k])) != n:
                raise ValueError(f"token key {k!r} length mismatch within example")
    bins = _ref_first_fit_decreasing(lengths, row_len, max_segments)
    R = len(bins)
    S = max_segments if max_segments is not None else max(len(b) for b in bins)

    out = {
        k: np.zeros((R, row_len), np.asarray(examples[0][k]).dtype)
        for k in token_keys
    }
    out["segment_ids"] = np.zeros((R, row_len), np.int32)
    out["positions"] = np.zeros((R, row_len), np.int32)
    for k in seg_keys:
        proto = np.asarray(examples[0][k])
        out[k] = np.zeros((R, S) + proto.shape, proto.dtype)
    out["segment_valid"] = np.zeros((R, S), np.int32)

    real_tokens = 0
    for r, bin_idx in enumerate(bins):
        cursor = 0
        for s, idx in enumerate(bin_idx):
            n = lengths[idx]
            sl = slice(cursor, cursor + n)
            for k in token_keys:
                out[k][r, sl] = np.asarray(examples[idx][k])
            out["segment_ids"][r, sl] = s + 1
            out["positions"][r, sl] = np.arange(n)
            for k in seg_keys:
                out[k][r, s] = np.asarray(examples[idx][k])
            out["segment_valid"][r, s] = 1
            cursor += n
            real_tokens += n
    return out, (len(examples), R, row_len, real_tokens, S)


def _corpus(kind, seed, capacity):
    """Lengths of one seeded corpus of the given kind."""
    rng = np.random.default_rng([seed, capacity])
    if kind == "random":
        return rng.integers(1, capacity + 1, int(rng.integers(1, 400)))
    if kind == "skewed":  # short histories dominate, as in the Amazon cell
        return np.minimum(1 + rng.geometric(0.15, 500), capacity)
    if kind == "runs":  # long runs of equal lengths, in shuffled order
        vals = rng.integers(1, capacity + 1, 6)
        return rng.permutation(np.repeat(vals, rng.integers(1, 80, 6)))
    if kind == "at_capacity":  # whole rows mixed with items that fill gaps
        return rng.permutation(np.r_[np.full(30, capacity),
                                     rng.integers(1, capacity + 1, 120)])
    if kind == "single":
        return rng.integers(1, capacity + 1, 1)
    if kind == "all_equal":
        return np.full(int(rng.integers(1, 300)), int(rng.integers(1, capacity + 1)))
    raise ValueError(kind)


_KINDS = ("random", "skewed", "runs", "at_capacity", "single", "all_equal")


@pytest.mark.parametrize("max_segments", [None, 1, 2, 3, 4])
@pytest.mark.parametrize("kind", _KINDS)
def test_ffd_matches_scan_oracle(kind, max_segments):
    for seed in range(8):
        for capacity in (1, 7, 16, 61):
            lengths = _corpus(kind, seed, capacity)
            assert first_fit_decreasing(lengths, capacity, max_segments) == (
                _ref_first_fit_decreasing(lengths, capacity, max_segments)
            ), (kind, seed, capacity)


def test_ffd_rejects_a_cap_below_one():
    with pytest.raises(ValueError, match="max_segments"):
        first_fit_decreasing([3, 4], 16, max_segments=0)


def test_ffd_of_nothing_is_no_bins():
    assert first_fit_decreasing([], 16) == []


def _rich_examples(n, row, seed, mixed_dtypes=False):
    """Examples as the TIGER cell makes them: int32 token keys, a float
    token key, a shaped segment key and a 0-d one. With ``mixed_dtypes``
    later examples carry wider dtypes than the first, which the packer
    casts down to the first (permuted) example's."""
    rng = np.random.default_rng([seed, 5])
    out = []
    for i in range(n):
        ln = int(rng.integers(1, row + 1))
        wide = mixed_dtypes and i % 3 == 1
        out.append({
            "item_input_ids": rng.integers(1, 5000, ln).astype(
                np.int64 if wide else np.int32),
            "token_type_ids": (np.arange(ln) % 3).astype(np.int32),
            "timestamps": rng.random(ln).astype(np.float64 if wide else np.float32),
            "target_ids": rng.integers(0, 256, 3).astype(np.int64 if wide else np.int32),
            "example_id": np.int32(i),
        })
    return out


def _assert_packs_equal(got, want):
    arrays, rep = got
    ref_arrays, ref_rep = want
    assert (rep.n_examples, rep.n_rows, rep.row_len, rep.real_tokens,
            rep.max_segments) == ref_rep
    assert list(arrays) == list(ref_arrays)
    for k, v in ref_arrays.items():
        assert arrays[k].dtype == v.dtype, k
        assert arrays[k].shape == v.shape, k
        np.testing.assert_array_equal(arrays[k], v, err_msg=k)


@pytest.mark.parametrize("mixed_dtypes", [False, True])
@pytest.mark.parametrize("max_segments", [None, 1, 4])
@pytest.mark.parametrize("seed", [None, (7, 3), 2**31 + 12345])
def test_pack_examples_matches_per_example_oracle(seed, max_segments, mixed_dtypes):
    kw = dict(segment_keys=("target_ids", "example_id"),
              max_segments=max_segments, seed=seed)
    for corpus_seed, (n, row) in enumerate([(1, 16), (37, 16), (300, 61)]):
        exs = _rich_examples(n, row, corpus_seed, mixed_dtypes)
        _assert_packs_equal(pack_examples(exs, row, **kw),
                            _ref_pack_examples(exs, row, **kw))


def test_pack_examples_rejects_token_length_mismatch():
    exs = _examples(n=6)
    exs[4] = {**exs[4], "targets": np.ones(len(exs[4]["input_ids"]) + 1, np.int32)}
    with pytest.raises(ValueError, match="'targets' length mismatch"):
        pack_examples(exs, 16)
    with pytest.raises(ValueError, match="'targets' length mismatch"):
        _ref_pack_examples(exs, 16)


@pytest.mark.parametrize("epoch", [0, 1, 2])
def test_pack_examples_matches_oracle_on_the_tiger_cell_corpus(epoch):
    """The TIGER training cell's own corpus (its generator and config,
    cut to 3,000 examples), repacked as the cell repacks it each epoch."""
    import json
    from pathlib import Path

    from benchmark.configs.tiger_amazon import adapter

    root = Path(__file__).resolve().parents[1] / "benchmark"
    cfg = json.loads((root / "configs/tiger_amazon/config.json").read_text())
    traffic = json.loads((root / "traffic/train_packed.json").read_text())
    traffic["corpus_examples"] = 3000
    seed = 1
    exs = adapter.make_examples(cfg, traffic, seed, adapter.make_catalog(cfg, seed))
    kw = dict(segment_keys=("target_ids", "example_id"),
              max_segments=int(traffic["pack_max_segments"]), seed=(seed, epoch))
    row_len = 1 + cfg["max_items"] * cfg["sem_id_dim"]
    _assert_packs_equal(pack_examples(exs, row_len, **kw),
                        _ref_pack_examples(exs, row_len, **kw))
