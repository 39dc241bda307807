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
