"""What decides ``correct`` for served TIGER answers.

Judged from what each answer says, so the verdict cannot depend on arrival
timing, on which requests shared a micro-batch or a decode step, or on slot
order: for a sample of finished requests (drawn from the seed, the longest
history in it) the plain reference runs once over each prompt with its
served beams, and three numbers are compared.

``score_gap``  the widest gap between a served beam's score and the
    reference's own sum of tempered, trie-masked log-probabilities along
    that beam. Covers the encoder prefill, the page writes and paged reads,
    the suffix caches, the trie mask and the beam's bookkeeping.
``beam_gap``   the widest gap by which the served beam set lies below what
    it must hold whatever the batches were: every served beam starts with
    one of the reference's best K first codes, and no legal sibling of a
    served beam's last code scores above the worst served beam. (The middle
    level is left out on purpose: which prefixes survive it depends on ties
    that rounding breaks, and a check of it would flip with timing.)
``bad_items``  beams that are not catalog items, whose item id is not the
    catalog's, or whose scores are out of order. Limit 0.
"""

from __future__ import annotations

import numpy as np


def sample(done, n: int, seed: int):
    """A sample of finished requests drawn from the seed, the longest
    history always in it."""
    if not done:
        return []
    rng = np.random.default_rng([seed, 51])
    idx = rng.permutation(len(done))[:n].tolist()
    longest = int(np.argmax([len(r.arrival.history) for r in done]))
    if longest not in idx:
        idx[-1] = longest
    return [done[i] for i in idx]


def _prompt_arrays(cfg, catalog_ids, recs):
    D, L = cfg["sem_id_dim"], cfg["max_items"] * cfg["sem_id_dim"]
    n = len(recs)
    hist = np.zeros((n, L), np.int32)
    n_tok = np.zeros(n, np.int32)
    user = np.zeros(n, np.int32)
    for i, r in enumerate(recs):
        h = np.asarray(r.arrival.history, np.int64)[-cfg["max_items"]:]
        hist[i, :len(h) * D] = catalog_ids[h].reshape(-1)
        n_tok[i] = len(h) * D
        user[i] = r.arrival.user_id % cfg["num_user_embeddings"]
    return user, hist, n_tok


def numbers(catalog, logp, beams, scores, items):
    """The three numbers from reference rows ``logp`` (N, K, depth, cb)."""
    N, K, depth = beams.shape
    score_gap = beam_gap = 0.0
    bad = 0
    for n in range(N):
        path = np.zeros((K, depth))
        for k in range(K):
            tup = tuple(int(c) for c in beams[n, k])
            if catalog.item_of.get(tup, -2) != int(items[n, k]):
                bad += 1
            for d in range(depth):
                path[k, d] = logp[n, k, d, beams[n, k, d]]
        total = path.sum(axis=1)
        if not np.all(np.isfinite(total)):
            bad += int((~np.isfinite(total)).sum())
            continue
        if np.any(np.diff(scores[n]) > 1e-6):
            bad += 1
        score_gap = max(score_gap, float(np.max(np.abs(scores[n] - total))))
        # First codes: every served beam starts inside the best K.
        row0 = logp[n, 0, 0]
        legal0 = np.sort(row0[np.isfinite(row0)])[::-1]
        kth = legal0[min(K, len(legal0)) - 1]
        beam_gap = max(beam_gap, float(kth - path[:, 0].min()))
        # Last codes: no unserved legal sibling beats the worst served beam.
        worst = total.min()
        served = {tuple(int(c) for c in b) for b in beams[n]}
        for k in range(K):
            pre = tuple(int(c) for c in beams[n, k, :depth - 1])
            base = path[k, :depth - 1].sum()
            row = logp[n, k, depth - 1]
            for c in np.nonzero(np.isfinite(row))[0]:
                if pre + (int(c),) not in served:
                    beam_gap = max(beam_gap, float(base + row[c] - worst))
    return {"score_gap": score_gap, "beam_gap": beam_gap, "bad_items": float(bad)}


def _path_scores(logp, beams):
    N, K, depth = beams.shape
    out = np.zeros((N, K))
    for n in range(N):
        for k in range(K):
            out[n, k] = sum(logp[n, k, d, beams[n, k, d]] for d in range(depth))
    return out


def control_beam_gap(logp, low, beams) -> float:
    """What ``beam_gap`` reads with the lower precision in the program's
    place: at the same prompts and prefixes, the codes the control puts
    first are judged by the reference's rows. First codes: its best K
    against the reference's K-th best. Last codes: its best child of each
    served prefix against the reference's best child."""
    N, K, depth = beams.shape
    gap = 0.0
    for n in range(N):
        ref0, low0 = logp[n, 0, 0], low[n, 0, 0]
        legal = np.nonzero(np.isfinite(ref0))[0]
        k = min(K, len(legal))
        kth = np.sort(ref0[legal])[::-1][k - 1]
        picked = legal[np.argsort(low0[legal])[::-1][:k]]
        gap = max(gap, float(kth - ref0[picked].min()))
        for b in range(K):
            ref_row, low_row = logp[n, b, depth - 1], low[n, b, depth - 1]
            if np.isfinite(ref_row).any():
                gap = max(gap, float(ref_row[np.isfinite(ref_row)].max()
                                     - ref_row[int(np.argmax(low_row))]))
    return gap


def judge_served(cell, params, catalog_ids, done, seed, control=False):
    ref = cell.reference
    cfg = cell.config
    limits = cfg["limits"]["serve"]
    recs = sample(done, int(cell.traffic["check_requests"]), seed)
    if not recs:
        return {"answered": {"value": 0.0, "limit": 1.0}}, {}
    catalog = ref.Catalog(catalog_ids, cfg["codebook_size"])
    user, hist, n_tok = _prompt_arrays(cfg, catalog_ids, recs)
    beams = np.stack([np.asarray(r.response.sem_ids) for r in recs]).astype(np.int64)
    scores = np.stack([np.asarray(r.response.scores, np.float64) for r in recs])
    items = np.stack([np.asarray(r.response.items) for r in recs])
    logp = ref.served_logps(params, cfg, catalog, user, hist, n_tok, beams)
    got = numbers(catalog, logp, beams, scores, items)
    checks = {k: {"value": v, "limit": float(limits[k])} for k, v in got.items()}
    extra = {"checked_requests": len(recs)}
    if control:
        # The control: the reference one precision step below the
        # configuration's bf16, put in the program's place. It need not
        # decode: on the same prompts and beams its scores stand in for
        # the served ones.
        # It need not decode: on the same prompts and beams its scores stand
        # in for the served ones and go through ``numbers`` like them; which
        # codes it puts first is read by ``control_beam_gap``. The items are
        # the program's, so ``bad_items`` says nothing of the control.
        low = ref.served_logps(params, cfg, catalog, user, hist, n_tok, beams,
                               mode="fp8")
        ctl = {
            "score_gap": numbers(catalog, logp, beams, _path_scores(low, beams),
                                 items)["score_gap"],
            "beam_gap": control_beam_gap(logp, low, beams),
        }
        extra["control_checks"] = {
            k: {"value": v, "limit": float(limits[k])} for k, v in ctl.items()}
    return checks, extra
