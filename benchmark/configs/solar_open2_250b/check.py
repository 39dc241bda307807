"""What decides ``correct`` for served Solar-Open2 / LCRec answers.

Judged from what each answer says and from what the engine RETAINS for it,
so the verdict cannot depend on arrival timing, on which requests shared a
prefill or a decode step, on slot order or on whether the admit was cold or
warm: for a sample of the requests the TIMED window itself finished (spread
over the history lengths, the longest always in it, a warm admit and a cold
one where the window had both) the plain reference runs its full forward
pass over ``prompt ++ served beam``, a row a beam, and over the prompt alone,
and five numbers are compared.

``score_gap``  the widest gap between a served beam's score and the
    reference's own sum of trie-masked log-probabilities along that beam.
    Covers the paged prefill (page writes, the KDA end states, the first
    code's beams), the decode steps (paged reads merged with the suffix,
    the recurrent step, the reorder), the experts and the head.
``beam_gap``   the widest gap by which the served beam set lies below what
    it must hold whatever the batches were: every served beam starts with
    one of the reference's best W first codes, and no legal sibling of a
    served beam's last code scores above the worst served beam. (The middle
    levels are left out on purpose: which prefixes survive them depends on
    ties that rounding breaks.)
``bad_items``  beams that are not catalog items, whose item id is not the
    catalog's, or whose scores are out of order. Limit 0.
``state_gap``  the recurrent state itself: of the snapshot the prefix index
    retains for the request (``kda_s0_*``: what a warm admit binds and every
    beam starts from), the widest relative distance, a KDA layer, to the
    reference's state after the prompt's last token,
    |S - S_ref| / |S_ref| over the layer's heads. A request whose entry the
    index dropped before the close is passed over (``checked_states`` counts
    the rest); with none left the gap reads 1, as a state left at zero would.
``state_bf16_share``  the share of those snapshots' elements that bfloat16
    holds exactly. The configuration states the state float32
    (``state_dtype``); a state carried in bfloat16 reads 1 here and, at the
    real widths, NOTHING else: it moves a score and the state's norm by less
    than the bfloat16 products the configuration states do (PERF.md section
    6, PR 36), so no comparison with the reference can hold a program to it.

The controls (``control=True``; the tests and the readings behind the limits
ask for them, a benchmark run never does) put a changed reference in the
program's place: ``fp8`` (matmul operands one precision below the bfloat16
the configuration states), ``beta_single`` (the write strength not doubled),
``no_gate`` (the output gate dropped), ``state_bf16`` (the recurrent state
rounded to bfloat16 after every token). Each answers the prompts ITSELF, by
the reference's plain beam search under its own arithmetic, and hands over
its own end states; its beams, scores and states then pass through the same
`numbers` and `state_numbers` a served answer does. Each must fail at least
one limit. A control's beam search costs 5 x W + W reference rows a prompt
where a check costs W, so the controls play on the ``control_requests``
shortest prompts of the sample (``config.json`` ``assumed``); their end
states, a row a prompt, are read on all of it.
"""

from __future__ import annotations

import numpy as np

CONTROLS = ("fp8", "beta_single", "no_gate", "state_bf16")


def sample(done, n: int, seed: int):
    """``n`` finished requests evenly spread over the history lengths (the
    longest always in), one of them a repeat (a warm admit) and one not,
    where the window had both. The seed breaks ties of equal length."""
    if not done:
        return []
    rng = np.random.default_rng([seed, 51])
    jitter = rng.random(len(done))
    order = sorted(range(len(done)),
                   key=lambda i: (len(done[i].arrival.history), jitter[i]))
    n = min(n, len(order))
    picks = sorted({int(round(q)) for q in np.linspace(0, len(order) - 1, n)})
    idx = [order[p] for p in picks]
    for want in (True, False):
        if not any(bool(done[i].arrival.repeat) == want for i in idx):
            pool = [i for i in order if bool(done[i].arrival.repeat) == want]
            if pool and len(idx) > 1:
                idx[0] = pool[len(pool) // 2]
    return [done[i] for i in idx]


def numbers(catalog, logp, beams, scores, items):
    """The three numbers from reference rows ``logp`` (N, W, depth, cb)."""
    N, W, depth = beams.shape
    score_gap = beam_gap = 0.0
    bad = 0
    for n in range(N):
        path = np.zeros((W, depth))
        for w in range(W):
            tup = tuple(int(c) for c in beams[n, w])
            if catalog.item_of.get(tup, -2) != int(items[n, w]):
                bad += 1
            for d in range(depth):
                path[w, d] = logp[n, w, d, beams[n, w, d]]
        total = path.sum(axis=1)
        if not np.all(np.isfinite(total)):
            bad += int((~np.isfinite(total)).sum())
            continue
        if np.any(np.diff(scores[n]) > 1e-6):
            bad += 1
        score_gap = max(score_gap, float(np.max(np.abs(scores[n] - total))))
        # First codes: every served beam starts inside the best W.
        row0 = logp[n, 0, 0]
        legal0 = np.sort(row0[np.isfinite(row0)])[::-1]
        kth = legal0[min(W, len(legal0)) - 1]
        beam_gap = max(beam_gap, float(kth - path[:, 0].min()))
        # Last codes: no unserved legal sibling beats the worst served beam.
        worst = total.min()
        served = {tuple(int(c) for c in b) for b in beams[n]}
        for w in range(W):
            pre = tuple(int(c) for c in beams[n, w, :depth - 1])
            base = path[w, :depth - 1].sum()
            row = logp[n, w, depth - 1]
            for c in np.nonzero(np.isfinite(row))[0]:
                if pre + (int(c),) not in served:
                    beam_gap = max(beam_gap, float(base + row[c] - worst))
    return {"score_gap": score_gap, "beam_gap": beam_gap, "bad_items": float(bad)}


def state_numbers(want, held) -> dict:
    """``state_gap`` and ``state_bf16_share`` of the end states ``held`` (a
    request each: (KDA layers, H, K, V), or None where the index had dropped
    the entry by the close) against the reference's ``want``. With nothing
    held at all the gap reads 1, as a state left at zero would."""
    gap, exact, size = 0.0, 0, 0
    for ref_s, got in zip(want, held):
        if got is None:
            continue
        got = np.ascontiguousarray(got, np.float32)
        for layer_ref, layer_got in zip(ref_s, got.reshape(np.shape(ref_s))):
            gap = max(gap, float(np.linalg.norm(layer_got - layer_ref)
                                 / max(np.linalg.norm(layer_ref), 1e-30)))
        # a float32 whose low 16 bits are zero is a bfloat16
        exact += int(np.count_nonzero((got.view(np.uint32) & 0xFFFF) == 0))
        size += got.size
    return {"state_gap": gap if size else 1.0,
            "state_bf16_share": exact / max(size, 1)}


def control_numbers(ref, params, cfg, catalog, prompts, want_states, width,
                    n_played: int, name: str, pad_to) -> dict:
    """One control in the program's place: its own beams and scores on the
    ``n_played`` shortest prompts, judged by the reference's rows along
    them, and its own end states on every prompt."""
    kw = {"mode": "fp8"} if name == "fp8" else {"variant": name}
    short = sorted(range(len(prompts)), key=lambda i: len(prompts[i]))[:n_played]
    played = [ref.beam_search(params, cfg, catalog, prompts[i], width,
                              pad_to=pad_to, **kw) for i in short]
    beams = np.stack([b for b, _ in played])
    scores = np.stack([s for _, s in played])
    items = np.array([[catalog.item_of[tuple(int(c) for c in b)] for b in row]
                      for row in beams])
    logp = ref.served_logps(params, cfg, catalog, [prompts[i] for i in short],
                            beams, pad_to=pad_to)
    got = numbers(catalog, logp, beams, scores, items)
    states = [ref.end_states(params, cfg, p, pad_to=pad_to, **kw) for p in prompts]
    got.update(state_numbers(want_states, states))
    return got


def _pad_to(cfg):
    """Rows of a request are padded on the left to its history bucket, so
    the reference compiles one program a bucket, not one a length."""
    D = cfg["sem_id_dim"]
    buckets = [b * D for b in cfg["assumed"]["serve"]["history_buckets"]]
    return lambda n: next((b for b in buckets if b >= n), n)


def judge_served(cell, params, catalog_ids, done, seed, control=False):
    ref = cell.reference
    cfg = cell.config
    adapter = cell.adapter
    limits = cfg["limits"]["serve"]
    recs = sample(done, int(cell.traffic["check_requests"]), seed)
    if not recs:
        return {"answered": {"value": 0.0, "limit": 1.0}}, {}
    catalog = ref.Catalog(catalog_ids, cfg["codebook_size"])
    prompts = [adapter.prompt_tokens(cfg, catalog_ids, r.arrival.history)
               for r in recs]
    beams = np.stack([np.asarray(r.response.sem_ids) for r in recs]).astype(np.int64)
    scores = np.stack([np.asarray(r.response.scores, np.float64) for r in recs])
    items = np.stack([np.asarray(r.response.items) for r in recs])
    pad_to = _pad_to(cfg)
    logp = ref.served_logps(params, cfg, catalog, prompts, beams, pad_to=pad_to)
    got = numbers(catalog, logp, beams, scores, items)
    want_states = [ref.end_states(params, cfg, p, pad_to=pad_to) for p in prompts]
    held = [adapter.retained_states(cfg, r.arrival.history) for r in recs]
    got.update(state_numbers(want_states, held))
    checks = {k: {"value": v, "limit": float(limits[k])} for k, v in got.items()}
    extra = {"checked_requests": len(recs),
             "checked_states": sum(h is not None for h in held),
             "checked_warm": int(sum(bool(r.arrival.repeat) for r in recs))}
    if control:
        by_control = {
            name: control_numbers(
                ref, params, cfg, catalog, prompts, want_states, beams.shape[1],
                int(cfg["assumed"]["control_requests"]), name, pad_to)
            for name in CONTROLS}
        extra["controls"] = {
            name: {k: {"value": v, "limit": float(limits[k])} for k, v in c.items()}
            for name, c in by_control.items()}
        # ``control_checks`` (what `benchmark.run` turns into
        # ``control_correct``) is the control that came CLOSEST to passing:
        # every control fails only if that one does.
        closest = min((by_control[name] for name in CONTROLS), key=lambda c: max(
            c[k] / max(float(limits[k]), 1e-12) for k in c))
        extra["control_checks"] = {
            k: {"value": v, "limit": float(limits[k])} for k, v in closest.items()}
    return checks, extra
