"""The serving cell's ``correct`` at a tiny size on the CPU: one seed gives
the same verdict however the batches form, the lower-precision control fails
it, and an answer altered where it is produced fails it."""

import copy
import dataclasses

import numpy as np
import pytest

from bench_tiny import FAKE_DEVICE, tiny_root


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One engine, three formations of the same requests."""
    from genrec_tpu.kernels.policy import interpret_mode

    from benchmark.harness.spec import Spec
    from benchmark.harness.traffic import deployment_trace

    cell = Spec(tiny_root(tmp_path_factory.mktemp("serve"))).cell("tiger_serve_steady")
    cfg, seed = cell.config, 2**31 + 5
    with interpret_mode():
        engine, head, params, catalog = cell.adapter.build_serve(
            cfg, cell.traffic, seed)
        try:
            _, base = deployment_trace(cell.traffic, 1.0, cfg["max_items"],
                                       len(catalog), seed)
            at_once = [dataclasses.replace(a, due_s=0.0) for a in base]
            one_by_one = None  # each alone: sent when the last has answered
            order = np.random.default_rng(0).permutation(len(base))
            shuffled = [dataclasses.replace(base[j], due_s=0.002 * i)
                        for i, j in enumerate(order)]
            runs = {}
            for name, arrivals in (("at_once", at_once), ("one_by_one", one_by_one),
                                   ("shuffled", shuffled)):
                # Idle engine: forget retained prefixes, so that every
                # formation prefills in its own buckets.
                engine._runners[head.name].clear_prefix_cache("test")
                groups = [at_once[i:i + 1] for i in range(len(base))] \
                    if arrivals is None else [arrivals]
                records = []
                for g in groups:
                    records += cell.kind.drive(
                        engine, cell.adapter.make_request, head.name, g)[0]
                runs[name] = [r for r in records if r.response is not None]
                assert len(runs[name]) == len(base)
        finally:
            engine.stop()
    return cell, params, catalog, seed, runs


def _verdict(checks):
    return all(c["value"] <= c["limit"] for c in checks.values())


def test_same_verdict_under_three_batch_formations(served):
    cell, params, catalog, seed, runs = served
    check = cell._config_module("check")
    buckets = set()
    for name, done in runs.items():
        checks, _ = check.judge_served(cell, params, catalog, done, seed)
        assert _verdict(checks), (name, checks)
        buckets.add(tuple(sorted({r.response.bucket for r in done})))
    # The formations really differed: not every run used the same buckets.
    assert len(buckets) > 1


def test_lower_precision_control_fails(served):
    cell, params, catalog, seed, runs = served
    check = cell._config_module("check")
    _, extra = check.judge_served(cell, params, catalog, runs["at_once"], seed,
                                  control=True)
    assert not _verdict(extra["control_checks"])
    assert set(extra["control_checks"]) == {"score_gap", "beam_gap"}
    assert (extra["control_checks"]["score_gap"]["value"]
            > 3 * cell.config["limits"]["serve"]["score_gap"])


def test_altered_answer_fails(served):
    cell, params, catalog, seed, runs = served
    check = cell._config_module("check")
    done = copy.deepcopy(check.sample(runs["at_once"], 12, seed))
    r = done[0].response
    legal = {tuple(t) for t in np.asarray(catalog).tolist()}
    beam = [int(c) for c in r.sem_ids[0]]
    for c in range(cell.config["codebook_size"]):  # another real item's code
        alt = (beam[0], beam[1], c)
        if c != beam[2] and alt in legal:
            r.sem_ids = np.array(r.sem_ids)
            r.sem_ids[0, 2] = c
            break
    else:
        r.sem_ids = np.array(r.sem_ids)
        r.sem_ids[0, 2] = (beam[2] + 1) % cell.config["codebook_size"]
    checks, _ = check.judge_served(cell, params, catalog, done, seed)
    assert not _verdict(checks)


def test_a_run_with_a_token_altered_where_it_is_produced_is_not_correct(tmp_path):
    """The rest of a run after the look for a chip, sound and then with the
    head altering a code in ``paged_finalize``."""
    from genrec_tpu.kernels.policy import interpret_mode

    from benchmark import run as brun
    from benchmark.harness import faults
    from benchmark.harness.spec import Spec

    cell = Spec(tiny_root(tmp_path)).cell("tiger_serve_steady")
    with interpret_mode():
        sound = brun.run_cell(cell, 2**31 + 6, 0.5, False, FAKE_DEVICE, 0.0)
        undo = faults.plant_altered_token(cell.adapter)
        try:
            broken = brun.run_cell(cell, 2**31 + 6, 0.5, False, FAKE_DEVICE, 0.0)
        finally:
            undo()
    assert sound["correct"] is True, sound["checks"]
    assert set(sound["metrics"]) == {"serve_latency_p50_ms",
                                     "serve_completed_per_s", "setup_s"}
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert broken["correct"] is False
    assert (broken["checks"]["bad_items"]["value"] > 0
            or broken["checks"]["score_gap"]["value"]
            > broken["checks"]["score_gap"]["limit"])


def test_set_up_leaves_the_prefix_cache_at_its_cap(tmp_path):
    """``build`` sends the deployment's past: the window opens on a cache
    that holds as many entries as it may, and a pool that holds their pages."""
    from genrec_tpu.kernels.policy import interpret_mode

    from benchmark.harness.spec import Spec

    cell = Spec(tiny_root(tmp_path)).cell("tiger_serve_steady")
    with interpret_mode():
        engine, head, _, _, arrivals = cell.kind.build(cell, 2**31 + 8, 0.5)
        try:
            stats = engine.stats()
        finally:
            engine.stop()
    cap = cell.config["assumed"]["serve"]["prefix_cache_entries"]
    assert stats["prefix_cache"][head.name]["entries"] == cap
    assert stats["kv_pool"][head.name]["pages_in_use"] >= cap
    assert len(arrivals) == round(cell.traffic["rate_per_s"] * 0.5)
