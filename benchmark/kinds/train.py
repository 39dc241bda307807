"""A training cell: set-up builds ONE object (compiled step + state), drives
it from the seed through its first steps by the window's own call and feed,
and hands the same object to the measured window. The plain reference then
follows those first steps from the same weights and raw examples.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import device as devmod
from benchmark.harness import tracing

B1 = 0.9  # Adam's first-moment decay: mu_1 = (1 - B1) * g_1


def _find_mu(opt_state):
    """The Adam first moment inside an optax state, whatever wraps it."""
    found = []

    def visit(node):
        if hasattr(node, "mu") and hasattr(node, "nu"):
            found.append(node.mu)
        elif isinstance(node, (tuple, list)):
            for c in node:
                visit(c)

    visit(opt_state)
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0]


@jax.jit
def _leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


@jax.jit
def _leaf_diff_norms(a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b)


@jax.jit
def _along(new, old):
    """Over the whole tree: (new - old) . old, the part of a change that
    lies along the weights it started from, and old . old."""
    pairs = list(zip(*map(jax.tree_util.tree_leaves, (new, old))))
    f32 = lambda x: x.astype(jnp.float32)
    return (sum(jnp.sum((f32(x) - f32(y)) * f32(y)) for x, y in pairs),
            sum(jnp.sum(jnp.square(f32(y))) for _, y in pairs))


def _flat(tree) -> dict:
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path): float(v)
            for path, v in leaves}


def worst_leaf_gap(prog: dict, ref: dict, skip=()) -> tuple[float, str]:
    """Gap between the program's norm and the reference's, leaf by leaf,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger. Returns the worst gap and its leaf."""
    names = [n for n in ref if n not in skip]
    med = float(np.median([ref[n] for n in names])) if names else 0.0
    worst, where = 0.0, ""
    for n in names:
        den = max(ref[n], med)
        gap = abs(prog[n] - ref[n]) / den if den > 0 else 0.0
        if gap > worst:
            worst, where = gap, n
    return worst, where


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """Every number compared, beside its limit."""
    checks = {}
    for i, (lp, lr) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        checks[f"loss_gap_step{i}"] = abs(lp - lr) / abs(lr)
    if len(prog["losses"]) < len(ref["losses"]):
        checks["loss_gap_step1"] = float("inf")  # a step never came
    g_ref = ref["grad_norms"]
    checks["grad_gap"], _ = worst_leaf_gap(prog["grad_norms"], g_ref)
    # Leaves whose reference gradient is nought to rounding move under
    # Adam by round-off alone: left out of the change, by this rule.
    med = float(np.median(list(g_ref.values())))
    skip = {n for n, v in g_ref.items() if v < 1e-3 * med}
    checks["change_gap"], _ = worst_leaf_gap(
        prog["change_norms"], ref["change_norms"], skip)
    # Weight decay is a few thousandths of a warm-up step's norm, so the
    # norms above cannot see it; along the starting weights it is the
    # whole of what the two sides may differ by. Over the whole tree,
    # against what the configuration's decay moves the reference by.
    checks["decay_gap"] = (abs(prog["change_along"] - ref["change_along"])
                           / ref["decay_along"])
    out = {}
    for name, value in checks.items():
        key = "loss_gap" if name.startswith("loss_gap") else name
        if key in limits:
            out[name] = {"value": float(value), "limit": float(limits[key])}
    return out


def reference_readings(cell, entry, batches, mode="f32") -> dict:
    """The plain reference over the same first steps, from the weights the
    seed gives and the raw examples the batches name. Runs after the window
    has closed and the program's state is freed."""
    ref = cell.reference
    adapter = cell.adapter
    cfg = cell.config
    params0 = adapter.make_params(cfg, entry.seed)
    examples = []
    for b in batches:
        ids = b["example_id"][b["segment_valid"] == 1]
        examples.append(adapter.reference_examples(entry.examples, ids, cfg))
    key = jax.random.key((entry.seed + 7919) % (2**31 - 1)) \
        if cfg["dropout"] > 0 else None
    out = ref.train_steps(params0, cfg, cfg["optimizer"], examples, mode=mode,
                          key=key, block_rows=int(cell.traffic["reference_block_rows"]))
    opt = cfg["optimizer"]
    lr_sum = sum(ref.lr_at(opt, i) for i in range(len(examples)))
    along, square = _along(out["params"], params0)
    return {
        "losses": out["losses"],
        "grad_norms": _flat(_leaf_norms(out["first_grad"])),
        "change_norms": _flat(_leaf_diff_norms(out["params"], params0)),
        "change_along": float(along),
        "decay_along": float(opt["weight_decay"] * lr_sum * square),
    }


def program_readings(entry, n_steps: int) -> dict:
    return {
        "losses": entry.tracker.step_losses[:n_steps],
        "grad_norms": _flat(jax.tree_util.tree_map(
            lambda x: x / (1.0 - B1), entry.snapshots[1])),
        "change_norms": _flat(entry.snapshots[n_steps][0]),
        "change_along": float(entry.snapshots[n_steps][1][0]),
    }


def run(cell, seed: int, seconds: float, trace: bool, t_begin: float,
        control: bool = False) -> dict:
    cfg, traffic = cell.config, cell.traffic
    n_check = int(traffic["check_steps"])
    span_tracer = None
    if trace:
        from genrec_tpu.obs.spans import SpanTracer

        span_tracer = SpanTracer(capacity=100_000, enabled=True)
    entry = cell.adapter.build_train(cfg, traffic, seed, cell.chips,
                                     tracer=span_tracer)
    params0 = entry.params0
    entry.snap_at[1] = lambda st: _leaf_norms(_find_mu(st.opt_state))
    entry.snap_at[n_check] = lambda st: (
        _leaf_diff_norms(st.params, params0), _along(st.params, params0))
    batches = entry.first_batches(n_check)

    # First steps (they compile) through the window's own call and feed,
    # then two more as warm-up (the epoch's end runs too). All set-up.
    entry.run_epoch(max_steps=n_check)
    entry.run_epoch(start_batch=n_check, max_steps=n_check + 2)
    jax.block_until_ready(entry.state.params)
    prog = program_readings(entry, n_check)

    tr = tracing.Tracer(cell, enabled=trace)
    g0 = entry.loop.goodput.run_report()
    repack0, steps0 = entry.repack_seconds, entry.steps_done
    tokens = slots = 0
    setup_s = time.monotonic() - t_begin
    t0 = time.monotonic()
    tr.maybe_start(t0, seconds)
    entry.on_step = tr.poll
    while time.monotonic() - t0 < seconds:
        entry.epoch += 1
        res = entry.run_epoch()
        tok, slo = entry.epoch_tokens(res.n_batches)
        tokens += tok
        slots += slo
        tr.poll()
    jax.block_until_ready(entry.state.params)
    t1 = time.monotonic()
    entry.on_step = None
    tr.stop()
    window_s = t1 - t0
    g1 = entry.loop.goodput.run_report()
    steps = entry.steps_done - steps0
    peak = devmod.memory_peak_bytes()

    ctx = {
        "cell": cell, "kind": "train", "window_s": window_s,
        "chips": cell.chips, "tokens": tokens, "slot_tokens": slots,
        "steps": steps,
        "data_wait_s": g1["buckets"]["data_wait"] - g0["buckets"]["data_wait"],
        "repack_s": entry.repack_seconds - repack0,
        "enc_tokens_per_example": entry.mean_history_tokens(),
        "memory_peak_bytes": peak, "bytes_limit": devmod.bytes_limit(),
        "spans": list(span_tracer.spans()) if span_tracer else [],
        "repack_spans": list(entry.repack_spans), "trace": tr,
    }
    e2e = {
        "train_tokens_per_s_per_chip": tokens / window_s / cell.chips,
        "setup_s": setup_s,
    }
    entry.close()
    del params0

    ref = reference_readings(cell, entry, batches)
    checks = compare(prog, ref, cell.config["limits"]["train"])
    result = {
        "attempted": steps, "failed": 0,
        "checks": checks, "e2e": e2e, "ctx": ctx,
    }
    if control:
        low = reference_readings(cell, entry, batches, mode="fp8")
        result["control_checks"] = compare(low, ref, cell.config["limits"]["train"])
    return result
