"""Compiled-path (Mosaic) validation + microbench for the Pallas kernels.

The test suite runs the kernels through the Pallas interpreter on CPU;
this module is where the actual TPU lowering is exercised. Runnable
standalone:

    python -m genrec_tpu.kernels.preflight

On a TPU it compiles every kernel with ``interpret=False``, checks it
against its XLA reference and times both paths. ``--interpret`` runs the
same legs at tiny shapes through the interpreter on CPU (no timings).
Anything else — no TPU and no ``--interpret``, or a default-on kernel
that does not compile or does not match — is a non-zero exit. One JSON
object on stdout; ``chip_smoke.py`` runs the same legs at its own shapes.

Tolerances. The XLA references run under
``jax.default_matmul_precision("highest")``. On the chip the kernels do
NOT: Mosaic multiplies fp32 operands in one bf16 pass, the same default
XLA applies to the path each kernel replaces, so a kernel differs from
the exact reference by bf16 rounding of every product — an error that
scales with the outputs (0.09 on paged accumulators of magnitude ~10,
35 on HSTU gradients of magnitude ~1e4; first chip run, PR 21). Each leg
therefore reports ``max_abs_err``, the reference's own magnitude
``ref_max_abs``, and their per-output ratio ``max_rel_err``, which is what
``tol`` bounds. ``xla_default_rel_err`` is the yardstick beside it: the
same ratio for the XLA reference at the TPU's DEFAULT precision. `TPU_TOL`
holds the bounds established on a v5e (PERF.md, Findings PR 21),
`INTERPRET_TOL` the CPU interpreter's, where everything is fp32.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

#: Kernels `kernels/policy.py` turns on by default on a TPU: the ones whose
#: failure fails the preflight. `rq_cascade` is off by default (ROADMAP D8).
DEFAULT_ON = (
    "paged_attention", "paged_attention_int8", "fused_linear_ce",
    "sharded_fused_linear_ce", "hstu_attention", "hstu_attention_bwd",
)

#: Bounds on max_rel_err (max |kernel - reference| over max |reference|,
#: per output), compiled on a TPU v5e: bf16-product rounding, see above.
#: Measured there (PR 21): 1.7e-3 (paged, bf16 pool) to 5.8e-3 (HSTU
#: backward); the bound is ~3.5x the worst row. rq_cascade asks for
#: HIGHEST precision in-kernel and lands at 1e-7.
TPU_TOL = {
    "paged_attention": 2e-2,
    "paged_attention_int8": 2e-2,
    "fused_linear_ce": 2e-2,
    "sharded_fused_linear_ce": 2e-2,
    "hstu_attention": 2e-2,
    "hstu_attention_bwd": 2e-2,
    "rq_cascade": 1e-3,
}
INTERPRET_TOL = dict.fromkeys(TPU_TOL, 1e-4)


def _bench_chained(f, x0, *rest, n=512, reps=3):
    """Per-iteration wall-time (ms) of ``n`` data-dependent applications of
    ``f`` looped ON DEVICE (lax.scan carries f's output back as its first
    argument), so one dispatch amortizes over the whole loop and a sub-ms
    kernel is not buried under it; scan compiles the kernel once
    regardless of n."""
    import jax

    @jax.jit
    def chained(x0, *rest):
        def body(x, _):
            return f(x, *rest), None

        out, _ = jax.lax.scan(body, x0, None, length=n)
        return out

    jax.block_until_ready(chained(x0, *rest))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(chained(x0, *rest))
        times.append((time.perf_counter() - t0) * 1e3 / n)
    return float(np.median(times))


def _compare(got, ref_fn, *args) -> dict:
    """Error columns of one table row: ``got`` against ``ref_fn(*args)``
    jitted at full fp32 matmul precision, and that same reference at the
    backend's default precision as the yardstick (module docstring)."""
    import jax

    with jax.default_matmul_precision("highest"):
        ref = jax.jit(ref_fn)(*args)
    ref_default = jax.jit(ref_fn)(*args)
    flat = lambda t: [  # noqa: E731
        np.asarray(x, np.float32) for x in jax.tree_util.tree_leaves(t)
    ]
    ref = flat(ref)
    scale = [max(float(np.max(np.abs(r))), 1e-30) for r in ref]

    def errs(other):
        return [float(np.max(np.abs(o - r))) for o, r in zip(flat(other), ref)]

    err = errs(got)
    return {
        "max_abs_err": max(err),
        "ref_max_abs": max(scale),
        "max_rel_err": max(e / s for e, s in zip(err, scale)),
        "xla_default_rel_err": max(
            e / s for e, s in zip(errs(ref_default), scale)
        ),
    }


def _rq_cascade_xla(x, codebooks):
    """Plain-XLA residual-quantization cascade (reference for the kernel)."""
    import jax
    import jax.numpy as jnp

    def layer(resid, cb):
        d2 = (
            jnp.sum(resid**2, -1, keepdims=True)
            - 2.0 * jnp.matmul(resid, cb.T, precision=jax.lax.Precision.HIGHEST)
            + jnp.sum(cb**2, -1)
        )
        ids = jnp.argmin(d2, -1)
        return resid - cb[ids], ids

    def scan_fn(resid, cb):
        resid, ids = layer(resid, cb)
        return resid, ids

    resid, ids = jax.lax.scan(scan_fn, x, codebooks)
    return ids.T, x - resid


# ---------------------------------------------------------------------------
# Legs. Each returns `_compare`'s columns plus whatever it wants reported,
# and RAISES when the kernel does not compile or run: `run_legs` is the one
# place that turns a refusal into a table row.
# ---------------------------------------------------------------------------


def _hstu_inputs(rng, B, H, L, D, n=3):
    import jax.numpy as jnp

    qkv = [jnp.asarray(rng.normal(size=(B, H, L, D)), jnp.float32)
           for _ in range(n)]
    ts = jnp.asarray(np.cumsum(rng.integers(3600, 2e5, (B, L)), 1), jnp.int32)
    pad = jnp.zeros((B, L), bool)
    pt = jnp.asarray(rng.normal(size=(H, 32)) * 0.1, jnp.float32)  # (H, pos buckets)
    tt = jnp.asarray(rng.normal(size=(H, 64)) * 0.1, jnp.float32)  # (H, time buckets)
    # Packed-row segments (the PR-2 operands): three segments per row.
    seg = jnp.asarray(np.broadcast_to(1 + np.arange(L) * 3 // L, (B, L)), jnp.int32)
    return qkv, ts, pad, pt, tt, seg


def leg_hstu_attention(rng, interpret, timing, shape=None):
    """HSTU fused attention forward (bench-scale: B4 H4 L200 D64; tiny in
    interpret mode, where pallas is ~1000x slower)."""
    import jax

    from genrec_tpu.kernels.hstu_attention import (
        hstu_attention_pallas,
        hstu_attention_xla,
    )

    B, H, L, D = shape or ((2, 2, 50, 32) if interpret else (4, 4, 200, 64))
    (q, k, v), ts, pad, pt, tt, seg = _hstu_inputs(rng, B, H, L, D)
    got = jax.jit(
        lambda *a: hstu_attention_pallas(*a, interpret=interpret, segment_ids=seg)
    )(q, k, v, ts, pad, pt, tt)
    entry = _compare(
        got, lambda *a: hstu_attention_xla(*a, segment_ids=seg),
        q, k, v, ts, pad, pt, tt,
    )
    entry["shape"] = [B, H, L, D]
    if timing:
        # The output has q's shape, so it scan-carries back as q.
        entry["pallas_ms"] = _bench_chained(
            lambda *a: hstu_attention_pallas(*a, segment_ids=seg),
            q, k, v, ts, pad, pt, tt,
        )
        entry["xla_ms"] = _bench_chained(
            lambda *a: hstu_attention_xla(*a, segment_ids=seg),
            q, k, v, ts, pad, pt, tt,
        )
    return entry


def leg_hstu_attention_bwd(rng, interpret, timing, shape=None):
    """HSTU fused backward (long-context scale: L=2048 compiled; the grads
    the training step actually uses)."""
    import jax

    from genrec_tpu.kernels.hstu_attention import (
        hstu_attention_bwd_pallas,
        hstu_attention_xla,
    )

    B, H, L, D = shape or ((2, 2, 50, 32) if interpret else (2, 4, 2048, 64))
    (q, k, v, g), ts, pad, pt, tt, seg = _hstu_inputs(rng, B, H, L, D, n=4)

    def xla_bwd(g, q, k, v):
        _, vjp = jax.vjp(
            lambda q, k, v, pt, tt: hstu_attention_xla(
                q, k, v, ts, pad, pt, tt, segment_ids=seg
            ),
            q, k, v, pt, tt,
        )
        return vjp(g)

    def pallas_bwd(g, q, k, v, interpret=False):
        return hstu_attention_bwd_pallas(
            q, k, v, ts, pad, pt, tt, g, interpret=interpret, segment_ids=seg
        )

    got = jax.jit(lambda *a: pallas_bwd(*a, interpret=interpret))(g, q, k, v)
    entry = _compare(got, xla_bwd, g, q, k, v)
    entry["shape"] = [B, H, L, D]
    if timing:
        # dq has g's shape: chain it back as the cotangent.
        entry["pallas_ms"] = _bench_chained(
            lambda *a: pallas_bwd(*a)[0], g, q, k, v
        )
        entry["xla_ms"] = _bench_chained(lambda *a: xla_bwd(*a)[0], g, q, k, v)
    return entry


def _ce_inputs(rng, R, V, D):
    import jax.numpy as jnp

    x = jnp.asarray(rng.normal(size=(R, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(V, D)) * 0.1, jnp.float32)
    tgt = jnp.asarray(rng.integers(0, V, (R,)), jnp.int32)
    return x, w, tgt


def leg_fused_linear_ce(rng, interpret, timing, shape=None):
    """Fused linear+CE, forward and both backward kernels (SASRec-Amazon
    scale: R=B*L=6400 rows, V~12k items, d=64 — where the materialized
    (R, V) logits cost ~300MB of HBM traffic per direction)."""
    import jax

    from genrec_tpu.kernels.fused_ce import (
        fused_linear_ce,
        fused_linear_ce_bwd,
        fused_linear_ce_fwd,
        linear_ce_xla,
    )

    R, V, D = shape or ((256, 1000, 48) if interpret else (6400, 12160, 64))
    x, w, tgt = _ce_inputs(rng, R, V, D)

    def pallas_all(x, w):
        loss, lse = fused_linear_ce_fwd(x, w, tgt, interpret=interpret)
        g = (tgt != 0).astype(loss.dtype)  # cotangent of loss.sum()
        dx, dw = fused_linear_ce_bwd(x, w, tgt, lse, g, interpret=interpret)
        return loss, dx, dw

    def xla_all(x, w):
        loss, vjp = jax.vjp(lambda x, w: linear_ce_xla(x, w, tgt), x, w)
        return (loss, *vjp((tgt != 0).astype(loss.dtype)))

    got = jax.jit(pallas_all)(x, w)
    entry = _compare(got, xla_all, x, w)
    entry["shape"] = [R, V, D]
    if timing:
        # Time the TRAINING direction (fwd+bwd): grads wrt x chain back
        # as the next iteration's x.
        entry["pallas_ms"] = _bench_chained(
            lambda x, w: jax.grad(lambda x: fused_linear_ce(x, w, tgt).sum())(x),
            x, w,
        )
        entry["xla_ms"] = _bench_chained(
            lambda x, w: jax.grad(lambda x: linear_ce_xla(x, w, tgt).sum())(x),
            x, w,
        )
    return entry


def leg_sharded_fused_linear_ce(rng, interpret, timing, shape=None):
    """Vocab-sharded fused CE (the LCRec tp>1 head path): shard_map with
    the "model" axis over every device there is — on one chip this still
    exercises the full sharded code path (axis_index, the vlim scalar
    operand, psum/pmax merge) under Mosaic compilation. Interpret mode
    runs its own pallas_call per shard, so it stays on one device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from genrec_tpu.kernels.fused_ce import linear_ce_xla, sharded_fused_linear_ce
    from genrec_tpu.kernels.policy import interpret_mode

    R, V, D = shape or ((256, 1000, 48) if interpret else (6400, 12160, 64))
    n_dev = 1 if interpret else jax.device_count()
    V -= V % n_dev
    x, w, tgt = _ce_inputs(rng, R, V, D)
    live = V - V // 16  # exercise the dynamic vocab limit
    tgt = jnp.minimum(tgt, live - 1)
    mesh = Mesh(
        np.array(jax.devices()[:n_dev]).reshape(1, n_dev), ("data", "model")
    )

    def pallas_grad(x, w):
        def loss_fn(x, w):
            return sharded_fused_linear_ce(
                x, w, tgt, mesh, "model", "data", 0, live
            ).sum()

        return jax.value_and_grad(loss_fn, argnums=(0, 1))(x, w)

    def xla_grad(x, w):
        return jax.value_and_grad(
            lambda x, w: linear_ce_xla(x, w[:live], tgt).sum(), argnums=(0, 1)
        )(x, w)

    # The sharded entry point takes no interpret argument (its custom_vjp
    # signature is the trainers'); the interpreter is asked for by mode.
    if interpret:
        with interpret_mode():
            got = jax.jit(pallas_grad)(x, w)
    else:
        got = jax.jit(pallas_grad)(x, w)
    entry = _compare(got, xla_grad, x, w)
    entry.update(shape=[R, V, D], tp=n_dev)
    if timing:
        entry["pallas_ms"] = _bench_chained(
            lambda x, w: pallas_grad(x, w)[1][0], x, w
        )
    return entry


def _paged_inputs(rng, S, Kb, H, hd, page, Pm, dtype):
    import jax.numpy as jnp

    P = 1 + S * Pm
    q = jnp.asarray(rng.normal(size=(S, Kb, H, hd)), dtype)
    kp = jnp.asarray(rng.normal(size=(P, page, H * hd)), dtype)
    vp = jnp.asarray(rng.normal(size=(P, page, H * hd)), dtype)
    # Scattered page ids: consecutive slots must not read consecutive rows.
    bt = jnp.asarray(1 + rng.permutation(S * Pm).reshape(S, Pm), jnp.int32)
    sl = jnp.asarray(rng.integers(1, Pm * page + 1, (S,)), jnp.int32)
    return q, kp, vp, bt, sl


_PAGED_SHAPE = (64, 10, 6, 64, 16, 4)  # S, beams, H, hd, page, pages/slot
_PAGED_SHAPE_INTERPRET = (4, 3, 2, 16, 16, 4)


def leg_paged_attention(rng, interpret, timing, shape=None, dtype="float32"):
    """Paged decode attention (serving-scale: 64 slots x 10 beams, H6
    hd64, 16-token pages through a block table) vs the pure-JAX gather
    fallback that CPU serving runs. ``dtype`` is the pool's; operands
    reach the reference upcast to fp32, so both sides see equal values."""
    import jax
    import jax.numpy as jnp

    from genrec_tpu.kernels.paged_attention import paged_attention_stats_pallas
    from genrec_tpu.ops.paged import _stats_fallback

    shape = shape or (_PAGED_SHAPE_INTERPRET if interpret else _PAGED_SHAPE)
    q, kp, vp, bt, sl = _paged_inputs(rng, *shape, jnp.dtype(dtype))
    got = jax.jit(
        lambda q: paged_attention_stats_pallas(q, kp, vp, bt, sl, interpret=interpret)
    )(q)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    entry = _compare(
        got, lambda q: _stats_fallback(f32(q), f32(kp), f32(vp), bt, sl), q
    )
    entry.update(shape=list(shape), pool_dtype=str(dtype))
    if timing:
        # acc has q's shape and is fp32: it scan-carries back as q.
        def acc_of(stats_fn):
            return lambda q: stats_fn(q, kp, vp, bt, sl)[0].astype(q.dtype)

        entry["pallas_ms"] = _bench_chained(acc_of(paged_attention_stats_pallas), q)
        entry["xla_ms"] = _bench_chained(acc_of(_stats_fallback), q)
    return entry


def leg_paged_attention_int8(rng, interpret, timing, shape=None):
    """The dequant-in-kernel twin over int8 pools vs the
    dequant-after-gather fallback."""
    import jax
    import jax.numpy as jnp

    from genrec_tpu.kernels.paged_attention import (
        paged_attention_stats_pallas_quantized,
    )
    from genrec_tpu.ops.paged import _stats_fallback
    from genrec_tpu.ops.quant import QuantizedKVPool, quantize_symmetric

    shape = shape or (_PAGED_SHAPE_INTERPRET if interpret else _PAGED_SHAPE)
    q, kp, vp, bt, sl = _paged_inputs(rng, *shape, jnp.float32)
    kq = QuantizedKVPool(*quantize_symmetric(kp, (-1,)))
    vq = QuantizedKVPool(*quantize_symmetric(vp, (-1,)))
    got = jax.jit(
        lambda q: paged_attention_stats_pallas_quantized(
            q, kq, vq, bt, sl, interpret=interpret
        )
    )(q)
    entry = _compare(got, lambda q: _stats_fallback(q, kq, vq, bt, sl), q)
    entry["shape"] = list(shape)
    if timing:
        entry["pallas_ms"] = _bench_chained(
            lambda q: paged_attention_stats_pallas_quantized(q, kq, vq, bt, sl)[0], q
        )
        entry["xla_ms"] = _bench_chained(
            lambda q: _stats_fallback(q, kq, vq, bt, sl)[0], q
        )
    return entry


def leg_rq_cascade(rng, interpret, timing, shape=None):
    """RQ cascade (rqvae-scale: B2048 D32 L3 K256). Off by default."""
    import jax
    import jax.numpy as jnp

    from genrec_tpu.kernels.rq_cascade import rq_cascade_pallas

    Bq, Dq, Lq, Kq = shape or ((128, 16, 3, 20) if interpret else (2048, 32, 3, 256))
    x = jnp.asarray(rng.normal(size=(Bq, Dq)), jnp.float32)
    cbs = jnp.asarray(rng.normal(size=(Lq, Kq, Dq)), jnp.float32)
    ids, qsum = jax.jit(
        lambda *a: rq_cascade_pallas(*a, blk_b=256, interpret=interpret)
    )(x, cbs)
    entry = _compare((ids, qsum), _rq_cascade_xla, x, cbs)
    # A flipped id is a wrong answer whatever the sums say: ids compare as
    # floats inside _compare, so any mismatch is an error >= 1 over a
    # reference magnitude < codebook size.
    entry["ids_match"] = bool(np.array_equal(
        np.asarray(ids), np.asarray(jax.jit(_rq_cascade_xla)(x, cbs)[0])
    ))
    if not entry["ids_match"]:
        entry["max_rel_err"] = float("inf")
    if timing:
        # qsum has x's shape, so it scan-carries back as x.
        entry["pallas_ms"] = _bench_chained(
            lambda a, b: rq_cascade_pallas(a, b, blk_b=256)[1], x, cbs
        )
        entry["xla_ms"] = _bench_chained(
            lambda a, b: _rq_cascade_xla(a, b)[1], x, cbs
        )
    return entry


LEGS = {
    "paged_attention": leg_paged_attention,
    "paged_attention_int8": leg_paged_attention_int8,
    "fused_linear_ce": leg_fused_linear_ce,
    "sharded_fused_linear_ce": leg_sharded_fused_linear_ce,
    "hstu_attention": leg_hstu_attention,
    "hstu_attention_bwd": leg_hstu_attention_bwd,
    "rq_cascade": leg_rq_cascade,
}


def run_legs(legs, interpret: bool = False, timing: bool = False,
             seed: int = 0) -> dict:
    """Run ``legs`` ({row name: (leg name, kwargs)}) and return the kernel
    table: per row ``compiled``, the error columns of `_compare`, ``tol``
    and ``ok`` — or ``error`` with the compiler's message. One kernel's
    refusal must not hide the others, so each leg's failure is caught
    HERE, recorded with its traceback on stderr, and fails the table
    through ``ok``."""
    import traceback

    tol = INTERPRET_TOL if interpret else TPU_TOL
    rng = np.random.default_rng(seed)
    table: dict = {}
    for row, (leg, kwargs) in legs.items():
        try:
            entry = LEGS[leg](rng, interpret, timing, **kwargs)
        except Exception as e:  # noqa: BLE001 — boundary: report every kernel
            traceback.print_exc(file=sys.stderr)
            table[row] = {"compiled": False, "ok": False,
                          "error": f"{type(e).__name__}: {e}"[:2000]}
            continue
        entry.update(compiled=True, tol=tol[leg],
                     ok=bool(entry["max_rel_err"] < tol[leg]))
        table[row] = entry
    return table


def run(interpret: bool = False, timing: bool | None = None) -> dict:
    """Validate (and, compiled, time) every kernel at its default shapes.
    Returns a JSON-able result; ``ok`` covers the default-on kernels."""
    import jax

    timing = (not interpret) if timing is None else timing
    kernels = run_legs({name: (name, {}) for name in LEGS}, interpret, timing)
    return {
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "interpret": interpret,
        "kernels": kernels,
        "ok": all(kernels[k]["ok"] for k in DEFAULT_ON),
    }


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description="Pallas kernel preflight")
    ap.add_argument(
        "--interpret",
        action="store_true",
        help="run in interpreter mode on the CPU (tiny shapes, no timings)",
    )
    args = ap.parse_args(argv)
    import jax

    if args.interpret:
        # Interpret mode is a CPU smoke test; do not take the chip for it.
        # Must run before first device use.
        jax.config.update("jax_platforms", "cpu")
    from genrec_tpu.parallel.mesh import enable_compile_cache, require_tpu

    if not args.interpret:
        require_tpu("kernel preflight (pass --interpret for the CPU smoke test)")
    enable_compile_cache()
    res = run(interpret=args.interpret)
    print(json.dumps(res))
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
