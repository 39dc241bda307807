"""Level 1 of graftlint: lower registered entry points, run IR rules.

Two things live here:

1. **The shared lower/compile harness** the standalone ``scripts/check_*``
   checks are built on (CLI conventions, platform pinning, optimized-HLO
   lowering, verdict emission, docs/PERF.md notes, out/ artifacts). The
   five check scripts each used to carry a private copy of this plumbing;
   they now import it, keeping their CLIs and verdict JSON bit-compatible.

2. **Composable IR rules** over a compile-manifest entry
   (analysis/manifest.py):

   - ``constant_bake``   — literals over a byte threshold embedded in the
     executable (the baked trie today; a million-item catalog tomorrow).
     Catalog-sized data must arrive as a runtime operand, or every
     catalog change recompiles and executable size scales with corpus.
   - ``missing_donation`` — entry argnums declared dead-after-call
     (``BuiltEntry.expect_donated``) that the jit does not donate: one
     dead copy of the buffer stays live across the call (wasted HBM equal
     to the buffer size).
   - ``f64_op``          — double-precision tensors in the optimized HLO
     (silent upcasts double memory traffic and are 10-30x slower on TPU).
   - ``host_transfer_in_loop`` — callbacks/infeed/outfeed inside a
     scan/while body: a device loop that syncs to host every iteration.

Rules read three artifacts of one trace: the jaxpr (host transfers), the
lowering's ``args_info`` (donation — visible on every backend, including
CPU where XLA itself ignores donation), and the optimized HLO text
(constants, dtypes).

jax is imported inside functions, never at module scope: the AST level
and the CLI plumbing must stay importable without pulling in a backend.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
from typing import Optional, Sequence

from genrec_tpu.analysis.findings import Finding
from genrec_tpu.analysis.manifest import BuiltEntry

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: Global default for the constant-bake threshold (bytes). Entries can
#: pin a tighter one (BuiltEntry.max_const_bytes); graftlint exposes
#: --max-const-bytes for one-off sweeps.
DEFAULT_MAX_CONST_BYTES = 64 * 1024


# ---------------------------------------------------------------------------
# Shared check-script harness (CLI / lowering / verdict conventions)
# ---------------------------------------------------------------------------

def check_args(argv=None, *, small_help: str = "tiny shapes for fast CI runs",
               note_help: str = "append the verdict to docs/PERF.md",
               extra: Optional[Sequence[tuple]] = None) -> argparse.Namespace:
    """The standard check-script CLI: --write-note / --small / --platform.

    ``extra`` adds script-specific flags as (args_tuple, kwargs_dict)
    pairs. Parsing happens BEFORE jax is imported (scripts pin the
    platform after import via :func:`pin_platform`).
    """
    ap = argparse.ArgumentParser()
    ap.add_argument("--write-note", action="store_true", help=note_help)
    ap.add_argument("--small", action="store_true", help=small_help)
    ap.add_argument("--platform", default=None)
    for args, kwargs in extra or ():
        ap.add_argument(*args, **kwargs)
    return ap.parse_args(argv)


def optimized_hlo(fn, *args, **jit_kwargs) -> str:
    """Optimized HLO text of ``fn(*args)`` as ONE jit program.

    ``fn`` may already be jitted (has ``.lower``) — jit_kwargs must then
    be empty — or a plain callable that gets wrapped here. Compiling is
    itself an assertion: a function that cannot lower/compile as a single
    program raises instead of returning.
    """
    import jax

    if not hasattr(fn, "lower"):
        fn = jax.jit(fn, **jit_kwargs)
    elif jit_kwargs:
        raise ValueError("fn is already jitted; jit_kwargs would be ignored")
    return fn.lower(*args).compile().as_text()


def emit_verdict(verdict: dict) -> None:
    """The one-JSON-line-on-stdout contract of scripts/ci_checks.sh."""
    print(json.dumps(verdict))


def append_perf_note(note: str, repo: str = REPO) -> None:
    with open(os.path.join(repo, "docs", "PERF.md"), "a") as f:
        f.write(note)


def dump_artifact(name: str, text: str, repo: str = REPO) -> str:
    """Write a debug artifact under out/ (e.g. the offending HLO)."""
    out_dir = os.path.join(repo, "out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, name)
    with open(path, "w") as f:
        f.write(text)
    return path


# ---------------------------------------------------------------------------
# HLO text parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_CONST_RE = re.compile(r"\b(\w+)\[([\d,]*)\]\S*\s+constant\(")


def hlo_constants(hlo: str) -> list[dict]:
    """Every literal in an HLO module as {dtype, shape, bytes, line}."""
    out = []
    for line in hlo.splitlines():
        m = _CONST_RE.search(line)
        if not m:
            continue
        dtype, dims = m.group(1), m.group(2)
        if dtype not in _DTYPE_BYTES:
            continue
        shape = tuple(int(d) for d in dims.split(",") if d)
        n_bytes = _DTYPE_BYTES[dtype] * (math.prod(shape) if shape else 1)
        out.append({"dtype": dtype, "shape": shape, "bytes": n_bytes,
                    "line": line.strip()})
    return out


_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\w+)\[([\d,]*)\](?:\{[^}]*\})?\s+([\w\-]+)\("
)


def hlo_ops_of_size(hlo: str, n_elements: int) -> list[tuple[str, str]]:
    """(opcode, line) of every HLO instruction whose RESULT is an array
    of exactly ``n_elements`` — in the entry computation and in every
    fused or called one, since the text lists them all. Over a serving
    executable and the page pool's element count this is the relayout
    check: a pool may come back out of a ``scatter`` (or the fusion or
    loop the compiler wrapped it in), never out of a ``copy``,
    ``transpose`` or ``convert``."""
    out = []
    for line in hlo.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        dims = [int(d) for d in m.group(2).split(",") if d]
        if dims and math.prod(dims) == n_elements:
            out.append((m.group(3), line.strip()))
    return out


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

_LOOP_PRIMS = {"scan", "while"}
_HOST_PRIMS = {"pure_callback", "io_callback", "debug_callback",
               "infeed", "outfeed"}


def _subjaxprs(params: dict):
    for val in params.values():
        if hasattr(val, "jaxpr"):  # ClosedJaxpr
            yield val.jaxpr
        elif hasattr(val, "eqns"):  # raw Jaxpr
            yield val
        elif isinstance(val, (tuple, list)):
            for item in val:
                if hasattr(item, "jaxpr"):
                    yield item.jaxpr
                elif hasattr(item, "eqns"):
                    yield item


def host_ops_in_loops(jaxpr) -> list[dict]:
    """Host-transfer primitives that execute inside a scan/while body.

    A callback at a program's top level is one host sync per call —
    sometimes a legitimate choice. The same callback inside a loop body
    is a host round-trip per iteration, which serializes the loop on
    host latency; that is the rule.
    """
    hits: list[dict] = []

    def walk(jx, in_loop: bool):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if in_loop and name in _HOST_PRIMS:
                hits.append({"primitive": name})
            child_in_loop = in_loop or name in _LOOP_PRIMS
            for sub in _subjaxprs(eqn.params):
                walk(sub, child_in_loop)

    walk(jaxpr, False)
    return hits


def primitives_of_size(jaxpr, n_elements: int) -> set[str]:
    """Names of the primitives with an operand or a result of exactly
    ``n_elements``. Call-like equations (pjit, scan, while, cond, custom
    derivatives) are walked into, not reported; a ``pallas_call`` is a
    leaf. What a traced program ASKS for of a value of that size, before
    any compiler has chosen a layout for it."""
    hits: set[str] = set()

    def sized(v) -> bool:
        shape = getattr(getattr(v, "aval", None), "shape", None)
        return bool(shape) and math.prod(shape) == n_elements

    def walk(jx):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            subs = [] if name == "pallas_call" else list(_subjaxprs(eqn.params))
            for sub in subs:
                walk(sub)
            if not subs and any(map(sized, (*eqn.invars, *eqn.outvars))):
                hits.add(name)

    walk(jaxpr)
    return hits


# ---------------------------------------------------------------------------
# IR rules over a manifest entry
# ---------------------------------------------------------------------------

def _leaf_bytes(arg_info) -> int:
    import numpy as np

    return (int(math.prod(arg_info.shape or (1,)))
            * np.dtype(arg_info.dtype).itemsize)


def analyze_entry(
    name: str,
    built: BuiltEntry,
    max_const_bytes: int = DEFAULT_MAX_CONST_BYTES,
) -> tuple[list[Finding], dict]:
    """Run every IR rule over one built entry.

    Returns (findings, stats). One trace feeds all rules: the jaxpr
    (host transfers), the lowering (donation), the compiled text
    (constants, dtypes).
    """
    import jax

    findings: list[Finding] = []
    traced = built.fn.trace(*built.args)
    lowered = traced.lower()

    # -- donation audit ------------------------------------------------------
    args_info = lowered.args_info[0]
    for argnum in built.expect_donated:
        leaves = jax.tree_util.tree_leaves(args_info[argnum])
        undonated = [l for l in leaves if not l.donated]
        if undonated:
            wasted = sum(_leaf_bytes(l) for l in undonated)
            findings.append(Finding(
                rule="missing_donation",
                where=name,
                key=f"arg{argnum}",
                message=(
                    f"{name}: argument {argnum} is dead after the call but "
                    f"{len(undonated)}/{len(leaves)} of its buffers are not "
                    f"donated — ~{wasted / 1e6:.2f} MB of HBM held as a dead "
                    "copy across the step (donate_argnums)"
                ),
                detail={"argnum": argnum, "undonated_buffers": len(undonated),
                        "wasted_bytes": wasted},
            ))

    hlo = lowered.compile().as_text()

    # -- constant bake -------------------------------------------------------
    threshold = (
        built.max_const_bytes
        if built.max_const_bytes is not None else max_const_bytes
    )
    constants = hlo_constants(hlo)
    big: dict[str, dict] = {}
    for const in constants:
        if const["bytes"] <= threshold:
            continue
        key = f"{const['dtype']}{list(const['shape'])}"
        slot = big.setdefault(key, {**const, "count": 0})
        slot["count"] += 1
    for key, const in sorted(big.items()):
        findings.append(Finding(
            rule="constant_bake",
            where=name,
            key=key,
            message=(
                f"{name}: {const['count']} literal(s) of shape "
                f"{const['dtype']}{list(const['shape'])} "
                f"({const['bytes'] / 1e6:.2f} MB each) baked into the "
                f"executable (threshold {threshold} B) — pass catalog-sized "
                "data as a runtime operand, or every refresh recompiles"
            ),
            detail={"bytes": const["bytes"], "count": const["count"],
                    "threshold": threshold},
        ))

    # -- dtype discipline ----------------------------------------------------
    if not built.allow_f64:
        f64_lines = [l.strip() for l in hlo.splitlines()
                     if re.search(r"\bf64\[|\bc128\[", l)]
        if f64_lines:
            findings.append(Finding(
                rule="f64_op",
                where=name,
                key="f64",
                message=(
                    f"{name}: {len(f64_lines)} double-precision op(s) in the "
                    "optimized HLO — a silent upcast somewhere in the entry "
                    f"(first: {f64_lines[0][:120]})"
                ),
                detail={"count": len(f64_lines), "first": f64_lines[0][:200]},
            ))

    # -- host transfers in loop bodies ---------------------------------------
    hits = host_ops_in_loops(traced.jaxpr.jaxpr)
    if hits:
        prims = sorted({h["primitive"] for h in hits})
        findings.append(Finding(
            rule="host_transfer_in_loop",
            where=name,
            key=",".join(prims),
            message=(
                f"{name}: {len(hits)} host-transfer op(s) ({', '.join(prims)}) "
                "inside a scan/while body — the device loop round-trips to "
                "host every iteration"
            ),
            detail={"count": len(hits), "primitives": prims},
        ))

    stats = {
        "hlo_bytes": len(hlo),
        "n_constants": len(constants),
        "const_threshold": threshold,
    }
    return findings, stats


def analyze_manifest(
    entries,
    max_const_bytes: int = DEFAULT_MAX_CONST_BYTES,
    on_error: str = "finding",
) -> tuple[list[Finding], dict]:
    """Run the IR rules over every manifest entry.

    A builder or compile that raises becomes an ``entry_error`` finding
    (the manifest itself is load-bearing: a silently skipped entry would
    read as clean) unless ``on_error='raise'``.
    """
    findings: list[Finding] = []
    stats: dict = {}
    for name, entry in sorted(entries.items()):
        try:
            built = entry.build()
            entry_findings, entry_stats = analyze_entry(
                name, built, max_const_bytes=max_const_bytes
            )
        except Exception as e:  # noqa: BLE001 — reported, never swallowed
            if on_error == "raise":
                raise
            findings.append(Finding(
                rule="entry_error",
                where=name,
                key=type(e).__name__,
                message=f"{name}: entry failed to build/lower: {e!r:.300}",
                detail={"error": repr(e)[:500]},
            ))
            stats[name] = {"error": repr(e)[:200]}
        else:
            findings.extend(entry_findings)
            stats[name] = entry_stats
    return findings, stats
