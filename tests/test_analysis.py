"""graftlint (genrec_tpu/analysis): trigger + just-barely-doesn't-trigger
fixtures for every IR and AST rule, baseline mechanics, and the self-run
asserting the repo is clean modulo the checked-in baseline.

The deliberately-injected violations here are the ISSUE-8 acceptance
set: constant bake over threshold, missing donation, upward obs import,
lock-held blocking call, trace-impure time.time()."""

import ast
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from genrec_tpu.analysis import findings as F
from genrec_tpu.analysis import lint
from genrec_tpu.analysis.manifest import BuiltEntry

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# IR rules (analysis/ir.py)
# ---------------------------------------------------------------------------

class TestIRRules:
    def test_constant_bake_triggers_over_threshold(self):
        import jax
        import jax.numpy as jnp

        from genrec_tpu.analysis import ir

        baked = np.arange(256 * 256, dtype=np.float32).reshape(256, 256)

        def f(x):
            return x + jnp.asarray(baked)

        built = BuiltEntry(fn=jax.jit(f),
                           args=(jnp.zeros((256, 256), jnp.float32),))
        found, _ = ir.analyze_entry("fix/baked", built, max_const_bytes=65536)
        bake = [f for f in found if f.rule == "constant_bake"]
        assert len(bake) == 1, found
        assert bake[0].detail["bytes"] == 256 * 256 * 4
        assert "f32[256, 256]" in bake[0].key

    def test_constant_bake_quiet_under_threshold(self):
        import jax
        import jax.numpy as jnp

        from genrec_tpu.analysis import ir

        small = np.arange(128, dtype=np.float32)  # 512 B

        def f(x):
            return x + jnp.asarray(small)

        built = BuiltEntry(fn=jax.jit(f), args=(jnp.zeros((128,), jnp.float32),))
        found, _ = ir.analyze_entry("fix/small", built, max_const_bytes=65536)
        assert not [f for f in found if f.rule == "constant_bake"], found

    def test_missing_donation_flagged_then_fixed(self):
        import jax
        import jax.numpy as jnp

        from genrec_tpu.analysis import ir

        def step(state, batch):
            return {"w": state["w"] + batch.sum()}

        state = {"w": jnp.zeros((64, 64), jnp.float32)}
        batch = jnp.ones((8,), jnp.float32)

        undonated = BuiltEntry(fn=jax.jit(step), args=(state, batch),
                               expect_donated=(0,))
        found, _ = ir.analyze_entry("fix/undonated", undonated)
        don = [f for f in found if f.rule == "missing_donation"]
        assert len(don) == 1, found
        assert don[0].detail["wasted_bytes"] == 64 * 64 * 4

        donated = BuiltEntry(fn=jax.jit(step, donate_argnums=(0,)),
                             args=(state, batch), expect_donated=(0,))
        found, _ = ir.analyze_entry("fix/donated", donated)
        assert not [f for f in found if f.rule == "missing_donation"], found

    def test_f64_flagged_and_allow_flag(self):
        import jax
        import jax.numpy as jnp

        from genrec_tpu.analysis import ir

        def upcast(x):
            return jnp.asarray(x, jnp.float64) * 2.0

        with jax.enable_x64(True):
            built = BuiltEntry(fn=jax.jit(upcast),
                               args=(jnp.zeros((8,), jnp.float32),))
            found, _ = ir.analyze_entry("fix/f64", built)
            assert _rules(found) == ["f64_op"], found

            allowed = BuiltEntry(fn=jax.jit(upcast),
                                 args=(jnp.zeros((8,), jnp.float32),),
                                 allow_f64=True)
            found, _ = ir.analyze_entry("fix/f64ok", allowed)
            assert not found, found

    def test_f64_quiet_on_f32_program(self):
        import jax
        import jax.numpy as jnp

        from genrec_tpu.analysis import ir

        built = BuiltEntry(fn=jax.jit(lambda x: x * 2.0),
                           args=(jnp.zeros((8,), jnp.float32),))
        found, _ = ir.analyze_entry("fix/f32", built)
        assert not found, found

    def test_hlo_ops_of_size_tells_a_relayout_from_a_scatter(self):
        """What chip_smoke.py's whole-pool check rests on: of the values
        of one element count, a relaying program shows a copy or a
        transpose, an in-place page write only parameter, scatter and
        the fusion around it."""
        import jax.numpy as jnp

        from genrec_tpu.analysis import ir

        pool = jnp.zeros((11, 8, 24), jnp.float32)
        n = pool.size
        relay = ir.optimized_hlo(
            lambda p: p.reshape(11, 8, 2, 12).transpose(0, 2, 1, 3) * 2.0, pool)
        assert {"copy", "transpose"} & {
            op for op, _ in ir.hlo_ops_of_size(relay, n)}
        stay = ir.optimized_hlo(
            lambda p, rows: p.at[jnp.array([3, 5])].set(rows),
            pool, jnp.ones((2, 8, 24)), donate_argnums=(0,))
        ops = {op for op, _ in ir.hlo_ops_of_size(stay, n)}
        assert "scatter" in ops and not {"copy", "transpose"} & ops, ops
        assert not ir.hlo_ops_of_size(stay, n + 1)

    def test_host_transfer_in_loop_flagged(self):
        import jax
        import jax.numpy as jnp

        from genrec_tpu.analysis import ir

        def cb(x):
            return np.asarray(x) * 2

        def body(c, x):
            y = jax.pure_callback(cb, jax.ShapeDtypeStruct((), jnp.float32), x)
            return c + y, y

        def loop(xs):
            return jax.lax.scan(body, jnp.float32(0.0), xs)

        built = BuiltEntry(fn=jax.jit(loop), args=(jnp.zeros((4,), jnp.float32),))
        found, _ = ir.analyze_entry("fix/cb_loop", built)
        host = [f for f in found if f.rule == "host_transfer_in_loop"]
        assert len(host) == 1 and "pure_callback" in host[0].key, found

    def test_host_transfer_outside_loop_not_flagged(self):
        import jax
        import jax.numpy as jnp

        from genrec_tpu.analysis import ir

        def cb(x):
            return np.asarray(x) * 2

        def once(x):
            return jax.pure_callback(
                cb, jax.ShapeDtypeStruct((4,), jnp.float32), x
            ) + 1.0

        built = BuiltEntry(fn=jax.jit(once), args=(jnp.zeros((4,), jnp.float32),))
        found, _ = ir.analyze_entry("fix/cb_top", built)
        assert not [f for f in found if f.rule == "host_transfer_in_loop"], found

    def test_entry_error_is_a_finding_not_a_crash(self):
        from genrec_tpu.analysis import ir
        from genrec_tpu.analysis.manifest import EntryPoint

        def broken():
            raise RuntimeError("fixture: builder exploded")

        entries = {"fix/broken": EntryPoint("fix/broken", (), broken, "test")}
        found, stats = ir.analyze_manifest(entries)
        assert _rules(found) == ["entry_error"]
        assert "error" in stats["fix/broken"]


# ---------------------------------------------------------------------------
# AST rules (analysis/lint.py)
# ---------------------------------------------------------------------------

def _write_pkg_file(tmp_path, rel, body):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(body))
    return str(path)


@pytest.fixture(scope="module")
def layers():
    return lint.load_layer_map(REPO)


class TestLayerMap:
    def test_generated_from_architecture_md(self, layers):
        # The map is GENERATED from the doc — the load-bearing rows.
        assert layers["serving"] == 6.0
        assert layers["trainers"] == 4.0
        assert layers["models"] == 3.0
        assert layers["data"] == 1.0
        assert layers["core"] == 0.0 and layers["parallel"] == 0.0
        assert layers["obs"] == lint.LEAF_LEVEL  # Lx row

    def test_missing_map_raises_not_vacuous(self):
        with pytest.raises(ValueError, match="vacuous"):
            lint.parse_layer_map("# Architecture\n\nno diagram here\n")


class TestLayering:
    def test_upward_obs_import_flagged(self, tmp_path, layers):
        p = _write_pkg_file(
            tmp_path, "genrec_tpu/obs/bad.py",
            "from genrec_tpu.parallel.mesh import allgather_host_ints\n",
        )
        found = lint.lint_file(p, repo=str(tmp_path), layers=layers)
        assert _rules(found) == ["layering"]
        assert found[0].key == "obs->parallel"

    def test_serving_must_not_import_trainers(self, tmp_path, layers):
        p = _write_pkg_file(
            tmp_path, "genrec_tpu/serving/bad.py",
            "def f():\n    from genrec_tpu.trainers.packed_loop import PackedTrainLoop\n",
        )
        found = lint.lint_file(p, repo=str(tmp_path), layers=layers)
        assert [f.key for f in found] == ["serving->trainers"]

    def test_data_must_not_import_models(self, tmp_path, layers):
        p = _write_pkg_file(
            tmp_path, "genrec_tpu/data/bad.py",
            "import genrec_tpu.models.sasrec\n",
        )
        found = lint.lint_file(p, repo=str(tmp_path), layers=layers)
        assert [f.key for f in found] == ["data->models"]

    def test_downward_and_configlib_imports_clean(self, tmp_path, layers):
        p = _write_pkg_file(
            tmp_path, "genrec_tpu/models/ok.py",
            """\
            from genrec_tpu.ops.losses import cross_entropy_with_ignore
            from genrec_tpu import configlib
            from genrec_tpu.obs.flight_recorder import get_flight_recorder
            """,
        )
        assert lint.lint_file(p, repo=str(tmp_path), layers=layers) == []

    def test_relative_imports_are_the_same_edge(self, tmp_path, layers):
        """`from ..parallel import mesh` is the obs->parallel edge in
        relative spelling — the machine-enforced map must see it."""
        p = _write_pkg_file(
            tmp_path, "genrec_tpu/obs/rel.py",
            """\
            from ..parallel.mesh import allgather_host_ints
            from .. import trainers
            from .spans import SpanTracer
            """,
        )
        found = lint.lint_file(p, repo=str(tmp_path), layers=layers)
        assert sorted(f.key for f in found) == [
            "obs->parallel", "obs->trainers"
        ]  # the intra-package `.spans` import is not an edge

    def test_leaf_may_use_open_packages(self, tmp_path, layers):
        """configlib is open for EVERY layer, leaves included — the
        open-package check must precede the leaf-source rule."""
        p = _write_pkg_file(
            tmp_path, "genrec_tpu/obs/uses_config.py",
            "from genrec_tpu import configlib\n",
        )
        assert lint.lint_file(p, repo=str(tmp_path), layers=layers) == []

    def test_unmapped_package_is_flagged(self, tmp_path, layers):
        """A package — or top-level module — with no architecture.md row
        is one the layering rule cannot constrain: that gap must be a
        finding, not silence."""
        _write_pkg_file(tmp_path, "genrec_tpu/streaming/loop.py",
                        "import genrec_tpu.trainers\n")
        _write_pkg_file(tmp_path, "genrec_tpu/util.py", "x = 1\n")
        _write_pkg_file(tmp_path, "genrec_tpu/pipelines.py", "")  # exempt
        _write_pkg_file(tmp_path, "genrec_tpu/obs/__init__.py", "")
        found = lint.check_unmapped_packages(str(tmp_path), layers)
        assert sorted(f.key for f in found) == ["streaming", "util"]
        assert all(f.rule == "unmapped_package" for f in found)

    def test_leaf_to_leaf_import_flagged(self, tmp_path, layers):
        """obs<->analysis edges would be cycles the level ordering cannot
        see — leaves import nothing but open packages."""
        p = _write_pkg_file(
            tmp_path, "genrec_tpu/obs/uses_analysis.py",
            "from genrec_tpu.analysis import summary_metrics\n",
        )
        found = lint.lint_file(p, repo=str(tmp_path), layers=layers)
        assert [f.key for f in found] == ["obs->analysis"]

    def test_library_must_not_import_driver_modules(self, tmp_path, layers):
        """pipelines is exempt as a SOURCE (task runner), but importing
        it from library code drags every layer into one image."""
        p = _write_pkg_file(
            tmp_path, "genrec_tpu/serving/uses_driver.py",
            "from genrec_tpu import pipelines\n",
        )
        found = lint.lint_file(p, repo=str(tmp_path), layers=layers)
        assert [f.key for f in found] == ["serving->pipelines"]


class TestTracePurity:
    def test_impure_jitted_fn_flagged(self, tmp_path):
        p = _write_pkg_file(
            tmp_path, "genrec_tpu/ops/bad.py",
            """\
            import time
            import jax
            import numpy as np

            def step(params, batch):
                t0 = time.time()
                noise = np.random.rand()
                scale = float(params)
                if batch:
                    params = params + noise + t0 + scale
                return params

            step_fn = jax.jit(step)
            """,
        )
        found = lint.lint_file(p, repo=str(tmp_path), layers=None)
        assert _rules(found) == ["trace_purity"]
        msgs = " ".join(f.message for f in found)
        assert "time.time" in msgs
        assert "np.random" in msgs
        assert "float() coercion" in msgs
        assert "`if batch`" in msgs
        assert len(found) == 4

    def test_same_calls_outside_traced_fn_clean(self, tmp_path):
        p = _write_pkg_file(
            tmp_path, "genrec_tpu/ops/ok.py",
            """\
            import time
            import jax
            import numpy as np

            def host_helper(n):
                # Not handed to jit/scan: host impurity is fine here.
                return time.time() + np.random.rand(n).sum()

            def step(params, batch):
                if batch is None:  # None-check of a STATIC arg: allowed
                    return params
                n = int(params.shape[0])   # static shape read: allowed
                d = float(params.ndim)     # static rank read: allowed
                return params * 2 * n * d

            step_fn = jax.jit(step)
            """,
        )
        assert lint.lint_file(p, repo=str(tmp_path), layers=None) == []

    def test_scan_body_by_name_is_traced(self, tmp_path):
        p = _write_pkg_file(
            tmp_path, "genrec_tpu/ops/scanbad.py",
            """\
            import time
            import jax

            def body(carry, x):
                return carry + time.time(), x

            def run(xs):
                return jax.lax.scan(body, 0.0, xs)
            """,
        )
        found = lint.lint_file(p, repo=str(tmp_path), layers=None)
        assert _rules(found) == ["trace_purity"]

    def test_lambda_fingerprints_survive_line_shifts(self, tmp_path):
        """Traced-lambda findings are keyed by source-order ordinal, not
        line number — the baseline contract (findings.py) requires
        fingerprints to survive unrelated edits above the lambda."""
        body = """\
            import time
            import jax

            def run(xs):
                return jax.lax.scan(lambda c, x: (c + time.time(), x), 0.0, xs)
            """
        p1 = _write_pkg_file(tmp_path, "genrec_tpu/ops/l1.py", body)
        f1 = lint.lint_file(p1, repo=str(tmp_path), layers=None)
        p2 = _write_pkg_file(tmp_path, "genrec_tpu/ops/l2.py",
                             "\n" * 25 + textwrap.dedent(body))
        f2 = lint.lint_file(p2, repo=str(tmp_path), layers=None)
        assert len(f1) == len(f2) == 1
        assert f1[0].key == f2[0].key == "<lambda#1>:time.time()"

    def test_fori_and_while_loop_bodies_are_traced(self, tmp_path):
        # fori_loop traces args[2]; while_loop traces BOTH cond and body —
        # neither position is args[0] (the bug a review pass caught).
        p = _write_pkg_file(
            tmp_path, "genrec_tpu/ops/loopbad.py",
            """\
            import time
            import jax

            def fbody(i, val):
                return val + time.time()

            def wcond(val):
                return val < 10

            def wbody(val):
                return val + time.time()

            def run():
                a = jax.lax.fori_loop(0, 4, fbody, 0.0)
                b = jax.lax.while_loop(wcond, wbody, 0.0)
                return a + b
            """,
        )
        found = lint.lint_file(p, repo=str(tmp_path), layers=None)
        assert _rules(found) == ["trace_purity"]
        flagged = {f.detail["function"] for f in found}
        assert flagged == {"fbody", "wbody"}, flagged


class TestLockDiscipline:
    def test_blocking_calls_under_lock_flagged(self, tmp_path):
        p = _write_pkg_file(
            tmp_path, "genrec_tpu/serving/bad.py",
            """\
            import time
            import threading

            class Engine:
                def __init__(self):
                    self._lock = threading.Lock()

                def bad(self, fut, queue):
                    with self._lock:
                        time.sleep(0.5)
                        out = fut.result()
                        item = queue.get()
                    return out, item
            """,
        )
        found = lint.lint_file(p, repo=str(tmp_path), layers=None)
        assert _rules(found) == ["lock_held_blocking"]
        assert len(found) == 3  # sleep, result, queue.get

    def test_blocking_outside_lock_or_with_timeout_clean(self, tmp_path):
        p = _write_pkg_file(
            tmp_path, "genrec_tpu/serving/ok.py",
            """\
            import time
            import threading

            class Engine:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._work = threading.Condition(self._lock)

                def ok(self, fut, fut2, queue):
                    with self._lock:
                        queue.get(timeout=1.0)   # bounded: allowed
                        queue.get(False)         # non-blocking: allowed
                        queue.get(block=False)   # non-blocking: allowed
                        fut2.result(timeout=1.0) # bounded: allowed
                        self._work.wait(0.05)    # releases the lock: allowed
                        stats = {}.get("x")      # dict.get: not a queue
                    time.sleep(0.5)              # not under the lock
                    return fut.result()          # not under the lock
            """,
        )
        assert lint.lint_file(p, repo=str(tmp_path), layers=None) == []

    def test_rule_scoped_to_threaded_packages(self, tmp_path):
        # Same offense in ops/ (no thread pools): out of scope by design.
        p = _write_pkg_file(
            tmp_path, "genrec_tpu/ops/anything.py",
            """\
            import time
            import threading

            _lock = threading.Lock()

            def f(fut):
                with _lock:
                    return fut.result()  # unbounded, but ops/ is out of scope
            """,
        )
        assert lint.lint_file(p, repo=str(tmp_path), layers=None) == []


# ---------------------------------------------------------------------------
# Baseline + obs summary mechanics
# ---------------------------------------------------------------------------

class TestBaseline:
    def _mk(self, rule, where, key):
        return F.Finding(rule=rule, where=where, key=key, message="m")

    def test_split_new_baselined_stale(self, tmp_path):
        a = self._mk("layering", "x.py", "a->b")
        b = self._mk("constant_bake", "e", "f32[9]")
        path = str(tmp_path / "baseline.json")
        F.save_baseline(path, [a, self._mk("gone", "y.py", "z")])
        new, old, stale = F.split_by_baseline([a, b], F.load_baseline(path))
        assert new == [b]
        assert old == [a]
        assert stale == ["gone::y.py::z"]

    def test_fingerprint_has_no_line_numbers(self):
        f = F.Finding(rule="layering", where="genrec_tpu/obs/goodput.py",
                      key="obs->parallel", message="m", detail={"line": 221})
        assert "221" not in f.fingerprint

    def test_missing_baseline_is_empty(self, tmp_path):
        assert F.load_baseline(str(tmp_path / "nope.json")) == []

    def test_entry_error_can_never_be_suppressed(self, tmp_path):
        """entry_error means the analysis did NOT run; baselining it
        would make a blind spot read as clean forever."""
        broken = self._mk("entry_error", "train/foo", "RuntimeError")
        path = str(tmp_path / "baseline.json")
        F.save_baseline(path, [broken, self._mk("layering", "x.py", "a->b")])
        fps = F.load_baseline(path)
        assert fps == ["layering::x.py::a->b"]  # entry_error filtered out
        # Even a hand-added fingerprint is ignored at split time.
        new, old, _stale = F.split_by_baseline(
            [broken], [broken.fingerprint]
        )
        assert new == [broken] and old == []

    def test_summary_metrics_namespace_and_strict_json(self):
        a = self._mk("layering", "x.py", "a->b")
        b = self._mk("constant_bake", "e", "f32[9]")
        metrics = F.summary_metrics([a, b], new=[b], baselined=[a], stale=[])
        assert all(k.startswith("analysis/") for k in metrics)
        assert metrics["analysis/findings"] == 2
        assert metrics["analysis/new"] == 1
        assert metrics["analysis/rule/layering"] == 1
        # Tracker/flight-recorder friendly: strict-JSON round-trip.
        def reject(tok):
            raise ValueError(tok)
        assert json.loads(json.dumps(metrics), parse_constant=reject) == metrics


# ---------------------------------------------------------------------------
# Repo self-runs + manifest
# ---------------------------------------------------------------------------

class TestSelfRun:
    def test_ast_level_clean_modulo_baseline(self):
        """The repo's own AST lint: every finding is in the committed
        baseline (new layering/purity/lock debt fails here first)."""
        found = lint.lint_repo(REPO)
        baseline = F.load_baseline(
            os.path.join(REPO, "genrec_tpu", "analysis", "baseline.json")
        )
        new, _old, _stale = F.split_by_baseline(found, baseline)
        assert not new, [f.message for f in new]

    def test_graftlint_ast_only_subprocess(self):
        """The driver's verdict contract: one JSON line, rc 0, metrics in
        the analysis/* namespace."""
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "graftlint.py"),
             "--ast-only"],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = [l for l in proc.stdout.splitlines() if l.strip()]
        assert len(lines) == 1
        verdict = json.loads(lines[0])
        assert verdict["check"] == "graftlint"
        assert verdict["ok"] is True
        assert verdict["levels"] == ["ast"]
        assert verdict["new"] == 0
        assert set(verdict) >= {"findings", "baselined", "stale_baseline",
                                "metrics", "new_findings"}
        assert all(k.startswith("analysis/") for k in verdict["metrics"])

    def test_update_baseline_refused_on_partial_runs(self):
        """A partial run cannot see the other level's findings: rewriting
        the baseline from it would drop those suppressions and fail the
        next full CI run on already-tracked debt."""
        for flag in ("--ast-only", "--ir-only"):
            proc = subprocess.run(
                [sys.executable, os.path.join(REPO, "scripts", "graftlint.py"),
                 flag, "--update-baseline"],
                capture_output=True, text=True, timeout=120,
            )
            assert proc.returncode == 2, (flag, proc.returncode)  # argparse error
            assert "--update-baseline requires a both-level run" in proc.stderr

    def test_manifest_providers_register(self):
        from genrec_tpu.analysis.manifest import load_default_entries

        entries = load_default_entries()
        assert {"train/sasrec_packed_step", "train/tiger_step",
                "serve/tiger_generate_dense",
                "serve/tiger_paged_decode_step"} <= set(entries)
        for e in entries.values():
            assert callable(e.build)

    def test_ir_level_one_entry_clean(self):
        """One real manifest entry through the IR rules (the full-manifest
        run is the slow test + graftlint itself): the sasrec packed step
        must audit clean — donation present, no baked tables, no f64, no
        host syncs in the scan."""
        from genrec_tpu.analysis import ir
        from genrec_tpu.analysis.manifest import load_default_entries

        entry = load_default_entries()["train/sasrec_packed_step"]
        found, stats = ir.analyze_entry("train/sasrec_packed_step", entry.build())
        assert found == [], [f.message for f in found]
        assert stats["n_constants"] > 0  # the parser saw the module

    @pytest.mark.slow
    def test_graftlint_full_subprocess(self):
        """Acceptance: `python scripts/graftlint.py` exits 0 on the repo
        with the committed baseline (both levels)."""
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "graftlint.py"),
             "--platform", "cpu"],
            capture_output=True, text=True, timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stderr[-3000:]
        verdict = json.loads(proc.stdout.splitlines()[-1])
        assert verdict["ok"] is True and verdict["new"] == 0
        assert verdict["levels"] == ["ast", "ir"]
        assert len(verdict["entries"]) >= 4
        # The known debt stays visible (baselined, not silenced).
        assert verdict["baselined"] >= 1


# ---------------------------------------------------------------------------
# The repo's own discipline, pinned directly (belt to graftlint's braces)
# ---------------------------------------------------------------------------

class TestRepoInvariants:
    def test_obs_imports_nothing_from_genrec(self):
        """The PR-8 layering fix stays fixed: obs is a leaf substrate."""
        obs_dir = os.path.join(REPO, "genrec_tpu", "obs")
        for fname in os.listdir(obs_dir):
            if not fname.endswith(".py"):
                continue
            tree = ast.parse(open(os.path.join(obs_dir, fname)).read())
            rel = os.path.join("genrec_tpu", "obs", fname)
            for pkg, lineno in lint._genrec_imports(tree, rel):
                assert pkg == "obs", (
                    f"obs/{fname}:{lineno} imports genrec_tpu.{pkg}"
                )

    def test_paged_decode_compile_donates_slot_state(self):
        """The ONE function that jits a paged decode rung
        (`SlotTable.compile`, for the engine and the disagg worker alike)
        donates the slot-state operand (the PR-8 donation-audit fix) —
        checked at the source level so the fix cannot silently regress on
        CPU where donate_argnums() disables donation."""
        src = open(os.path.join(REPO, "genrec_tpu", "serving", "slots.py")).read()
        tree = ast.parse(src)
        fn = next(
            node for node in ast.walk(tree)
            if isinstance(node, ast.FunctionDef) and node.name == "compile"
        )
        jit_calls = [
            node for node in ast.walk(fn)
            if isinstance(node, ast.Call) and lint._dotted(node.func) == "jax.jit"
        ]
        assert jit_calls, "SlotTable.compile no longer jits directly"
        assert any(
            any(kw.arg == "donate_argnums" for kw in call.keywords)
            for call in jit_calls
        ), "SlotTable.compile lost its donate_argnums"

    @pytest.mark.parametrize("rel", ["serving/engine.py", "disagg/workers.py"])
    def test_slot_state_lives_in_the_slot_table_only(self, rel):
        """Where the slot state lives between steps and what a step pulls
        is serving/slots.py's to decide: neither holder of a `SlotTable`
        reads a state tree, a step vector or an active mask, or builds a
        decode step function, of its own."""
        tree = ast.parse(open(os.path.join(REPO, "genrec_tpu", rel)).read())
        own = {"state", "_state", "steps", "_steps", "active", "_active"}
        factories = {"make_decode_paged_fn", "make_spec_decode_paged_fn"}
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute):
                assert node.attr not in own | factories, (
                    f"{rel}:{node.lineno} reaches for .{node.attr}"
                )
