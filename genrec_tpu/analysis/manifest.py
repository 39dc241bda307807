"""Compile manifest: the registry of jitted entry points graftlint lowers.

The IR level of graftlint (analysis/ir.py) cannot discover "every
executable the fleet compiles" by static inspection — jit sites are
buried in trainer factories and serving warmup. Instead, the modules
that OWN an entry point register a small-shape builder here:

    from genrec_tpu.analysis.manifest import BuiltEntry, register_entry

    @register_entry("train/sasrec_packed_step", tags=("train",))
    def _entry() -> BuiltEntry:
        ...build a tiny model/state...
        return BuiltEntry(fn=jax.jit(step, donate_argnums=0),
                          args=(state, batch), expect_donated=(0,))

Registration is import-time cheap (the decorator stores the builder;
nothing is built or traced until graftlint calls it), so trainers and
serving heads can register unconditionally at module scope. The builder
must jit EXACTLY the way production does (same donate_argnums, same
wrapper factories) — the donation audit checks the declared donation of
the built fn, so a builder that re-jits with its own flags would audit
itself instead of the production path.

``expect_donated`` lists the argnums whose buffers are dead after the
call in production (train state consumed by the step, decode slot state
replaced by the step's output). The donation audit reports any of these
that the jit does NOT donate as wasted HBM (one dead copy of the buffer
kept alive across the call).

This module imports nothing from genrec_tpu (and no jax at module
scope): like obs, the analysis package is importable from every layer.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Mapping, Optional, Sequence


@dataclasses.dataclass
class BuiltEntry:
    """One lowered-and-analyzable entry point, produced by a builder.

    ``fn`` must be a jitted callable (supports ``.lower``/``.trace``);
    ``args`` may mix concrete arrays and ShapeDtypeStructs.
    """

    fn: Any
    args: tuple
    expect_donated: tuple = ()
    allow_f64: bool = False
    #: Per-entry constant-bake threshold override (bytes). Entries whose
    #: CI shapes shrink a production-sized constant below the global
    #: threshold pin a tighter one so the rule still bites (the same
    #: self-test discipline as the check_*_hlo regex self-tests).
    max_const_bytes: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class EntryPoint:
    name: str
    tags: tuple
    build: Callable[[], BuiltEntry]
    provider: str  # module that registered it, for the report


_REGISTRY: dict[str, EntryPoint] = {}

#: Modules that register entries at import time. graftlint imports these;
#: adding an entry point elsewhere means adding its module here (see
#: docs/ANALYSIS.md "Registering a new entry point").
DEFAULT_PROVIDERS = (
    "genrec_tpu.trainers.sasrec_trainer",
    "genrec_tpu.trainers.tiger_trainer",
    "genrec_tpu.serving.heads",
)


def register_entry(name: str, *, tags: Sequence[str] = ()):
    """Decorator: register ``builder`` as compile-manifest entry ``name``.

    Re-registration under the same name overwrites (idempotent module
    reloads in tests), returns the builder unchanged.
    """

    def deco(builder: Callable[[], BuiltEntry]):
        _REGISTRY[name] = EntryPoint(
            name=name,
            tags=tuple(tags),
            build=builder,
            provider=getattr(builder, "__module__", "?"),
        )
        return builder

    return deco


def registered_entries() -> Mapping[str, EntryPoint]:
    """The entries registered so far (import providers first)."""
    return dict(_REGISTRY)


def load_default_entries(
    providers: Sequence[str] = DEFAULT_PROVIDERS,
) -> Mapping[str, EntryPoint]:
    """Import every provider module (running their register_entry
    decorators) and return the populated manifest."""
    for mod in providers:
        importlib.import_module(mod)
    return registered_entries()
