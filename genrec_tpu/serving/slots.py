"""The decode side of a paged head's slot set: one table, held by the
co-located engine's `_PagedRunner` and by the disaggregated
`DecodeWorker` alike.

`SlotTable` owns what lives between two decode steps and what a step
does with it: the slot state tree (host-resident numpy between steps),
each slot's step counter and active flag, the ladder of slot rungs and
the executable compiled for each, binding a row, reading a row, and the
fixed-shape step itself (rung choice, staging, launch, write-back,
accept clamp, the per-request spans). Where the state lives between
steps and what the host pulls after a launch is decided HERE and nowhere
else, so a change to either is one edit that the serving cell checks.

What a caller keeps: who occupies a slot (a queue entry or a `Flight`
with its handoff), the `Response` and the future, `pool.evict`, the
prefix index, the counters and the batcher lane. What differs between
the callers comes in as a value: the params of this call, the mesh, the
tracer with the span identity, a function from slot to its trace.
"""

from __future__ import annotations

import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from genrec_tpu.obs.memory import tree_nbytes
from genrec_tpu.serving.aot import (
    donate_argnums,
    named,
    paged_decode_donate_argnums,
    sds_tree,
)


def stage(tree, mesh):
    """Per-call operands (batch arrays, slot state, step vectors, block
    tables) on their way into a compiled executable. Single device:
    device arrays, as always. Under a mesh: HOST arrays — the mesh-lowered
    executable places them to its expected (replicated) sharding at
    dispatch, whereas a device-0-committed jnp array would be rejected as
    a sharding mismatch."""
    return jax.tree_util.tree_map(
        jnp.asarray if mesh is None else np.asarray, tree
    )


class DecodeStep(NamedTuple):
    """What one `SlotTable.step` launched, for the caller's counters and
    lane phases. `accept` is None on a plain step, else the codes each
    live slot advanced by (clamped, in slot order)."""

    slots: int  # the rung the step ran at
    live: int
    kv_tokens: int
    leaves: int  # state leaves pulled back
    drafted: int  # speculated tokens proposed over the live slots
    accept: Optional[np.ndarray]
    t_stage: float  # staging began
    t0: float  # launch began
    t_launched: float  # the executable call returned
    t1: float  # outputs on the host


class SlotTable:
    """Slot state, slot rungs and the decode step of ONE paged head.

    Single-writer: every method runs on the thread that owns `pool` (the
    engine's batcher, the front's runtime thread), except `compile`,
    which reads shapes only and may run on a catalog staging thread.
    With ``spec_topology`` the tree-verify step is compiled and launched
    INSTEAD of the plain step at every rung (same operands; it returns
    (state, accept_len), accept_len >= 1 since the root level is exact).
    """

    def __init__(self, head, pool, *, floor: int, mesh=None,
                 spec_topology=None, spec_fanout=None):
        self.head = head
        self.pool = pool
        self.mesh = mesh
        self.spec_topology = spec_topology
        self._spec_fanout = spec_fanout
        # Speculated tokens a slot proposes a step: the tree less its roots.
        self._drafted = (
            int(spec_topology.n_nodes - spec_topology.beams)
            if spec_topology is not None else 0
        )
        n = pool.cfg.max_slots
        self._state = head.paged_state_zeros(n)
        self._steps = np.zeros(n, np.int32)
        self._active = np.zeros(n, bool)
        # The collapsed decode-side ladder: max_slots halving down to
        # ``floor``. Slots fill lowest-index-first (kv_pool heap), so a
        # step runs at the smallest rung covering the highest active
        # slot — a lightly loaded table doesn't pay max_slots of decode.
        floor = max(int(floor), 1)
        rungs, s = {n}, n
        while s > floor:
            s = max(s // 2, floor)
            rungs.add(s)
        self.rungs = tuple(sorted(rungs))
        #: rung -> AOT executable (installed by the caller's warmup; a
        #: catalog rung growth swaps the whole table in at once).
        self.executables: dict[int, object] = {}

    # -- compilation ---------------------------------------------------------

    def compile(self, S: int, params, operands=None):
        """The step executable at rung ``S``: `jit_{head}_decode_s{S}`,
        or `jit_{head}_spec_s{S}` when the table speculates (the tree
        topology is a static constant of the trace). ``operands``
        overrides the head's live runtime operands (catalog precompile
        against a NEW trie aval)."""
        head, pool = self.head, self.pool
        if self.spec_topology is not None:
            fn, kind = head.make_spec_decode_paged_fn(self._spec_fanout), "spec"
        else:
            fn, kind = head.make_decode_paged_fn(), "decode"
        ops = operands if operands is not None else head.runtime_operands()
        args = (
            params,
            *(sds_tree(op) for op in ops),  # trie operand: threaded, not baked
            sds_tree({k: v[:S] for k, v in self._state.items()}),
            jax.ShapeDtypeStruct((S,), np.int32),
            jax.ShapeDtypeStruct((S, pool.cfg.pages_per_slot), np.int32),
            jax.ShapeDtypeStruct((S,), np.int32),
            sds_tree(pool.k_pools),
            sds_tree(pool.v_pools),
        )
        # Donate the slot-state operand: step()'s write-back overwrites
        # every row, so the input tree is dead after the call — undonated,
        # XLA would double-buffer the whole slot ladder's decode state
        # (graftlint missing_donation audits the same argnums).
        donate = donate_argnums(*paged_decode_donate_argnums(len(ops)))
        return jax.jit(
            named(fn, f"{head.name}_{kind}_s{S}"), donate_argnums=donate
        ).lower(*args).compile()

    def record_memory(self, ledger, group: str) -> None:
        """The table's share of an HBM model: the slot state (host numpy
        between steps, but on the device during every call) and each
        rung's executable."""
        ledger.record_operand(group, "paged_slot_state",
                              tree_nbytes(self._state))
        label = "spec_decode" if self.spec_topology is not None else "decode"
        for S, ex in self.executables.items():
            ledger.record_executable(group, f"{label}/S{S}", ex)

    # -- rows ----------------------------------------------------------------

    @property
    def idle(self) -> bool:
        return not self._active.any()

    @property
    def live(self) -> int:
        return int(self._active.sum())

    def active_slots(self) -> np.ndarray:
        return np.nonzero(self._active)[0]

    def finished(self) -> np.ndarray:
        """Active slots whose every code is decoded."""
        return np.nonzero(
            self._active & (self._steps >= self.head.paged_total_steps)
        )[0]

    def bind(self, slots, init=None) -> None:
        """Enter ``slots`` (one index or several) into decode: rows
        zeroed, then ``init`` written (leaf -> value or per-slot rows; a
        leaf it lacks stays zero), the head's init step, active. The
        rows are marked active LAST: an init that does not fit raises
        with the slots still unbound."""
        for leaf in self._state.values():
            leaf[slots] = 0
        if init:
            for key, val in init.items():
                self._state[key][slots] = val
        self._steps[slots] = self.head.paged_init_step
        self._active[slots] = True

    def row(self, slot, keys=None) -> dict:
        """One slot's state row, COPIED: a bare ``leaf[slot]`` is a numpy
        VIEW into the live buffer, and what is built from it would change
        when the slot is reused by a later admission (observed as
        responses "mixing" catalog versions after a hot swap)."""
        return {
            k: np.array(self._state[k][slot])
            for k in (self._state if keys is None else keys)
        }

    def release(self, slot) -> None:
        self._active[slot] = False

    # -- decode (one fixed-shape step over all slots) ------------------------

    def step(self, params, tracer, ident, trace_of) -> Optional[DecodeStep]:
        """Advance every active slot: one decode position through the
        plain step, or 1..(1 + spec_depth) through the tree-verify step.
        None when no slot is active. With ``tracer`` on, every resident
        request gets the step's interval(s) tagged with its own position
        (`decode_step`, or `draft` -> `tree_verify` -> `accept`):
        ``ident()`` is the recording component's identity attrs and
        ``trace_of(slot)`` a sequence starting (trace id, parent span
        id), or None for an untraced request."""
        active_idx = self.active_slots()
        if not len(active_idx):
            return None
        topo, pool, mesh = self.spec_topology, self.pool, self.mesh
        hi = int(active_idx[-1]) + 1
        S = next(s for s in self.rungs if s >= hi)
        # Host-side operand staging. On spec iterations this interval is
        # the `draft` span: the drafter's trie expansion executes inside
        # the verify call, so staging is the only host-visible slice of
        # the draft phase.
        t_stage = time.monotonic()
        args = (
            params,
            *self.head.runtime_operands(),
            stage({k: v[:S] for k, v in self._state.items()}, mesh),
            stage(np.where(self._active[:S], self._steps[:S], 0)
                  .astype(np.int32), mesh),
            stage(pool.block_tables[:S], mesh),
            stage(pool.seq_lens[:S], mesh),
            pool.k_pools,
            pool.v_pools,
        )
        t0 = time.monotonic()
        out = self.executables[S](*args)
        if topo is not None:
            out, accept = out
        t_launched = time.monotonic()
        for k, v in out.items():  # write back into the host rows
            self._state[k][:S] = np.asarray(v)
        adv = None
        if topo is not None:
            # Accept lengths ride the same fetch as the state write-back
            # (no extra host<->device sync); clamped against remaining
            # codes so a garbage row can never overshoot a slot's total,
            # and to >= 1: the root level is always exact.
            adv = np.maximum(
                np.minimum(
                    np.asarray(accept)[active_idx],
                    self.head.paged_total_steps - self._steps[active_idx],
                ).astype(np.int32),
                1,
            )
        t1 = time.monotonic()
        traced = []
        if tracer.enabled:
            idn, rec = ident(), tracer.record_span
            for i, slot in enumerate(active_idx):
                tr = trace_of(slot)
                if tr is None:
                    continue
                at = int(self._steps[slot])
                if adv is None:
                    rec("decode_step", tr[0], t0, t1, parent_id=tr[1],
                        step=at, slots=S, **idn)
                else:
                    rec("draft", tr[0], t_stage, t0, parent_id=tr[1],
                        step=at, drafted=self._drafted, **idn)
                    rec("tree_verify", tr[0], t0, t1, parent_id=tr[1],
                        step=at, slots=S, accept_len=int(adv[i]), **idn)
                    traced.append((tr, int(adv[i])))
        self._steps[active_idx] += 1 if adv is None else adv
        if traced:
            t2 = time.monotonic()
            for tr, n in traced:
                rec("accept", tr[0], t1, t2, parent_id=tr[1],
                    accept_len=n, **idn)
        return DecodeStep(
            slots=S, live=len(active_idx),
            kv_tokens=int(pool.seq_lens[active_idx].sum()),
            leaves=len(out), drafted=len(active_idx) * self._drafted, accept=adv,
            t_stage=t_stage, t0=t0, t_launched=t_launched, t1=t1,
        )
