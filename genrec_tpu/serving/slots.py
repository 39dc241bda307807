"""The decode side of a paged head's slot set: one table, held by the
co-located engine's `_PagedRunner` and by the disaggregated
`DecodeWorker` alike.

`SlotTable` owns what lives between two decode steps and what a step
does with it: the slot state tree, each slot's step counter and active
flag, the ladder of slot rungs and the executable compiled for each,
binding a row, reading a row, and the fixed-shape step itself (rung
choice, staging, launch, fetch, accept clamp, the per-request spans).
Where the state lives between steps and what the host pulls after a
launch is decided HERE and nowhere else, so a change to either is one
edit that the serving cell checks.

The state tree is DEVICE-resident. A rung's executable takes the whole
``max_slots`` table donated, advances its first S rows in place and
hands the table back, so a step's output is the next step's input and no
state leaf crosses to the host for the table's sake. The host keeps a
numpy copy of the leaves the head's `paged_finalize` reads
(`head.paged_result_leaves`), refreshed from each launch in one fetch,
and `row` answers from it. Rows enter through `bind`, which queues host
rows; the next step (or device row read) writes them to the device in
one launch of the table's own small row-write program.

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
from jax.sharding import NamedSharding, PartitionSpec

from genrec_tpu.obs.memory import tree_nbytes
from genrec_tpu.serving.aot import (
    donate_argnums,
    named,
    paged_decode_donate_argnums,
    sds_tree,
)


def stage(tree, mesh):
    """Per-call operands (batch arrays, step vectors, block tables, the
    rows a bind writes; never the slot state, which stays on the device)
    on their way into a compiled executable. Single device:
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
    pulled_bytes: int  # of those leaves' S rows, and the accept lengths
    staged_bytes: int  # of the step vectors, and the rows a bind queued
    drafted: int  # speculated tokens proposed over the live slots
    accept: Optional[np.ndarray]
    t_stage: float  # staging began
    t0: float  # launch began
    t_launched: float  # the executable call returned
    t1: float  # outputs on the host


class SlotTable:
    """Slot state, slot rungs and the decode step of ONE paged head.

    Single-writer: every method runs on the thread that owns `pool` (the
    engine's batcher, the front's runtime thread), except `compile` and
    `compile_writer`, which read shapes only and may run on a catalog
    staging thread. With ``spec_topology`` the tree-verify step is
    compiled and launched INSTEAD of the plain step at every rung (same
    operands; it returns (state, accept_len), accept_len >= 1 since the
    root level is exact).
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
        zeros = head.paged_state_zeros(n)
        # The table itself, on the device (under a mesh: replicated, the
        # placement the mesh-lowered executables are compiled to take and
        # to return, so an output is fed straight back).
        self._placement = (
            None if mesh is None else NamedSharding(mesh, PartitionSpec())
        )
        self._state = jax.device_put(zeros, self._placement)
        self._avals = sds_tree(self._state)  # donated buffers come and go
        #: Bytes of the leaves the head names as recurrent state
        #: (`Head.paged_recurrent_leaves`): what the table holds that is
        #: not KV, for the engine's gauges.
        self.recurrent_nbytes = tree_nbytes(
            [self._avals[k] for k in getattr(head, "paged_recurrent_leaves", ())])
        # The host's copy of the leaves the head's finalize reads, as of
        # the last launch or bind: what `row` serves.
        self._host = {k: np.array(zeros[k]) for k in head.paged_result_leaves}
        # slot -> the rows (init leaf -> value) a bind queued for it.
        self._pending: dict[int, dict] = {}
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
        #: The row-write program (`compile_writer`, at the caller's
        #: warmup): rows of the lowest rung's count a launch.
        self.writer = None

    # -- compilation ---------------------------------------------------------

    def compile(self, S: int, params, operands=None):
        """The step executable at rung ``S``: `jit_{head}_decode_s{S}`,
        or `jit_{head}_spec_s{S}` when the table speculates (the tree
        topology is a static constant of the trace). ``operands``
        overrides the head's live runtime operands (catalog precompile
        against a NEW trie aval). It takes the whole table and the
        step's three vectors as ONE staged (S, 2 + pages) array, runs
        the head's step on rows [:S] and writes them back in place;
        beside the table it returns those S rows of the head's result
        leaves (all the host fetches) and the accept lengths (None on a
        plain step)."""
        head, pool = self.head, self.pool
        if self.spec_topology is not None:
            fn, kind = head.make_spec_decode_paged_fn(self._spec_fanout), "spec"
        else:
            fn, kind = head.make_decode_paged_fn(), "decode"
        ops = operands if operands is not None else head.runtime_operands()
        n_ops, result = len(ops), tuple(self._host)

        def step(params, *rest):
            table, vectors, k_pools, v_pools = rest[n_ops:]
            out = fn(params, *rest[:n_ops],
                     {k: v[:S] for k, v in table.items()},
                     vectors[:, 0], vectors[:, 2:], vectors[:, 1],
                     k_pools, v_pools)
            out, accept = out if kind == "spec" else (out, None)
            # A leaf the step does not return (a read-only one) is
            # handed back as it came, its buffer the donated one.
            table = {
                k: jax.lax.dynamic_update_slice_in_dim(v, out[k], 0, axis=0)
                if k in out else v
                for k, v in table.items()
            }
            return table, {k: out[k] for k in result}, accept

        args = (
            params,
            *(sds_tree(op) for op in ops),  # trie operand: threaded, not baked
            self._avals,
            jax.ShapeDtypeStruct((S, 2 + pool.cfg.pages_per_slot), np.int32),
            sds_tree(pool.k_pools),
            sds_tree(pool.v_pools),
        )
        # Donate the table: its output takes its place, so the input is
        # dead after the call — undonated, XLA would hold the whole
        # table twice and copy every leaf every step (graftlint
        # missing_donation audits the same argnums).
        donate = donate_argnums(*paged_decode_donate_argnums(n_ops))
        return jax.jit(
            named(step, f"{head.name}_{kind}_s{S}"), donate_argnums=donate,
            out_shardings=self._placement,
        ).lower(*args).compile()

    def compile_writer(self):
        """The row-write program `jit_{head}_bind_r{R}`, R the lowest
        rung: the table donated, R slot indices (``max_slots`` = no row)
        and R rows of each leaf an init may carry
        (`head.paged_init_leaves`); every other leaf's rows are zeroed.
        One fixed shape, so binding one row or R compiles nothing."""
        R = self.rungs[0]

        def bind(table, idx, rows):
            return {
                k: v.at[idx].set(rows.get(k, jnp.zeros((), v.dtype)),
                                 mode="drop")
                for k, v in table.items()
            }

        self.writer = jax.jit(
            named(bind, f"{self.head.name}_bind_r{R}"),
            donate_argnums=donate_argnums(0), out_shardings=self._placement,
        ).lower(
            self._avals, jax.ShapeDtypeStruct((R,), np.int32),
            sds_tree(self._init_rows(R)),
        ).compile()
        return self.writer

    def record_memory(self, ledger, group: str) -> None:
        """The table's share of an HBM model: the slot state (on the
        device between steps as during them), each rung's executable and
        the row-write program."""
        ledger.record_operand(group, "paged_slot_state",
                              tree_nbytes(self._avals))
        label = "spec_decode" if self.spec_topology is not None else "decode"
        for S, ex in self.executables.items():
            ledger.record_executable(group, f"{label}/S{S}", ex)
        if self.writer is not None:
            ledger.record_executable(group, "slot_bind", self.writer)

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

    def _init_rows(self, n: int) -> dict:
        """``n`` zero rows of each leaf an init may carry."""
        return {
            k: np.zeros((n, *self._avals[k].shape[1:]), self._avals[k].dtype)
            for k in self.head.paged_init_leaves
        }

    def bind(self, slots, init=None) -> None:
        """Enter ``slots`` (one index or several) into decode: rows
        zeroed, then ``init`` written (HOST values, leaf -> value or
        per-slot rows, of the head's `paged_init_leaves`; a leaf it lacks
        stays zero), the head's init step, active. The rows are marked
        active LAST: an init that does not fit raises with the slots
        still unbound. The device rows are written by the next step (or
        device row read) in one launch for every bind since the last;
        the host's copy of the result leaves is written here."""
        idx = np.atleast_1d(np.asarray(slots, np.int64))
        rows = self._init_rows(len(idx))
        for key, val in (init or {}).items():
            rows[key][:] = val
        for k, leaf in self._host.items():
            leaf[idx] = rows.get(k, 0)
        for j, slot in enumerate(idx):
            self._pending[int(slot)] = {k: v[j] for k, v in rows.items()}
        self._steps[idx] = self.head.paged_init_step
        self._active[idx] = True

    def flush(self) -> int:
        """Write the rows queued by `bind` to the device: one launch of
        the row-write program for each R of them, dispatched and not
        waited for. Returns the bytes staged."""
        if not self._pending:
            return 0
        pending, self._pending = list(self._pending.items()), {}
        R, staged = self.rungs[0], 0
        for at in range(0, len(pending), R):
            chunk = pending[at:at + R]
            idx = np.full(R, self.pool.cfg.max_slots, np.int32)
            idx[:len(chunk)] = [slot for slot, _ in chunk]
            rows = self._init_rows(R)
            for j, (_, row) in enumerate(chunk):
                for k, v in row.items():
                    rows[k][j] = v
            self._state = self.writer(
                self._state, stage(idx, self.mesh), stage(rows, self.mesh)
            )
            staged += idx.nbytes + tree_nbytes(rows)
        return staged

    def row(self, slot, keys=None) -> dict:
        """One slot's state row, COPIED: what is built from it must not
        change when the slot is reused by a later admission (observed as
        responses "mixing" catalog versions after a hot swap). Without
        ``keys``: the head's result leaves, from the host's copy. A leaf
        the host does not keep is read from the device, a blocking round
        trip that nothing on the serving path makes."""
        keys = self._host if keys is None else keys
        if any(k not in self._host for k in keys):
            self.flush()
        return {
            k: np.array((self._host if k in self._host else self._state)[k][slot])
            for k in keys
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
        # Host-side operand staging: the rows bound since the last step,
        # then the step's three vectors. On spec iterations this interval
        # is the `draft` span: the drafter's trie expansion executes
        # inside the verify call, so staging is the only host-visible
        # slice of the draft phase.
        t_stage = time.monotonic()
        staged_bytes = self.flush()
        # The step's vectors as ONE staged array, a row a slot: its step
        # (0 on an inactive slot), its KV length, its block table. A
        # host-to-device transfer costs by the array, not by the byte.
        vectors = np.empty((S, 2 + pool.cfg.pages_per_slot), np.int32)
        vectors[:, 0] = np.where(self._active[:S], self._steps[:S], 0)
        vectors[:, 1] = pool.seq_lens[:S]
        vectors[:, 2:] = pool.block_tables[:S]
        staged_bytes += vectors.nbytes
        args = (
            params,
            *self.head.runtime_operands(),
            self._state,
            stage(vectors, mesh),
            pool.k_pools,
            pool.v_pools,
        )
        t0 = time.monotonic()
        self._state, pulled, accept = self.executables[S](*args)
        t_launched = time.monotonic()
        # ONE fetch of what the host reads: S rows of the head's result
        # leaves and, speculating, the accept lengths. It returns when
        # the program has ended.
        pulled, accept = jax.device_get((pulled, accept))
        for k, v in pulled.items():
            self._host[k][:S] = v
        adv = None
        if topo is not None:
            # Clamped against remaining codes so a garbage row can never
            # overshoot a slot's total, and to >= 1: the root level is
            # always exact.
            adv = np.maximum(
                np.minimum(
                    accept[active_idx],
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
            leaves=len(pulled), pulled_bytes=tree_nbytes((pulled, accept)),
            staged_bytes=staged_bytes,
            drafted=len(active_idx) * self._drafted, accept=adv,
            t_stage=t_stage, t0=t0, t_launched=t_launched, t1=t1,
        )
