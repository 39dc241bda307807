"""`serving/slots.SlotTable`: the decode-slot table the engine's paged
runner and the disagg decode worker both hold, driven here on its own
with a stub head (two state leaves; a step that adds its step operand and,
when it speculates, accepts what its params say) and a real, tiny
`KVPagePool`. The plain and the tree-verify step share
one body in the table, so they share one body here."""

import numpy as np
import pytest

import jax.numpy as jnp

from genrec_tpu.serving.kv_pool import KVPagePool, PagedConfig
from genrec_tpu.serving.slots import SlotTable

TOTAL = 4  # codes a slot decodes


class _StubHead:
    name = "stub"
    paged_init_step = 1
    paged_total_steps = TOTAL

    def paged_state_zeros(self, n):
        return {"acc": np.zeros((n, 2), np.float32),
                "tag": np.zeros((n,), np.int32)}

    def runtime_operands(self):
        return ()

    def make_decode_paged_fn(self):
        def fn(params, state, steps, block_tables, seq_lens, k_pools, v_pools):
            return {"acc": state["acc"] + params["scale"] * steps[:, None],
                    "tag": state["tag"]}

        return fn

    def make_spec_decode_paged_fn(self, fanout):
        plain = self.make_decode_paged_fn()

        def fn(params, state, steps, *rest):
            # The raw accept length is whatever the params say: garbage
            # in, so that the table's clamp is what is tested.
            return (plain(params, state, steps, *rest),
                    jnp.full(steps.shape, params["accept"]))

        return fn


class _Topology:
    n_nodes, beams = 7, 2  # 5 speculated tokens a slot


class _Tracer:
    def __init__(self, enabled):
        self.enabled = enabled
        self.spans = []

    def record_span(self, name, trace_id, t0, t1, **attrs):
        self.spans.append((name, trace_id, attrs))


def _table(spec, max_slots=8, floor=2):
    cfg = PagedConfig(max_slots=max_slots, page_size=8, pages_per_slot=2)
    pool = KVPagePool(cfg, 1, 1, 8)
    table = SlotTable(_StubHead(), pool, floor=floor,
                      spec_topology=_Topology() if spec else None,
                      spec_fanout=2)
    params = _params()
    table.executables = {S: table.compile(S, params) for S in table.rungs}
    return table, pool, params


def _params(accept=1):
    return {"scale": jnp.float32(1.0), "accept": jnp.int32(accept)}


def _step(table, params, tracer=None, trace_of=None):
    return table.step(params, tracer or _Tracer(False),
                      lambda: {"component": "test"}, trace_of)


SPEC = pytest.mark.parametrize("spec", [False, True], ids=["plain", "tree_verify"])


@pytest.mark.parametrize("max_slots,floor,rungs", [
    (64, 16, (16, 32, 64)), (8, 1, (1, 2, 4, 8)), (6, 4, (4, 6)),
    (2, 2, (2,)), (2, 16, (2,)), (4, 0, (1, 2, 4)),
])
def test_rungs_halve_from_max_slots_down_to_the_floor(max_slots, floor, rungs):
    cfg = PagedConfig(max_slots=max_slots, page_size=8, pages_per_slot=2)
    table = SlotTable(_StubHead(), KVPagePool(cfg, 1, 1, 8), floor=floor)
    assert table.rungs == rungs


@SPEC
def test_executables_are_named_by_head_kind_and_rung(spec):
    table, _pool, _ = _table(spec)
    kind = "spec" if spec else "decode"
    for S, exe in table.executables.items():
        assert exe.as_text().split("\n", 1)[0].split()[1].rstrip(",") == (
            f"jit_stub_{kind}_s{S}")
    recorded = {}

    class Ledger:
        def record_operand(self, group, name, nbytes):
            recorded[(group, name)] = nbytes

        def record_executable(self, group, name, exe):
            recorded[(group, name)] = exe

    table.record_memory(Ledger(), "g")
    assert recorded.pop(("g", "paged_slot_state")) == 8 * (2 * 4 + 4)
    label = "spec_decode" if spec else "decode"
    assert set(recorded) == {("g", f"{label}/S{S}") for S in table.rungs}


def test_bound_row_is_zeroed_before_the_init_is_written():
    table, pool, params = _table(False)
    slot = pool.admit(3)
    table.bind(slot, {"acc": np.array([5.0, 6.0], np.float32),
                      "tag": np.int32(9)})
    _step(table, params)
    assert table.row(slot)["acc"].tolist() == [6.0, 7.0]
    table.release(slot)
    assert table.idle
    # Rebound with an init that lacks `tag`, then with no init at all: the
    # last tenant's values must not show through.
    table.bind(slot, {"acc": np.array([1.0, 1.0], np.float32)})
    row = table.row(slot)
    assert row["acc"].tolist() == [1.0, 1.0] and row["tag"] == 0
    table.bind(slot)
    assert table.row(slot)["acc"].tolist() == [0.0, 0.0]
    assert table.live == 1 and table.active_slots().tolist() == [slot]


def test_bind_takes_several_slots_with_per_slot_rows():
    table, pool, _ = _table(False)
    slots = [pool.admit(3) for _ in range(3)]
    table.bind(slots, {"acc": np.arange(6, dtype=np.float32).reshape(3, 2)})
    assert [table.row(s)["acc"].tolist() for s in slots] == [
        [0.0, 1.0], [2.0, 3.0], [4.0, 5.0]]
    assert table.row(slots[1], ["acc"]).keys() == {"acc"}


def test_an_init_that_does_not_fit_leaves_the_slot_unbound():
    table, pool, _ = _table(False)
    slot = pool.admit(3)
    with pytest.raises(ValueError):
        table.bind(slot, {"acc": np.zeros((3,), np.float32)})
    assert table.idle
    with pytest.raises(KeyError):
        table.bind(slot, {"no_such_leaf": np.int32(1)})
    assert table.idle


def test_row_read_is_a_copy():
    table, pool, params = _table(False)
    slot = pool.admit(3)
    table.bind(slot, {"acc": np.array([2.0, 3.0], np.float32)})
    row = table.row(slot)
    _step(table, params)  # the live buffer moves on
    table.release(slot)
    table.bind(slot)  # and the slot is reused
    assert row["acc"].tolist() == [2.0, 3.0]
    row["acc"][:] = 99.0  # nor does the copy write through
    assert table.row(slot)["acc"].tolist() == [0.0, 0.0]


@SPEC
def test_step_runs_the_smallest_rung_covering_the_highest_active_slot(spec):
    table, pool, params = _table(spec)  # rungs 2, 4, 8
    assert _step(table, params) is None  # idle: nothing launched
    slots = [pool.admit(5) for _ in range(5)]
    assert slots == [0, 1, 2, 3, 4]
    table.bind(slots[0])
    res = _step(table, params)
    assert (res.slots, res.live, res.kv_tokens, res.leaves) == (2, 1, 5, 2)
    assert res.t_stage <= res.t0 <= res.t_launched <= res.t1
    table.bind(slots[2])
    assert _step(table, params).slots == 4
    table.bind(slots[4])
    res = _step(table, params)
    assert (res.slots, res.live, res.kv_tokens) == (8, 3, 15)
    assert res.drafted == (15 if spec else 0)
    assert (res.accept is not None) == spec
    # The rung follows the HIGHEST live slot, not the count.
    table.release(slots[0])
    table.release(slots[2])
    assert _step(table, params).slots == 8
    table.release(slots[4])
    table.bind(slots[1])
    assert _step(table, params).slots == 2


def test_plain_step_advances_each_live_slot_by_one_until_finished():
    table, pool, params = _table(False)
    a, b = pool.admit(3), pool.admit(3)
    table.bind(a)
    _step(table, params)  # a: step 1 -> 2, acc += 1
    table.bind(b)
    _step(table, params)  # a: 2 -> 3, acc += 2; b: 1 -> 2, acc += 1
    assert table.row(a)["acc"].tolist() == [3.0, 3.0]
    assert table.row(b)["acc"].tolist() == [1.0, 1.0]
    assert table.finished().tolist() == []
    _step(table, params)
    assert table.finished().tolist() == [a]
    table.release(a)
    assert table.finished().tolist() == []
    _step(table, params)
    assert table.finished().tolist() == [b]


@pytest.mark.parametrize("raw", [-3, 0, 1, 2, 3, 99])
def test_accept_clamp_keeps_the_advance_inside_the_remaining_codes(raw):
    table, pool, params = _table(True)
    early, late = pool.admit(3), pool.admit(3)
    table.bind(late)
    _step(table, params)
    _step(table, params)  # late sits at step 3 of 4: one code left
    table.bind(early)  # step 1: three left
    res = _step(table, _params(accept=raw))
    assert res.accept.dtype == np.int32
    assert res.accept.tolist() == [min(max(raw, 1), 3), 1]
    assert table.finished().tolist() == (
        [early, late] if raw >= 3 else [late])


@SPEC
def test_step_spans_carry_each_request_its_own_position(spec):
    table, pool, params = _table(spec)
    slots = [pool.admit(3) for _ in range(3)]
    table.bind(slots[0])
    _step(table, _params(accept=2))
    table.bind(slots[1])
    table.bind(slots[2])
    traces = {0: ("t0", "p0", "up"), 1: ("t1", "p1", None), 2: None}
    tracer = _Tracer(True)
    _step(table, params, tracer, traces.__getitem__)
    by_trace = {}
    for name, tid, attrs in tracer.spans:
        assert attrs.pop("component") == "test"
        by_trace.setdefault(tid, []).append((name, attrs))
    at0 = 3 if spec else 2  # slot 0 advanced by its accept of 2, or by 1
    if spec:
        assert by_trace == {
            "t0": [("draft", {"parent_id": "p0", "step": at0, "drafted": 5}),
                   ("tree_verify", {"parent_id": "p0", "step": at0,
                                    "slots": 4, "accept_len": 1}),
                   ("accept", {"parent_id": "p0", "accept_len": 1})],
            "t1": [("draft", {"parent_id": "p1", "step": 1, "drafted": 5}),
                   ("tree_verify", {"parent_id": "p1", "step": 1,
                                    "slots": 4, "accept_len": 1}),
                   ("accept", {"parent_id": "p1", "accept_len": 1})],
        }
    else:
        assert by_trace == {
            "t0": [("decode_step", {"parent_id": "p0", "step": at0, "slots": 4})],
            "t1": [("decode_step", {"parent_id": "p1", "step": 1, "slots": 4})],
        }

    def never(slot):
        raise AssertionError("trace looked up with the tracer off")

    assert _step(table, params, _Tracer(False), never) is not None
