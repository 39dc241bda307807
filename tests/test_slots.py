"""`serving/slots.SlotTable`: the decode-slot table the engine's paged
runner and the disagg decode worker both hold, driven here on its own
with a stub head (two state leaves; a step that adds its step operand and,
when it speculates, accepts what its params say) and a real, tiny
`KVPagePool`. The plain and the tree-verify step share
one body in the table, so they share one body here. A second stub adds a
`cache` leaf that the head neither reads nor initialises: what the table
does with the leaves that never leave the device."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from genrec_tpu.serving.kv_pool import KVPagePool, PagedConfig
from genrec_tpu.serving.slots import SlotTable

TOTAL = 4  # codes a slot decodes


class _StubHead:
    name = "stub"
    paged_init_step = 1
    paged_total_steps = TOTAL
    paged_result_leaves = ("acc", "tag")
    paged_init_leaves = ("acc", "tag")

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


class _CachedStubHead(_StubHead):
    """`acc` is all the head reads; `tag` comes with an init; `cache`
    (three floats a slot, one more a step) only ever lives in the table."""

    paged_result_leaves = ("acc",)
    paged_init_leaves = ("acc", "tag")

    def paged_state_zeros(self, n):
        return {**super().paged_state_zeros(n),
                "cache": np.zeros((n, 3), np.float32)}

    def make_decode_paged_fn(self):
        plain = super().make_decode_paged_fn()

        def fn(params, state, steps, *rest):
            return {**plain(params, state, steps, *rest),
                    "cache": state["cache"] + 1.0}

        return fn


class _Topology:
    n_nodes, beams = 7, 2  # 5 speculated tokens a slot


class _Tracer:
    def __init__(self, enabled):
        self.enabled = enabled
        self.spans = []

    def record_span(self, name, trace_id, t0, t1, **attrs):
        self.spans.append((name, trace_id, attrs))


def _table(spec, max_slots=8, floor=2, head=_StubHead):
    cfg = PagedConfig(max_slots=max_slots, page_size=8, pages_per_slot=2)
    pool = KVPagePool(cfg, 1, 1, 8)
    table = SlotTable(head(), pool, floor=floor,
                      spec_topology=_Topology() if spec else None,
                      spec_fanout=2)
    params = _params()
    table.executables = {S: table.compile(S, params) for S in table.rungs}
    table.compile_writer()
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
    writer = recorded.pop(("g", "slot_bind"))  # the row-write program
    assert writer.as_text().split("\n", 1)[0].split()[1].rstrip(",") == (
        "jit_stub_bind_r2")
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


# -- the state stays on the device ------------------------------------------


@SPEC
def test_step_fetches_the_leaves_the_head_reads_and_no_other(spec, monkeypatch):
    table, pool, params = _table(spec, head=_CachedStubHead)
    fetched = []
    device_get = jax.device_get
    monkeypatch.setattr(
        jax, "device_get",
        lambda tree: fetched.append(jax.tree_util.tree_map(jnp.shape, tree))
        or device_get(tree))
    # Any host conversion of a `cache` leaf (whole, or rows of it) raises.
    array_type = type(jnp.zeros(()))
    value = array_type._value

    def guarded(self):
        assert self.shape[1:] != (3,), f"a cache leaf {self.shape} left the device"
        return value.fget(self)

    monkeypatch.setattr(array_type, "_value", property(guarded))
    slots = [pool.admit(3) for _ in range(3)]
    table.bind(slots[0], {"acc": np.array([1.0, 2.0], np.float32)})
    res = _step(table, params)  # rung 2
    accept = 2 * 4 if spec else 0
    assert (res.slots, res.leaves) == (2, 1)
    assert res.pulled_bytes == 2 * 2 * 4 + accept
    # The step vectors (a step, a KV length and two pages a slot) and the
    # bind's two indices with two rows of `acc` and of `tag`.
    assert res.staged_bytes == 2 * (1 + 1 + 2) * 4 + (2 * 4 + 2 * 12)
    table.bind(slots[2])
    res = _step(table, params)  # rung 4: four rows of the one leaf
    assert (res.slots, res.leaves) == (4, 1)
    assert res.pulled_bytes == 4 * 2 * 4 + 2 * accept
    want = {"acc": (4, 2)}
    assert fetched == [({"acc": (2, 2)}, (2,) if spec else None),
                       (want, (4,) if spec else None)]  # ONE fetch a step
    assert table.row(slots[0]).keys() == {"acc"}  # and served from it
    monkeypatch.undo()
    # The leaves the host never saw moved on all the same, on the device.
    assert table.row(slots[0], ["cache"])["cache"].tolist() == [2.0] * 3
    assert table.row(slots[2], ["tag", "cache"])["cache"].tolist() == [1.0] * 3


def test_table_matches_host_resident_arithmetic_row_for_row():
    """A script of binds, steps, releases and rebinds over two rungs ends
    in the table the host-resident arithmetic ends in: rows [:S] staged,
    stepped and written back whole, a bound row zeroed and then
    initialised. Written out in numpy here."""
    table, pool, params = _table(False, head=_CachedStubHead)
    n = 8
    ref = {"acc": np.zeros((n, 2), np.float32), "tag": np.zeros(n, np.int32),
           "cache": np.zeros((n, 3), np.float32)}
    steps, active = np.zeros(n, np.int32), np.zeros(n, bool)

    def bind(slots, init=None):
        table.bind(slots, init)
        for leaf in ref.values():
            leaf[slots] = 0
        for k, v in (init or {}).items():
            ref[k][slots] = v
        steps[slots], active[slots] = 1, True

    def step(S):
        assert _step(table, params).slots == S
        at = np.where(active[:S], steps[:S], 0)
        ref["acc"][:S] += at[:, None]
        ref["cache"][:S] += 1.0
        steps[active] += 1

    def release(slot):
        table.release(slot)
        active[slot] = False

    slots = [pool.admit(3) for _ in range(6)]
    assert slots == [0, 1, 2, 3, 4, 5]
    bind(0, {"acc": np.array([5.0, 6.0], np.float32), "tag": np.int32(9)})
    step(2)
    bind([1, 2], {"acc": np.arange(4, dtype=np.float32).reshape(2, 2)})
    step(4)
    release(0)
    bind(0)  # the same slot again, with no init: nothing shows through
    step(4)
    bind(5, {"tag": np.int32(7)})
    step(8)
    release(1)
    release(5)
    bind(1, {"acc": np.float32(3.0), "tag": np.int32(4)})  # and with one
    release(2)
    release(0)
    step(2)
    bind([4, 5, 3])  # three rows in one bind: two launches of two rows
    step(8)
    for slot in range(n):
        row = table.row(slot, list(ref))
        for k, leaf in ref.items():
            np.testing.assert_array_equal(row[k], leaf[slot], err_msg=f"{k}[{slot}]")
    assert table.active_slots().tolist() == np.nonzero(active)[0].tolist()


_COMPILES = None  # a list while a test counts the backend's compiles


def _on_event(event, _secs, **_kw):
    if _COMPILES is not None and "backend_compile" in event:
        _COMPILES.append(event)


jax.monitoring.register_event_duration_secs_listener(_on_event)


@pytest.mark.parametrize("rows", [1, 2, 8])
def test_binding_any_number_of_rows_compiles_nothing(rows):
    global _COMPILES
    _COMPILES = []
    try:
        table, pool, params = _table(False, head=_CachedStubHead)
        warm_up, _COMPILES = len(_COMPILES), []
        writer = table.writer  # compiled at warm-up, once
        slots = [pool.admit(3) for _ in range(rows)]
        table.bind(slots, {"acc": np.full((rows, 2), 4.0, np.float32)})
        res = _step(table, params)
        after = list(_COMPILES)
    finally:
        _COMPILES = None
    assert warm_up >= len(table.rungs) + 1  # the listener does hear compiles
    assert after == [] and table.writer is writer
    assert res.live == rows
    assert [table.row(s)["acc"].tolist() for s in slots] == [[5.0, 5.0]] * rows


def test_finished_row_read_outlives_the_next_bind_of_its_slot():
    table, pool, params = _table(False, head=_CachedStubHead)
    slot = pool.admit(3)
    table.bind(slot, {"acc": np.array([2.0, 3.0], np.float32)})
    for _ in range(TOTAL - 1):
        _step(table, params)
    assert table.finished().tolist() == [slot]
    row = table.row(slot)
    assert row["acc"].tolist() == [8.0, 9.0]  # 2, 3 plus steps 1, 2, 3
    table.release(slot)
    table.bind(slot, {"acc": np.array([-1.0, -1.0], np.float32)})
    _step(table, params)
    assert row["acc"].tolist() == [8.0, 9.0]
    assert table.row(slot)["acc"].tolist() == [0.0, 0.0]


# -- the compiled programs, for a described chip -----------------------------


@pytest.fixture(scope="module")
def one_chip():
    """A v5e chip that is described and not attached: the TPU compiler
    compiles for it here. Only inside this fixture (never while a module
    is imported): one process at a time may load the TPU's library."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here, or it is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_rungs_advance_the_donated_table_in_place_on_the_chip(one_chip, monkeypatch):
    """What the chip's compiler makes of a rung and of the row-write
    program: every state leaf's input buffer is its output's, no host
    transfer, and below the top rung no copy, transpose or convert of a
    whole suffix cache (at the top rung the step's output IS the leaf).
    The CPU backend cannot show this: it has no donation."""
    import math

    from genrec_tpu.analysis.ir import hlo_ops_of_size
    from genrec_tpu.serving.heads import _tiny_tiger_head

    head, params, _, _ = _tiny_tiger_head()
    cfg = PagedConfig(max_slots=8, page_size=8, pages_per_slot=2)
    pool = KVPagePool(cfg, *head.paged_layout())
    table = SlotTable(head, pool, floor=4)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            tree)

    table._avals = described(table._avals)
    pool.k_pools, pool.v_pools = described(pool.k_pools), described(pool.v_pools)
    operands = tuple(described(op) for op in head.runtime_operands())
    # The program asks the backend whether it donates (not on the CPU).
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # What is compiled for a described chip cannot be read back from the
    # persistent cache without one: keep it out.
    from jax.experimental.compilation_cache import compilation_cache

    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        texts = {
            f"decode_s{S}": table.compile(S, described(params), operands).as_text()
            for S in table.rungs
        }
        texts["bind"] = table.compile_writer().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    monkeypatch.undo()
    leaves = len(table._avals)
    cache = math.prod(table._avals["cache_k"].shape)
    for name, text in texts.items():
        header = text.split("\n", 1)[0]
        assert header.count("may-alias") + header.count("must-alias") == leaves, (
            name, header[:400])
        for word in ("MoveToHost", "outfeed", "S(5)"):
            assert word not in text, (name, word)
        if name == "decode_s8":
            continue
        moved = [line for op, line in hlo_ops_of_size(text, cache)
                 if op in ("copy", "transpose", "convert")]
        assert not moved, (name, moved[:2])
