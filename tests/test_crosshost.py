"""Cross-host serving (genrec_tpu/disagg/net.py + tensor-parallel
serving operands) — the PR-17 tentpole pins.

Acceptance bars, each pinned here:

- socket roundtrip parity: a front serving TIGER through a decode-host
  PROCESS returns sem-ids bit-identical to the in-process serializing
  front, under mixed warm/cold churn, with zero steady-state recompiles
  on BOTH sides (the peer's counter read across the wire);
- SIGKILL of the decode process mid-frame loses nothing: every accepted
  request resolves typed (at-most-once re-submit through the surviving
  host), the flight recorder narrates the death with the peer address;
- params-step skew is refused typed ACROSS the wire (the proxy's
  handshake-identity check), never silently mixed;
- tensor-parallel operands: `mesh=` row-shards the retrieval item table
  (pinned via the placed sharding SPEC, not just numerics) and shards
  the KV page bank over the head axis, with results bit-identical to
  single-device at a forced multi-device CPU mesh;
- the serializing transport's pad-skip: a run already at its compiled
  rung length crosses `admit` without an `np.pad` copy (and the full
  roundtrip stays recompile-free).

Each spawned decode host compiles a full (tiny) TIGER grid — the
subprocess tests share one module-scoped spawn where the scenario
allows it."""

import io
import signal
import socket as socket_mod
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from genrec_tpu.disagg import (
    DisaggFront,
    HandoffRefusedError,
    RemoteDecodeWorker,
    SocketTransport,
    spawn_decode_host,
)
from genrec_tpu.disagg.net import (
    BYE,
    HANDOFF,
    HELLO,
    recv_frame,
    send_frame,
)
from genrec_tpu.models.tiger import Tiger
from genrec_tpu.obs import prometheus_text
from genrec_tpu.obs.flight_recorder import get_flight_recorder
from genrec_tpu.serving import BucketLadder, PagedConfig, Request
from genrec_tpu.serving.heads import TigerGenerativeHead

K_CB = 8
CFG = dict(max_slots=2, page_size=8, pages_per_slot=4)
LADDER = ((1, 2), (8,))
_CHILD_ENV = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}


def _tiger_parts():
    valid = np.unique(
        np.random.default_rng(7).integers(0, K_CB, (20, 3)), axis=0)
    model = Tiger(embedding_dim=16, attn_dim=32, dropout=0.0, num_heads=4,
                  n_layers=2, num_item_embeddings=K_CB,
                  num_user_embeddings=20, sem_id_dim=3, max_pos=64)
    params = model.init(
        jax.random.key(0), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 6), jnp.int32), jnp.zeros((2, 6), jnp.int32),
        jnp.zeros((2, 3), jnp.int32), jnp.zeros((2, 3), jnp.int32),
        jnp.ones((2, 6), jnp.int32),
    )["params"]
    return model, valid, params


def make_decode_cfg():
    """Decode-host factory (runs in the CHILD process): the exact
    head/params/ladder the test fronts serve."""
    model, valid, params = _tiger_parts()
    return {
        "head": TigerGenerativeHead(model, valid, top_k=4, name="tiger"),
        "params": params,
        "ladder": BucketLadder(*LADDER),
        "paged_config": PagedConfig(**CFG),
        "params_step": 1,
    }


def make_skewed_cfg():
    """Same head, WRONG params step — the across-the-wire skew case."""
    cfg = make_decode_cfg()
    cfg["params_step"] = 99
    return cfg


def _front(model, valid, params, **kw):
    return DisaggFront(
        [TigerGenerativeHead(model, valid, top_k=4, name="tiger")], params,
        ladder=BucketLadder(*LADDER), max_batch=2, max_wait_ms=1.0,
        paged_config=PagedConfig(**CFG), params_step=1, **kw,
    )


def _reqs(n=6, seed=3):
    rng = np.random.default_rng(seed)
    valid_n = len(np.unique(
        np.random.default_rng(7).integers(0, K_CB, (20, 3)), axis=0))
    # Duplicated histories -> warm prefix-cache hits mixed with cold.
    lens = (3, 7, 5, 3, 7, 8, 1, 6)[:n]
    return [Request(head="tiger",
                    history=rng.integers(0, valid_n, ln),
                    user_id=int(rng.integers(0, 20)))
            for ln in lens]


# -- frame protocol ----------------------------------------------------------


def test_frame_roundtrip_and_insane_length():
    a, b = socket_mod.socketpair()
    try:
        payload = np.random.default_rng(0).bytes(1 << 12)
        n = send_frame(a, HANDOFF, {"seq": 7, "req": {"head": "t"}}, payload)
        ftype, meta, got = recv_frame(b)
        assert (ftype, meta["seq"], got) == (HANDOFF, 7, payload)
        assert n > len(payload)
        # A corrupt length prefix fails typed, never allocates blindly.
        a.sendall((1 << 62).to_bytes(8, "big"))
        with pytest.raises(ConnectionError, match="insane frame length"):
            recv_frame(b)
    finally:
        a.close()
        b.close()


# -- socket tier, cross-process ----------------------------------------------


@pytest.fixture(scope="module")
def serializing_baseline():
    """In-process serializing-front responses: the parity reference the
    socket tier must match bit-for-bit."""
    model, valid, params = _tiger_parts()
    front = _front(model, valid, params, transport="serializing").start()
    out = [f.result(120) for f in [front.submit(r) for r in _reqs()]]
    front.stop()
    return out


def test_socket_roundtrip_parity(serializing_baseline):
    """Cross-process == in-process serializing, bit-identical, under
    mixed warm/cold churn, zero steady-state recompiles both sides —
    plus the transport observability surface, in one spawn."""
    model, valid, params = _tiger_parts()
    proc, addr = spawn_decode_host(
        f"{__file__}:make_decode_cfg", worker_id="remote-d0",
        env=_CHILD_ENV,
    )
    try:
        front = _front(model, valid, params, transport="socket",
                       workers=[addr]).start()
        out = [f.result(120) for f in [front.submit(r) for r in _reqs()]]
        for b, t in zip(serializing_baseline, out):
            assert np.array_equal(np.asarray(b.sem_ids),
                                  np.asarray(t.sem_ids))
            np.testing.assert_allclose(np.asarray(b.scores),
                                       np.asarray(t.scores),
                                       rtol=0, atol=1e-6)
        st = front.stats()
        d = st["disagg"]
        assert d["transport"] == "socket"
        assert d["handoffs_admitted"] == len(out)
        assert d["handoffs_refused"] == 0
        assert d["transfer_bytes"] > 0
        # Per-transport wire section: frames/bytes/connects/receipts +
        # serialize-vs-network transfer_ms split.
        tr = d["transports"]["socket"]
        assert tr["frames_sent"] == len(out)
        assert tr["wire_bytes"] == d["transfer_bytes"]
        assert tr["serialize_ms"]["count"] == len(out)
        net = tr["network"]
        assert net["receipts"] == len(out)
        assert net["connects"] == 1
        assert net["peer_losses"] == 0
        assert net["in_flight_frames"] == 0
        assert net["network_ms"]["count"] == len(out)
        # Zero steady-state recompiles on BOTH sides — the peer's
        # counter read ACROSS the wire, fresh.
        assert st["recompilations"] == 0
        (dw,) = front._groups["tiger"].decode
        peer = dw.refresh_stats()
        assert peer["recompilations"] == 0
        assert peer["slots_active"] == 0
        # Counter/gauge typing pinned through the Prometheus exporter.
        text = prometheus_text(st)
        for line in (
            "# TYPE genrec_disagg_transports_socket_frames_sent counter",
            "# TYPE genrec_disagg_transports_socket_wire_bytes counter",
            "# TYPE genrec_disagg_transports_socket_network_receipts"
            " counter",
            "# TYPE genrec_disagg_transports_socket_network_connects"
            " counter",
            "# TYPE genrec_disagg_transports_socket_network_peer_losses"
            " counter",
            "# TYPE genrec_disagg_transports_socket_network"
            "_in_flight_frames gauge",
            "# TYPE genrec_disagg_transports_socket_network_network_ms_p50"
            " gauge",
        ):
            assert line in text, line
        front.stop()
        # Graceful drain: the host process exits clean, sockets closed.
        assert proc.wait(30) == 0
        assert dw.sockets_closed
    finally:
        proc.kill()


def test_socket_sigkill_mid_frame_at_most_once():
    """kill -9 the decode process with frames in flight: every accepted
    request resolves (re-submitted through the survivor, at most once),
    nothing hangs, and the flight recorder narrates the loss with the
    peer address."""
    model, valid, params = _tiger_parts()
    fr = get_flight_recorder()
    p1, a1 = spawn_decode_host(f"{__file__}:make_decode_cfg",
                               worker_id="remote-d1", env=_CHILD_ENV)
    p2, a2 = spawn_decode_host(f"{__file__}:make_decode_cfg",
                               worker_id="remote-d2", env=_CHILD_ENV)
    try:
        front = _front(model, valid, params, transport="socket",
                       workers=[a1, a2]).start()
        deaths_before = len(fr.events("disagg_worker_dead"))
        futs = [front.submit(r) for r in _reqs()]
        p1.send_signal(signal.SIGKILL)
        results, errors = [], []
        for f in futs:
            try:
                results.append(f.result(120))
            except Exception as e:  # noqa: BLE001 — typed check below
                errors.append(e)
        # Never a hang: every future resolved, one way or the other —
        # and anything that failed did so TYPED (the disagg family).
        from genrec_tpu.disagg import DisaggError

        assert len(results) + len(errors) == len(futs)
        assert all(isinstance(e, DisaggError) for e in errors), errors
        # The death is declared only after the reconnect budget exhausts
        # (the self-healing tier tries to get the peer back first) while
        # the stranded flights re-submit through the survivor right away
        # — so the futures above can resolve BEFORE the loss lands in
        # stats. Poll for it.
        deadline = time.monotonic() + 30.0
        while (front.stats()["disagg"]["decode_worker_deaths"] < 1
               and time.monotonic() < deadline):
            time.sleep(0.05)
        st = front.stats()
        assert st["disagg"]["decode_worker_deaths"] == 1
        deaths = fr.events("disagg_worker_dead")[deaths_before:]
        assert any(ev.get("peer") == a1 for ev in deaths), deaths
        tr = st["disagg"]["transports"]["socket"]
        assert tr["network"]["peer_losses"] == 1
        front.stop()
        assert p2.wait(30) == 0
    finally:
        p1.kill()
        p2.kill()


def test_socket_skew_refused_across_wire():
    """A decode host serving a different params step refuses the handoff
    typed at the front's proxy (handshake identity), before any page
    bytes cross the wire."""
    model, valid, params = _tiger_parts()
    proc, addr = spawn_decode_host(f"{__file__}:make_skewed_cfg",
                                   worker_id="remote-skew", env=_CHILD_ENV)
    try:
        front = _front(model, valid, params, transport="socket",
                       workers=[addr]).start()
        fut = front.submit(_reqs(1)[0])
        with pytest.raises(HandoffRefusedError, match="params step"):
            fut.result(60)
        st = front.stats()
        assert st["disagg"]["handoffs_refused"] == 1
        # Refused on the SEND side: no handoff frame ever left.
        assert st["disagg"]["transports"]["socket"]["network"][
            "receipts"] == 0
        front.stop()
    finally:
        proc.kill()
        proc.wait(10)


def test_remote_validate_is_typed_without_network():
    """The proxy's validate() against a fabricated handshake identity:
    every skew axis refuses typed (no process needed)."""
    from genrec_tpu.disagg.handoff import KVHandoff
    from genrec_tpu.serving.metrics import ServingMetrics

    w = RemoteDecodeWorker(
        "127.0.0.1:1", transport=SocketTransport(),
        metrics=ServingMetrics(), counters={},
        flight_recorder=get_flight_recorder().scoped("t"),
    )
    w.identity = {
        "head": "tiger", "layout": [2, 4, 8, "float32"],
        "kv_dtype": "float32", "params_step": 1, "catalog_version": "v1",
        "max_slots": 2, "page_size": 8, "pages_per_slot": 4,
    }

    def h(**kw):
        base = dict(head="tiger", n_tokens=3, bucket=(1, 8),
                    layout=(2, 4, 8, "float32"), kv_dtype="float32",
                    params_step=1, catalog_version="v1",
                    prefill_worker_id="p0", init=None)
        base.update(kw)
        return KVHandoff(**base)

    w.validate(h())  # matching identity admits
    for bad, pat in (
        (h(head="cobra"), "head"),
        (h(layout=(2, 4, 16, "float32")), "layout"),
        (h(kv_dtype="int8"), "dtype"),
        (h(params_step=2), "params step"),
        (h(catalog_version="v2"), "catalog"),
    ):
        with pytest.raises(HandoffRefusedError, match=pat):
            w.validate(bad)


# -- tensor-parallel serving operands ----------------------------------------


def _mesh4():
    from genrec_tpu.parallel import make_mesh

    return make_mesh({"model": 4}, devices=jax.devices()[:4])


def test_tp_item_topk_parity_and_row_sharding():
    """mesh= on the engine: retrieval results bit-identical to
    single-device, the item table GENUINELY row-sharded (pinned via the
    placed spec), zero recompiles."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from genrec_tpu.models.sasrec import SASRec
    from genrec_tpu.serving import ServingEngine
    from genrec_tpu.serving.heads import RetrievalHead

    n_items = 31  # (V+1) = 32 rows, divisible by the 4-way model axis
    model = SASRec(num_items=n_items, max_seq_len=8, embed_dim=16,
                   num_heads=2, num_blocks=1, ffn_dim=32, dropout=0.0)
    params = model.init(jax.random.key(0),
                        jnp.zeros((2, 8), jnp.int32))["params"]
    rng = np.random.default_rng(0)
    reqs = [Request(head="sasrec",
                    history=rng.integers(1, n_items + 1, n),
                    user_id=int(rng.integers(0, 20)))
            for n in (3, 7, 5, 8)]

    def run(mesh, quantized):
        eng = ServingEngine(
            [RetrievalHead("sasrec", model, top_k=5, quantized=quantized)],
            params, ladder=BucketLadder((1, 2), (8,)), max_batch=2,
            max_wait_ms=1.0, handle_signals=False, mesh=mesh,
        )
        eng.start()
        out = [f.result(120) for f in [eng.submit(r) for r in reqs]]
        stats = eng.stats()
        eng.stop()
        return out, stats, eng

    for quantized in (False, True):
        base, _, _ = run(None, quantized)
        tp, tstats, eng = run(_mesh4(), quantized)
        for b, t in zip(base, tp):
            assert np.array_equal(np.asarray(b.items), np.asarray(t.items))
            np.testing.assert_allclose(np.asarray(b.scores),
                                       np.asarray(t.scores),
                                       rtol=0, atol=1e-5)
        assert tstats["recompilations"] == 0
        if quantized:
            qt = eng._heads["sasrec"]._qtable
            assert qt.data.sharding.spec == P("model", None)
            assert qt.scale.sharding.spec == P("model")
        else:
            emb = eng._params["item_embedding"]
            assert isinstance(emb.sharding, NamedSharding)
            assert emb.sharding.spec == P("model", None)


@pytest.mark.parametrize("replay", [False, True],
                         ids=["cold", "warm_admit_and_reused_slot"])
def test_tp_paged_decode_parity_and_kv_sharding(replay):
    """mesh= on the paged TIGER engine: sem-ids bit-identical to
    single-device, the KV page bank sharded over the head axis (spec
    pin — JAX normalizes trailing Nones, so compare the prefix). With
    ``replay`` the first two requests come again once all four have
    answered: warm admits into slots already used (two slots), so the
    replicated slot table is written through `bind` on both paths as
    well as advanced by `step`."""
    from jax.sharding import NamedSharding

    from genrec_tpu.serving import ServingEngine

    model, valid, params = _tiger_parts()
    reqs = _reqs(4)

    def run(mesh):
        eng = ServingEngine(
            [TigerGenerativeHead(model, valid, top_k=4, name="tiger")],
            params, ladder=BucketLadder(*LADDER), max_batch=2,
            max_wait_ms=1.0, handle_signals=False,
            paged_config=PagedConfig(**CFG), params_step=1, mesh=mesh,
        )
        eng.start()
        out = [f.result(120) for f in [eng.submit(r) for r in reqs]]
        if replay:
            out += [f.result(120) for f in [eng.submit(r) for r in reqs[:2]]]
        stats = eng.stats()
        return out, stats, eng

    base, _, beng = run(None)
    beng.stop()
    tp, tstats, eng = run(_mesh4())
    for b, t in zip(base, tp):
        assert np.array_equal(np.asarray(b.sem_ids), np.asarray(t.sem_ids))
        np.testing.assert_allclose(np.asarray(b.scores),
                                   np.asarray(t.scores), rtol=0, atol=1e-5)
    if replay:
        for first, again in zip(tp[:2], tp[4:]):
            assert np.array_equal(np.asarray(first.sem_ids),
                                  np.asarray(again.sem_ids))
        # The default pool is the slots' own pages, so retained runs are
        # reclaimed under pressure: at least one replay still lands warm.
        assert tstats["prefix_cache"]["tiger"]["hits"] >= 1
        assert tstats["admits"] == 6  # through CFG's two slots
    assert tstats["recompilations"] == 0
    ksh = eng._runners["tiger"].pool.k_pools[0].sharding
    assert isinstance(ksh, NamedSharding)
    assert tuple(ksh.spec)[:3] == (None, None, "model"), ksh.spec
    table = eng._runners["tiger"].slots
    assert all(leaf.sharding.is_fully_replicated and
               len(leaf.sharding.device_set) == 4
               for leaf in table._state.values())
    eng.stop()


def test_tp_disagg_front_mesh_parity():
    """mesh= on the DisaggFront (in-process tiers): the shared page
    bank places onto the head axis and parity holds."""
    from jax.sharding import NamedSharding

    model, valid, params = _tiger_parts()
    reqs = _reqs(4)
    base_front = _front(model, valid, params,
                        transport="inprocess").start()
    base = [f.result(120) for f in [base_front.submit(r) for r in reqs]]
    base_front.stop()
    front = _front(model, valid, params, transport="inprocess",
                   mesh=_mesh4()).start()
    out = [f.result(120) for f in [front.submit(r) for r in reqs]]
    for b, t in zip(base, out):
        assert np.array_equal(np.asarray(b.sem_ids), np.asarray(t.sem_ids))
    bank = front._groups["tiger"].bank
    ksh = bank.k_pools[0].sharding
    assert isinstance(ksh, NamedSharding)
    assert tuple(ksh.spec)[:3] == (None, None, "model"), ksh.spec
    st = front.stats()
    assert st["recompilations"] == 0
    front.stop()


# -- serializing pad-skip (the satellite fix) --------------------------------


def test_admit_pad_skip_on_full_rung(monkeypatch):
    """A page run that already fills the compiled (pages_per_slot,)
    scatter rung crosses `SerializingTransport.admit` without an np.pad
    copy; a short run still pads. Pinned by counting np.pad calls
    through the transport module, plus a recompile-free roundtrip (the
    skip must not change the executable)."""
    import genrec_tpu.disagg.transport as tmod

    model, valid, params = _tiger_parts()
    head = TigerGenerativeHead(model, valid, top_k=4, name="tiger")
    # Size the pool so a MAX-bucket request's run is exactly the rung:
    # pages_per_slot = ceil(kv tokens at the largest history bucket /
    # page_size). A small-bucket request then lands under the rung.
    page = 8
    need = head.paged_kv_tokens(10**9, 8)
    cfg = PagedConfig(max_slots=2, page_size=page,
                      pages_per_slot=-(-need // page))
    calls = {"n": 0}
    real_pad = np.pad

    def counting_pad(*a, **kw):
        calls["n"] += 1
        return real_pad(*a, **kw)

    monkeypatch.setattr(tmod.np, "pad", counting_pad)
    front = DisaggFront(
        [head], params, ladder=BucketLadder((1, 2), (2, 8)),
        max_batch=2, max_wait_ms=1.0, paged_config=cfg, params_step=1,
        transport="serializing",
    ).start()
    # Largest history bucket -> full rung -> the pad must be SKIPPED.
    f1 = front.submit(Request(head="tiger",
                              history=np.arange(8) % len(valid),
                              user_id=1))
    f1.result(120)
    assert calls["n"] == 0, "full-rung run must skip the pad copy"
    # Small bucket -> short run -> still pads up to the rung.
    f2 = front.submit(Request(head="tiger", history=np.arange(2),
                              user_id=2))
    f2.result(120)
    st = front.stats()
    front.stop()
    assert calls["n"] > 0, "short run must pad to its rung"
    assert st["recompilations"] == 0
