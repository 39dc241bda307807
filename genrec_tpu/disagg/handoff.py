"""The typed KV-handoff artifact: what a prefill worker gives a decode
worker.

A `KVHandoff` is self-describing: beside the KV payload (a page run in a
shared in-process page bank, or serialized page content for the
host-roundtrip wire) it carries everything the receiving side needs to
VALIDATE the artifact before touching it — head name, page-pool layout,
token count, the donor's prefill bucket, the post-prefill slot-state
snapshot, and full provenance (params_step / catalog_version /
prefill_worker_id). Receipt validation is a typed refusal
(`HandoffRefusedError`), never silent mixing: a decode worker serving
params step N must not generate from KV a prefill worker encoded at step
M, and a catalog-version mismatch would beam-search against the wrong
trie.

The WIRE format (`pack_handoff`/`unpack_handoff`) is the cross-host
contract, pinned by ``WIRE_VERSION`` and tests/test_disagg.py: a JSON
header (provenance + layout + request lineage + array manifest)
followed by raw little-endian array bytes, framed inside one ``.npz``
container. The
serializing in-process transport round-trips every handoff through it,
so a future cross-host backend is a transport swap — the bytes already
mean the same thing on both sides.
"""

from __future__ import annotations

import dataclasses
import io
import json
from typing import Optional

import numpy as np

from genrec_tpu.obs.spans import TraceContext
from genrec_tpu.serving.types import ServingError

#: Bump when the pack/unpack layout changes; unpack refuses other
#: versions (typed) instead of misreading bytes.
#: v2: the header carries the request's lineage (``trace`` —
#: obs.TraceContext as {trace_id, parent_span_id, origin}), so the
#: decode side of a cross-host hop attaches its spans to the SAME
#: rooted trace the router/prefill side started (docs/OBSERVABILITY.md
#: "Request lineage"). v1 payloads are refused typed like any other
#: version skew.
#: v3: quantized KV (docs/SERVING.md "Quantized serving") — the header
#: carries ``kv_dtype``, and int8 payloads ship per-layer
#: ``k_scale{i}``/``v_scale{i}`` fp32 per-page-row scale planes beside
#: the int8 page content (the 2-4x wire shrink the quantized pool buys
#: travels the wire too). v2 payloads are refused typed.
#: v4: the page arrays ``k{i}``/``v{i}`` are recorded
#: ``(n_pages_used, page_size, n_heads * head_dim)``, the pool's own
#: shape, where v3 recorded ``(..., n_heads, head_dim)``. The BYTES are
#: the same row-major page rows; only the shape in each array's npy
#: header changed, and a v3 frame would not fit a v4 pool's compiled
#: scatter, so it is refused typed here instead.
WIRE_VERSION = 4


class DisaggError(ServingError):
    """Base class for disaggregated-serving errors."""


class HandoffRefusedError(DisaggError):
    """The receiving worker rejected a `KVHandoff` at validation time —
    wrong head, incompatible page layout, params/catalog version skew, or
    an unknown wire version. The refusal is the accounting: the request
    fails typed (and is counted/narrated) instead of decoding against
    mismatched state."""


class WorkerLostError(DisaggError):
    """The decode worker holding this request's KV died mid-flight and
    the typed at-most-once re-submit (back through a surviving
    prefill/decode pair — the KV died with the worker) could not complete
    it. Mirrors fleet.ReplicaLostError one level down: accepted work is
    never silently dropped."""


@dataclasses.dataclass
class KVHandoff:
    """One request's prefilled KV state, in flight between roles.

    ``layout`` is ``(n_layers, n_heads, head_dim, dtype_str)`` — the KV
    tensor geometry both sides must share (`layout_of`). Page SIZE is
    the transport's concern: the in-process tier shares one bank (views
    must match its geometry at construction), and the serializing tier
    re-checks the wire content's page size against the receiving pool
    at admit. ``init`` is the
    donor's post-prefill slot-state rows (host numpy, None/empty when the
    head's prefill leaves state zeroed — TIGER); the receiving worker
    patches bucket-dependent fields against the request's OWN bucket via
    ``head.paged_warm_state`` (the prefix-cache warm-admission semantics:
    a handoff is a warm admission whose donor ran on another worker).

    Payload is exactly one of:

    - ``pages`` — a page run in the SHARED page bank (in-process
      zero-copy transport; the handoff holds one allocator ref per page
      until it is admitted or dropped);
    - ``wire`` — the serialized page content (`pack_handoff` bytes, the
      host-roundtrip transport / future cross-host hop).
    """

    head: str
    n_tokens: int
    bucket: tuple[int, int]
    layout: tuple
    init: Optional[dict]
    params_step: Optional[int]
    catalog_version: Optional[str]
    prefill_worker_id: str
    warm: bool = False          # served from the prefill worker's prefix cache
    #: Request lineage (obs.TraceContext): rides the handoff by
    #: reference on the in-process tier and inside the wire header on
    #: the serializing tier, so the receiving decode worker's spans
    #: attach under the same trace the prefill side recorded into.
    trace: Optional[TraceContext] = None
    #: Page-pool storage dtype ("float32" | "int8") of the KV this
    #: handoff carries. Both sides must agree — a decode pool reading
    #: int8 rows as fp32 (or vice versa) would be silent garbage, so
    #: ``DecodeWorker.validate`` refuses skew typed.
    kv_dtype: str = "float32"
    pages: Optional[list] = None
    wire: Optional[bytes] = None

    @property
    def transfer_bytes(self) -> int:
        """Bytes that crossed the transport: the wire size, or 0 for the
        zero-copy in-process path (pages move by reference)."""
        return len(self.wire) if self.wire is not None else 0


def layout_of(head) -> tuple:
    """The handoff-validation layout tuple for one paged head + the
    page geometry it serves under (page_size from the pool config)."""
    n_layers, n_heads, head_dim, dtype = head.paged_layout()
    return (int(n_layers), int(n_heads), int(head_dim),
            np.dtype(dtype).name)


def pack_handoff(handoff: KVHandoff, k_content, v_content) -> bytes:
    """Serialize one handoff + its page content to the pinned wire
    format. ``k_content``/``v_content`` are per-layer host arrays shaped
    ``(n_pages_used, page_size, n_heads * head_dim)`` (the pool's own page
    shape, head-major features, as gathered) — exactly the pages
    the run covers, no padding (the receiving side re-pads to its own
    fixed scatter shape). For an int8 handoff (``handoff.kv_dtype ==
    "int8"``) each layer entry is a ``(data, scale)`` pair — int8 page
    rows plus their fp32 ``(n_pages_used, page_size)`` scale plane —
    and the scales ship as ``k_scale{i}``/``v_scale{i}`` arrays."""
    quantized = handoff.kv_dtype == "int8"
    header = {
        "wire_version": WIRE_VERSION,
        "head": handoff.head,
        "n_tokens": int(handoff.n_tokens),
        "bucket": list(handoff.bucket),
        "layout": list(handoff.layout),
        "kv_dtype": handoff.kv_dtype,
        "params_step": handoff.params_step,
        "catalog_version": handoff.catalog_version,
        "prefill_worker_id": handoff.prefill_worker_id,
        "warm": bool(handoff.warm),
        "trace": (handoff.trace.to_header()
                  if handoff.trace is not None else None),
        "n_layers": len(k_content),
        "state_keys": sorted(handoff.init) if handoff.init else [],
    }
    arrays = {"__header__": np.frombuffer(
        json.dumps(header).encode("utf-8"), np.uint8)}
    for i, (k, v) in enumerate(zip(k_content, v_content)):
        if quantized:
            arrays[f"k{i}"] = np.ascontiguousarray(k[0])
            arrays[f"k_scale{i}"] = np.ascontiguousarray(k[1])
            arrays[f"v{i}"] = np.ascontiguousarray(v[0])
            arrays[f"v_scale{i}"] = np.ascontiguousarray(v[1])
        else:
            arrays[f"k{i}"] = np.ascontiguousarray(k)
            arrays[f"v{i}"] = np.ascontiguousarray(v)
    for key in header["state_keys"]:
        arrays[f"s_{key}"] = np.ascontiguousarray(handoff.init[key])
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    return buf.getvalue()


def unpack_handoff(data: bytes) -> tuple[KVHandoff, tuple, tuple]:
    """Wire bytes -> (handoff, k_content, v_content). Refuses unknown
    wire versions typed — misreading a future layout as this one would
    be silent corruption, the one failure mode the format exists to
    prevent."""
    with np.load(io.BytesIO(data)) as z:
        header = json.loads(bytes(z["__header__"]).decode("utf-8"))
        if header.get("wire_version") != WIRE_VERSION:
            raise HandoffRefusedError(
                f"handoff wire version {header.get('wire_version')!r} != "
                f"supported {WIRE_VERSION}; refusing to decode bytes under "
                "the wrong layout"
            )
        n_layers = int(header["n_layers"])
        kv_dtype = header.get("kv_dtype", "float32")
        if kv_dtype == "int8":
            k_content = tuple(
                (z[f"k{i}"], z[f"k_scale{i}"]) for i in range(n_layers)
            )
            v_content = tuple(
                (z[f"v{i}"], z[f"v_scale{i}"]) for i in range(n_layers)
            )
        else:
            k_content = tuple(z[f"k{i}"] for i in range(n_layers))
            v_content = tuple(z[f"v{i}"] for i in range(n_layers))
        init = {key: z[f"s_{key}"] for key in header["state_keys"]} or None
    handoff = KVHandoff(
        head=header["head"],
        n_tokens=int(header["n_tokens"]),
        bucket=tuple(header["bucket"]),
        layout=tuple(header["layout"]),
        init=init,
        params_step=header["params_step"],
        catalog_version=header["catalog_version"],
        prefill_worker_id=header["prefill_worker_id"],
        warm=bool(header["warm"]),
        trace=TraceContext.from_header(header.get("trace")),
        kv_dtype=kv_dtype,
        wire=data,
    )
    return handoff, k_content, v_content
