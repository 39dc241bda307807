"""Adapter: builds the system under test for ``solar_open2_250b`` from a seed.

The one file of the configuration that imports the program. It builds what a
deployment of the LCRec recommender would: the cut Solar-Open2 language model
(``QwenLM`` over ``QwenConfig``: NoPE gated GQA, KDA with the doubled write
strength, sigmoid-routed experts of which a share is held), an
``LCRecGenerativeHead`` over a catalog of semantic ids, and a started
``ServingEngine(paged=True)``: the prompt's K and V of the full-attention
layer in the page pool, every KDA layer's state in the slot table, retained
prefixes with their state snapshots in the prefix index. Weights and the
catalog are made here from the seed, never by the program's initialisers, so
the plain reference gets the same tree.

The imports below are at the top on purpose: a checkout of the program that
lacks the paged LCRec path fails here, at once, before anything is built.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from genrec_tpu.models.backbones.qwen import QwenConfig, QwenLM
from genrec_tpu.models.lcrec import lcrec_paged_decode_step  # noqa: F401  (fail at once without it)


def model_config(cfg: dict, share: tuple[int, int] | None = None) -> QwenConfig:
    """The program's ``QwenConfig`` of the configuration as run. ``share``
    (first expert, experts held) overrides the file's (for the test that
    ties the share to the model)."""
    lac = cfg["linear_attn_config"]
    n = cfg["num_hidden_layers"]
    first, held = share if share is not None else (
        int(cfg.get("first_expert", 0)), int(cfg["n_routed_experts"]))
    return QwenConfig(
        vocab_size=cfg["vocab_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        hidden_size=cfg["hidden_size"], intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=n, num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        attention_bias=False, rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        use_rope=cfg["use_rope"], attn_output_gate=cfg["use_gqa_gate"],
        kda_neg_eigval=cfg["kda_allow_neg_eigval"],
        sparse_chunk=cfg["assumed"]["attention_query_tile"],
        # the published list is 0-based and names the FULL layers; the
        # program's is 1-based and names the KDA ones
        kda_layers=tuple(i + 1 for i in range(n) if i not in cfg["gqa_layers"]),
        mla_layers=(), kda_heads=lac["num_heads"], kda_head_dim=lac["head_dim"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_experts=cfg["n_routed_experts_published"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        norm_topk_prob=cfg["norm_topk_prob"], moe_capacity_factor=None,
        moe_first_expert=first, moe_experts_held=held, moe_scoring="sigmoid",
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        n_shared_experts=cfg["n_shared_experts"], router_aux_coef=0.0,
    )


def _model(cfg: dict, share=None) -> QwenLM:
    return QwenLM(model_config(cfg, share), dtype=jnp.dtype(cfg["compute_dtype"]))


def param_shapes(cfg: dict, share=None):
    model = _model(cfg, share)
    return jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 4), jnp.int32))["params"],
        jax.random.key(0))


_FLOAT32_LEAVES = ("A_log", "dt_bias")  # the configuration states them float32


@functools.lru_cache(maxsize=None)
def _leaf_fn(kind: str, shape: tuple, dtype: str):
    """One leaf from a key, made and rounded in one program (a float32
    staging copy of a whole expert stack is never kept)."""
    def make(k):
        if kind == "ones":
            v = jnp.ones(shape, jnp.float32)
        elif kind == "zeros":
            v = jnp.zeros(shape, jnp.float32)
        elif kind == "A_log":
            v = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif kind == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
            v = dt + jnp.log(-jnp.expm1(-dt))
        elif kind == "unit":
            v = jax.random.normal(k, shape, jnp.float32)
        elif kind == "head":
            v = 0.02 * jax.random.normal(k, shape, jnp.float32)
        else:  # (in, out) kernels, (taps, channels) filters, (experts, in, out) stacks
            v = jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[-2])
        return v.astype(dtype)
    return jax.jit(make)


def _leaf_kind(name: str) -> str:
    if name in ("weight", "scale"):
        return "ones"
    if name in ("bias", "selection_bias"):
        return "zeros"
    if name in _FLOAT32_LEAVES:
        return name
    return {"embed_tokens": "unit", "lm_head": "head"}.get(name, "fan_in")


def make_params(cfg: dict, seed: int, share=None, dtype: str | None = None):
    """The whole tree on the device, a leaf at a time, in ``param_dtype``
    (bfloat16; ``A_log`` and ``dt_bias`` float32). By the model's own
    distributions (``assumed.init``). With a ``share`` other than the file's,
    the expert stacks are drawn at the published count and sliced, so that
    every share of one seed is a slice of the same layer."""
    dtype = dtype or cfg["param_dtype"]
    shapes = param_shapes(cfg, share)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    key = jax.random.key(seed % (2**31 - 1))
    first, held = share if share is not None else (
        int(cfg.get("first_expert", 0)), int(cfg["n_routed_experts"]))
    out = []
    for i, (path, sd) in enumerate(leaves):
        name = str(path[-1].key)
        k = jax.random.fold_in(key, i)
        leaf_dtype = "float32" if name in _FLOAT32_LEAVES else dtype
        stack = (len(sd.shape) == 3 and sd.shape[0] == held
                 and str(path[-2].key) == "moe")
        if stack and share is not None:
            full = (cfg["n_routed_experts_published"],) + tuple(sd.shape[1:])
            v = _leaf_fn(_leaf_kind(name), full, leaf_dtype)(k)[first:first + held]
        else:
            v = _leaf_fn(_leaf_kind(name), tuple(sd.shape), leaf_dtype)(k)
        out.append(v)
    return jax.tree_util.tree_unflatten(treedef, out)


def make_catalog(cfg: dict, seed: int) -> np.ndarray:
    """``catalog_items`` unique sem-id tuples drawn from the seed."""
    rng = np.random.default_rng([seed, 11])
    cb, depth, n = cfg["codebook_size"], cfg["sem_id_dim"], cfg["assumed"]["catalog_items"]
    codes = rng.choice(cb ** depth, size=n, replace=False)
    out = np.zeros((n, depth), np.int64)
    for d in reversed(range(depth)):
        out[:, d] = codes % cb
        codes = codes // cb
    return out


def prompt_tokens(cfg: dict, catalog: np.ndarray, history) -> np.ndarray:
    """A history's prompt as the head lays it out: the newest ``max_items``
    items, oldest first, ``sem_id_dim`` codebook tokens each."""
    h = np.asarray(history, np.int64)[-cfg["max_items"]:]
    offs = cfg["base_vocab"] + np.arange(cfg["sem_id_dim"]) * cfg["codebook_size"]
    return (catalog[h] + offs).reshape(-1).astype(np.int32)


def make_head(cfg: dict, catalog: np.ndarray, share=None):
    from genrec_tpu.serving.heads import LCRecGenerativeHead

    return LCRecGenerativeHead(
        _model(cfg, share), cfg["base_vocab"], cfg["sem_id_dim"],
        cfg["codebook_size"], item_sem_ids=catalog,
        top_k=cfg["assumed"]["beam"], name="lcrec")


def paged_config(cfg: dict, head):
    from genrec_tpu.serving import PagedConfig

    a = cfg["assumed"]["serve"]
    kv_tokens = head.paged_kv_tokens(cfg["max_items"], cfg["max_items"])
    return PagedConfig(
        max_slots=a["max_slots"], page_size=a["page_size"],
        pages_per_slot=-(-kv_tokens // a["page_size"]), num_pages=a["num_pages"])


def build_serve(cfg: dict, traffic: dict, seed: int, tracer=None):
    """A started, warmed engine with one paged LCRec head."""
    from genrec_tpu.serving import BucketLadder, ServingEngine

    a = cfg["assumed"]["serve"]
    catalog = make_catalog(cfg, seed)
    params = make_params(cfg, seed)
    head = make_head(cfg, catalog)
    engine = ServingEngine(
        [head], params, paged=True, paged_config=paged_config(cfg, head),
        ladder=BucketLadder(tuple(a["batch_buckets"]), tuple(a["history_buckets"])),
        max_batch=a["max_batch"], max_wait_ms=a["max_wait_ms"],
        prefix_cache=True, prefix_cache_entries=a["prefix_cache_entries"],
        handle_signals=False, tracer=tracer,
    )
    engine.start()
    _keep_end_states_at_stop(engine, head.name)
    return engine, head, params, catalog


#: history key -> the KDA layers' end states (host rows, in layer order) the
#: prefix index of the engine built last retained when it was stopped.
_RETAINED: dict = {}


def _keep_end_states_at_stop(engine, head_name: str) -> None:
    """The check compares the retained snapshots' end states with the
    reference's, but a run stops the engine before it judges, and a drain
    empties the prefix index: so `stop` first notes each entry's ``kda_s0_*``
    rows (references to the host arrays the entries hold, no copy)."""
    index = engine._runners[head_name].prefix
    stop = engine.stop

    def stop_and_keep(*args, **kwargs):
        _RETAINED.clear()
        for entry in index.entries():
            keys = sorted((k for k in entry.init or () if k.startswith("kda_s0_")),
                          key=lambda k: int(k.rsplit("_", 1)[1]))
            _RETAINED[entry.key] = [entry.init[k] for k in keys]
        return stop(*args, **kwargs)

    engine.stop = stop_and_keep


def retained_states(cfg: dict, history):
    """(KDA layers, H, K, V): the end states the stopped engine retained for
    the request of this history, or None if it retained none."""
    h = np.asarray(history, np.int64)[-cfg["max_items"]:]
    rows = _RETAINED.get(tuple(int(x) for x in h))
    return np.stack(rows) if rows else None


def make_request(head_name: str, user_id: int, history):
    from genrec_tpu.serving import Request

    return Request(head=head_name, user_id=int(user_id),
                   history=np.asarray(history, np.int64))
