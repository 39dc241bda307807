"""config/lcrec/kimi_linear_48b_a3b.gin reaches `QwenConfig` through
`lcrec_trainer.train()`, trains and evaluates through the two-kind cache at toy
widths, and the combinations that are not wired are refused."""

import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIN = os.path.join(REPO, "config", "lcrec", "kimi_linear_48b_a3b.gin")

#: toy widths over the gin's (every mechanism and the layer order stay on)
TOY = dict(hidden_size=32, intermediate_size=64, num_heads=4, num_kv_heads=4,
           head_dim=8, kda_heads=2, kda_head_dim=8, kv_lora_rank=16,
           qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, sparse_chunk=32,
           num_experts=8, num_experts_per_tok=2, moe_intermediate_size=16,
           moe_experts_held=4, codebook_size=8, num_codebooks=3, vocab_rows=0,
           max_text_len=96, batch_size=8, eval_batch_size=8, amp=False)


DENSE_MLP = dict(num_experts=0, moe_experts_held=None, moe_dropless=False,
                 n_shared_experts=0)


@pytest.fixture
def gin():
    from genrec_tpu import configlib
    from genrec_tpu.configlib.parser import clear_macros

    configlib.clear_bindings()
    clear_macros()
    configlib.parse_config([GIN])
    yield configlib
    configlib.clear_bindings()
    clear_macros()


def test_gin_states_the_published_widths_and_the_cut(gin):
    b = gin.get_bindings("train")
    with open(os.path.join(REPO, "benchmark", "configs", "kimi_linear_48b_a3b",
                           "config.json")) as f:
        cfg = json.load(f)
    lac, n = cfg["linear_attn_config"], cfg["num_hidden_layers"]
    assert (b["hidden_size"], b["intermediate_size"], b["num_heads"], b["head_dim"],
            b["rms_norm_eps"]) == (cfg["hidden_size"], cfg["intermediate_size"],
                                   cfg["num_attention_heads"], cfg["head_dim"],
                                   cfg["rms_norm_eps"])
    assert tuple(b["kda_layers"]) == tuple(i for i in lac["kda_layers"] if i <= n)
    assert tuple(b["mla_layers"]) == tuple(i for i in lac["full_attn_layers"] if i <= n)
    from genrec_tpu.models.backbones import kda

    assert (b["kda_heads"], b["kda_head_dim"], kda._CONV_KERNEL) == (
        lac["num_heads"], lac["head_dim"], lac["short_conv_kernel_size"])
    assert (b["kv_lora_rank"], b["qk_nope_head_dim"], b["qk_rope_head_dim"],
            b["v_head_dim"]) == (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                                 cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    assert (b["num_experts"], b["moe_experts_held"], b["num_experts_per_tok"],
            b["moe_intermediate_size"], b["n_shared_experts"],
            b["first_k_dense_replace"]) == (
        cfg["num_experts_published"], cfg["num_experts"],
        cfg["num_experts_per_token"], cfg["moe_intermediate_size"],
        cfg["num_shared_experts"], cfg["first_k_dense_replace"])
    assert (b["moe_scoring"], b["routed_scaling_factor"], b["norm_topk_prob"]) == (
        cfg["moe_router_activation_func"], cfg["routed_scaling_factor"],
        cfg["moe_renormalize"])
    assert b["n_layers"] == n and b["vocab_rows"] == cfg["vocab_size"]
    assert b["router_aux_coef"] == cfg["router_aux_coef"] == 0.0


def test_train_runs_two_steps_and_evaluates_through_the_two_kind_cache(
        gin, tmp_path, monkeypatch):
    from genrec_tpu.models.backbones.qwen import QwenLM
    from genrec_tpu.trainers import lcrec_trainer

    seen = {}
    real = lcrec_trainer.make_sft_step

    def spying(loss, optimizer):
        step = real(loss, optimizer)

        def spied(state, batch):
            state, m = step(state, batch)
            seen.setdefault("metrics", []).append({k: float(v) for k, v in m.items()})
            return state, m

        return spied

    real_cache = QwenLM.init_cache

    def spying_cache(self, batch_size, max_len):
        caches = real_cache(self, batch_size, max_len)
        seen["cache_keys"] = [sorted(c) for c in caches]
        return caches

    monkeypatch.setattr(lcrec_trainer, "make_sft_step", spying)
    monkeypatch.setattr(QwenLM, "init_cache", spying_cache)
    valid, test = lcrec_trainer.train(
        **TOY, epochs=1, max_train_samples=16, max_eval_samples=4,
        eval_every_epoch=1, save_dir_root=str(tmp_path / "kimi"))
    assert len(seen["metrics"]) == 2
    for m in seen["metrics"]:
        assert m["loss"] == m["loss"] and m["real_tokens"] > 0
        assert 0 < m["expert_picks_here_share"] < 100.0  # 4 of 8 experts held
        assert m["expert_load_max_over_mean"] >= 1.0
        assert m["expert_pairs_per_held_expert"] > 0
        assert 0 < m["kda_state_keep_share"] < 100.0
    kda, mla = ["conv", "idx", "s"], ["idx", "latent"]
    assert seen["cache_keys"] == [kda, kda, kda, mla, kda]
    for metrics in (valid, test):
        assert 0.0 <= metrics["Recall@10"] <= 1.0


@pytest.mark.parametrize("over, match", [
    # (the expert layer off: its own refusal of sp/pp comes first)
    (dict(sequence_parallel=2, **DENSE_MLP), "data-parallel runs only"),
    (dict(pipeline_parallel=5, **DENSE_MLP), "data-parallel runs only"),
    (dict(tensor_parallel=2, expert_parallel=2, moe_experts_held=None),
     "data-parallel runs only"),
    (dict(use_lora=True), "LoRA on a backbone with kda_layers"),
    (dict(expert_parallel=2, moe_experts_held=None), "n_shared_experts with expert_parallel"),
    (dict(moe_dropless=False), "moe_dropless=True"),
])
def test_unwired_combinations_are_refused(gin, over, match):
    from genrec_tpu.trainers import lcrec_trainer

    with pytest.raises(ValueError, match=match):
        lcrec_trainer.train(**{**TOY, **over})
