"""config/lcrec/keye_vl2_30b_a3b.gin reaches `QwenConfig` through
`lcrec_trainer.train()`, and the combinations that are not wired are refused."""

import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIN = os.path.join(REPO, "config", "lcrec", "keye_vl2_30b_a3b.gin")

#: toy widths over the gin's (every mechanism stays on)
TOY = dict(hidden_size=32, intermediate_size=64, num_heads=4, num_kv_heads=2,
           head_dim=8, n_layers=2, sparse_topk=16, indexer_heads=2,
           indexer_head_dim=8, sparse_chunk=32, num_experts=8,
           num_experts_per_tok=2, moe_intermediate_size=16, moe_experts_held=4,
           codebook_size=8, num_codebooks=3, vocab_rows=0, max_text_len=96,
           batch_size=8, eval_batch_size=8, amp=False, do_eval=False)


DENSE_MLP = dict(num_experts=0, moe_experts_held=None, moe_dropless=False)


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
    import json

    b = gin.get_bindings("train")
    with open(os.path.join(REPO, "benchmark", "configs", "keye_vl2_30b_a3b",
                           "config.json")) as f:
        cfg = json.load(f)
    sa = cfg["sa_config"]
    assert (b["hidden_size"], b["num_heads"], b["num_kv_heads"], b["head_dim"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"])
    assert (b["sparse_topk"], b["indexer_heads"], b["indexer_head_dim"],
            b["sparse_chunk"]) == (sa["topk"], sa["indexer_num_heads"],
                                   sa["indexer_head_dim"], sa["q_chunk_size"])
    assert (b["num_experts"], b["moe_experts_held"], b["num_experts_per_tok"],
            b["moe_intermediate_size"]) == (
        cfg["num_experts_published"], cfg["num_experts"],
        cfg["num_experts_per_tok"], cfg["moe_intermediate_size"])
    assert b["n_layers"] == cfg["num_hidden_layers"]
    assert b["vocab_rows"] == cfg["vocab_size"]
    assert b["rope_theta"] == cfg["rope_theta"] and b["attention_bias"] is False
    assert b["router_aux_coef"] == cfg["router_aux_coef"]


def test_train_runs_two_steps_of_the_gin_at_toy_widths(gin, tmp_path, monkeypatch):
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

    monkeypatch.setattr(lcrec_trainer, "make_sft_step", spying)
    lcrec_trainer.train(**TOY, epochs=1, max_train_samples=16,
                        save_dir_root=str(tmp_path / "keye"))
    assert len(seen["metrics"]) == 2
    for m in seen["metrics"]:
        assert m["loss"] == m["loss"] and m["real_tokens"] > 0
        assert 0 < m["expert_picks_here_share"] < 100.0  # 4 of 8 experts held
        assert m["expert_load_max_over_mean"] >= 1.0
        assert 0 < m["sparse_keys_kept_share"] <= 100.0


@pytest.mark.parametrize("over, match", [
    # (the expert layer off: its own refusal of sp/pp comes first)
    (dict(sequence_parallel=2, **DENSE_MLP), "data-parallel runs only"),
    (dict(pipeline_parallel=2, **DENSE_MLP), "data-parallel runs only"),
    (dict(use_lora=True, lora_targets=("q_proj", "gate_proj")), "LoRA on the experts"),
    (dict(moe_dropless=False), "moe_dropless=True"),
    (dict(expert_parallel=2), "expert_parallel must stay 1"),
])
def test_unwired_combinations_are_refused(gin, over, match):
    from genrec_tpu.trainers import lcrec_trainer

    with pytest.raises(ValueError, match=match):
        lcrec_trainer.train(**{**TOY, **over})
