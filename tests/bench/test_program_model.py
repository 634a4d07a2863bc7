"""`common.program_model`: every cell's model is the program's own
architecture from `repro.configs`, at the sizes its configuration file
gives. Today's cells get exactly the model and split they were built
with when each system spelled its `ModelConfig` out; a configuration
file's published keys agree with the registry wherever `reduced` does
not say otherwise; and a DeepSeek-V3-shaped file builds MLA and MoE with
a dense prefix through data alone."""
import copy
import dataclasses

import jax
import pytest

from bench import common
from repro.configs import get_config
from repro.core.split import SplitConfig, SplitModel
from repro.models.config import AttentionConfig, ModelConfig

VIT = ModelConfig(
    name="vit-base", arch_type="vit", n_layers=12, d_model=768, d_ff=3072,
    vocab_size=1, layer_pattern=("attn",),
    attention=AttentionConfig(n_heads=12, n_kv_heads=12, head_dim=64,
                              use_rope=False),
    mlp_activation="gelu", norm="layernorm", num_classes=100,
    max_seq_len=512)
QWEN = ModelConfig(
    name="qwen2.5-14b-3L", arch_type="dense", n_layers=3, d_model=5120,
    d_ff=13824, vocab_size=152064, layer_pattern=("attn",),
    attention=AttentionConfig(n_heads=40, n_kv_heads=8, head_dim=128,
                              rope_theta=1e6, qkv_bias=True),
    mlp_activation="silu_glu", norm="rmsnorm", tie_embeddings=False,
    max_seq_len=512)
# what each cell's builder passes, and the model and split it gets
BUILT = {
    "vitb-fed-u10": ({"local_epochs": 10}, VIT, SplitConfig(
        head_cycles=1, tail_cycles=1, prompt_len=16, prune_gamma=0.5,
        local_epochs=10)),
    "vitb-fed-u1-secure": ({"local_epochs": 1}, VIT, SplitConfig(
        head_cycles=1, tail_cycles=1, prompt_len=16, prune_gamma=0.5,
        local_epochs=1)),
    "q14b-decode-closed": ({}, QWEN, SplitConfig(
        head_cycles=1, tail_cycles=1, prompt_len=4)),
}

# DeepSeek-V3's block at a size the CPU holds: latent attention, 8 routed
# experts top-2 and one shared, 1 dense layer then 4 MoE layers
DEEPSEEK = {
    "name": "deepseek-v3-tiny", "system": "serving",
    "hidden_size": 64, "num_hidden_layers": 5, "intermediate_size": 128,
    "vocab_size": 256, "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 24, "rope_theta": 10000.0, "tie_word_embeddings": False,
    "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "n_shared_experts": 1, "first_k_dense_replace": 1,
    "split": {"head_layers": 2, "body_layers": 2, "tail_layers": 1,
              "prompt_len": 4},
    "program": {"arch": "deepseek-v3-671b", "fields": {"max_seq_len": 64}},
}


@pytest.mark.parametrize("cell", sorted(BUILT))
def test_program_model_is_the_one_each_cell_was_built_with(cell):
    """Field for field, but `source`, which no program path reads."""
    kw, model, split = BUILT[cell]
    got, got_split = common.program_model(common.resolve(cell)["config"],
                                          **kw)
    assert dataclasses.replace(got, source="") == model
    assert got_split == split


@pytest.mark.parametrize("config", [c["name"] for c in
                                    common.benchmark()["configs"]])
def test_published_keys_are_the_registrys(config):
    """Every key the table maps, and `reduced` does not list, is the
    registry architecture's own value."""
    entry = {c["name"]: c for c in common.benchmark()["configs"]}[config]
    data = common.load_json(common.ROOT / entry["file"])
    base = get_config(data["program"]["arch"])
    mapped = [k for k in common.PROGRAM_KEYS if k in data]
    assert mapped
    for key in mapped:
        if key in entry["reduced"]:
            continue
        value = base
        for name in common.PROGRAM_KEYS[key].split("."):
            value = getattr(value, name)
        assert data[key] == value, key


def test_deepseek_shaped_config_builds_from_data_alone():
    cfg, split = common.program_model(DEEPSEEK)
    assert cfg.layer_pattern == ("moe",) and cfg.n_dense_layers == 1
    assert cfg.n_layers == 5 and cfg.name == "deepseek-v3-671b-5L"
    assert cfg.attention.mla == dataclasses.replace(
        cfg.attention.mla, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
    assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.d_ff_expert,
            cfg.moe.n_shared_experts) == (8, 2, 32, 1)
    assert (split.head_cycles, split.tail_cycles) == (1, 1)
    model = SplitModel(cfg, split)
    assert (model.n_head_layers, model.n_body_layers,
            model.n_tail_layers) == (2, 2, 1)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    head = shapes["head"]
    assert jax.tree.leaves(head["dense_stack"])[0].shape[0] == 1
    for seg, cycles in (("head", 1), ("body", 2), ("tail", 1)):
        block = shapes[seg]["stack"]["pos0"]
        assert block["moe"]["experts"]["up"].shape == (cycles, 8, 64, 32)
        assert block["moe"]["shared"]["up"]["w"].shape[-1] == 32
        assert block["attn"]["kv_a"]["w"].shape == (cycles, 64, 16 + 8)
    assert "moe" not in head["dense_stack"]["pos0"]


@pytest.mark.parametrize("split", [
    {"head_layers": 0, "body_layers": 3, "tail_layers": 2},   # no dense
    {"head_layers": 2, "body_layers": 1, "tail_layers": 1},   # 4 of 5
    {"head_layers": 2, "body_layers": 3, "tail_layers": 1},   # 6 of 5
])
def test_split_that_the_model_cannot_give_is_refused(split):
    config = copy.deepcopy(DEEPSEEK)
    config["split"].update(split)
    with pytest.raises(ValueError):
        common.program_model(config)


def test_split_of_part_of_a_cycle_is_refused():
    """gemma2-9b's cycle is a local and a global layer: a head of one
    layer would cut it."""
    config = {"hidden_size": 64, "num_hidden_layers": 6,
              "program": {"arch": "gemma2-9b"},
              "split": {"head_layers": 1, "body_layers": 3,
                        "tail_layers": 2, "prompt_len": 4}}
    with pytest.raises(ValueError, match="whole cycles"):
        common.program_model(config)
    config["split"].update(head_layers=2, body_layers=2)
    cfg, split = common.program_model(config)
    assert (split.head_cycles, split.tail_cycles) == (1, 1)


def test_key_the_architecture_has_no_config_for_is_refused():
    """qwen2.5-14b has no latent attention to set a rank in."""
    config = dict(common.resolve("q14b-decode-closed")["config"],
                  kv_lora_rank=512)
    with pytest.raises(ValueError, match="mla"):
        common.program_model(config)
