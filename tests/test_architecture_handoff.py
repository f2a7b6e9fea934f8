"""``run_lm_trial`` handed an architecture whole (models/architecture.py): the
keys read, what is refused, and that the four-size decoder is what it was."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from katib_tpu.models import architecture
from katib_tpu.models.transformer import (
    RotaryConfig, TransformerConfig, TransformerLM, rotary_embed)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _published(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_the_sparse_cell_s_file_reads_into_the_layers_it_states():
    arch = _published("laguna-xs2-d5e32")
    cfg = architecture.architecture_config(arch, 8192)
    assert (cfg.embed_dim, cfg.head_dim, cfg.num_kv_heads, cfg.window, cfg.mlp_hidden) == (2048, 128, 8, 512, 8192)
    assert [(l.attention, l.num_heads, l.mlp) for l in cfg.layers] == [
        ("full", 48, "dense"), ("sliding", 64, "routed"), ("sliding", 64, "routed"),
        ("sliding", 64, "routed"), ("full", 48, "routed")]
    routed = cfg.routed
    assert (routed.router_width, routed.held, routed.first_expert, routed.experts_per_token) == (256, 32, 0, 8)
    assert (routed.hidden, routed.shared_hidden, routed.routed_scale) == (512, 512, 2.5)
    assert not cfg.tied_head and cfg.attention_gate and cfg.vocab_size == 12544
    full, sliding = cfg.rotary_of("full"), cfg.rotary_of("sliding")
    assert (full.theta, full.fraction, full.yarn.factor, full.yarn.original_positions) == (5e5, 0.5, 64.0, 4096)
    assert sliding == RotaryConfig(theta=1e4, fraction=1.0, yarn=None)
    path = os.path.join(ROOT, "benchmarks", "configs", "laguna-xs2-d5e32.json")
    assert architecture.load_architecture(path) == arch


@pytest.mark.parametrize("change,message", [
    ({"kv_lora_rank": 512}, "kv_lora_rank"),
    ({"attention_bias": True}, "attention_bias"),
    ({"moe_apply_router_weight_on_input": True}, "moe_apply_router_weight_on_input"),
    ({"layer_types": ["linear_attention"] * 5}, "linear_attention"),
    ({"mlp_layer_types": ["dense"] * 4}, "lists 4 layers of 5"),
    ({"num_key_value_heads": 7}, "no multiple"),
    ({"sliding_window": None}, "no sliding_window"),
    ({"gating": "elementwise"}, "gating"),
    ({"expert_share": {"first": 250, "of": 256}}, "expert_share"),
    ({"rope_parameters": {"full_attention": {"rope_type": "llama3", "rope_theta": 1e4}}}, "llama3"),
    ({"rope_parameters": {"full_attention": {"rope_theta": 1e4}}}, "no group for"),
])
def test_what_is_refused_is_refused_by_name(change, message):
    arch = dict(_published("laguna-xs2-d5e32"), **change)
    with pytest.raises(architecture.ArchitectureRefused, match=message):
        architecture.architecture_config(arch, 8192)


def test_more_positions_than_published_are_refused():
    with pytest.raises(architecture.ArchitectureRefused, match="positions"):
        architecture.architecture_config(_published("tiny-sparse"), 8192)


def test_run_lm_trial_takes_the_file_s_path_and_not_sizes_beside_it(tmp_path, capsys):
    from katib_tpu.parallel.train import run_lm_trial

    path = os.path.join(ROOT, "benchmarks", "configs", "tiny-sparse.json")
    run_lm_trial({"architecture": path, "seq_len": "32", "batch_size": "2", "num_steps": "2",
                  "learning_rate": "1e-3"})
    assert "loss=" in capsys.readouterr().out
    with pytest.raises(ValueError, match="embed_dim"):
        run_lm_trial({"architecture": path, "embed_dim": "64"})


def test_the_four_size_decoder_keeps_its_parameters_and_its_numbers():
    """Names, shapes and initial values as before the layer kinds came; the
    rotary defaults are the literals they replaced."""
    cfg = TransformerConfig(vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, max_seq_len=16,
                            dtype=jnp.float32)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((2, 16), jnp.int32))["params"]
    flat = {"/".join(k.key for k in path): v.shape for path, v in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert flat == {
        "embed": (64, 32), "ln_f/scale": (32,),
        **{f"block{i}/{name}": shape for i in range(2) for name, shape in {
            "ln1/scale": (32,), "ln2/scale": (32,), "attn/qkv/kernel": (32, 3, 4, 8),
            "attn/out/kernel": (4, 8, 32), "mlp/up/kernel": (32, 128), "mlp/gate/kernel": (32, 128),
            "mlp/down/kernel": (128, 32)}.items()}}
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 4, 8))
    positions = jnp.arange(16)[None]
    np.testing.assert_array_equal(rotary_embed(x, positions), rotary_embed(x, positions, 10000.0, 1.0))
    half = rotary_embed(x, positions, 5e5, 0.5)
    np.testing.assert_array_equal(half[..., 4:], x[..., 4:])
    np.testing.assert_allclose(half[..., :4], rotary_embed(x[..., :4], positions, 5e5), rtol=1e-6)
