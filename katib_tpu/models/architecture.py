"""A decoder's architecture handed in whole: the keys of a published
``config.json`` (the transformers convention) read into a TransformerConfig.

``run_lm_trial`` takes the path of such a file in its ``architecture``
assignment (docs/architecture-handoff.md). Every key is either read, known to
say nothing about the program that is built, or refused: a key this module has
never heard of may change the arithmetic, and a model that is silently not the
one asked for is worse than none. No model's name is known here.

A file may describe one chip's share of a deployment: ``num_experts`` then
counts the experts held here and ``expert_share`` says which of how many
(``{"first": 0, "of": 256}``: the router keeps 256 outputs); ``vocab_size`` is
the slice of the vocabulary held, and is simply the vocabulary.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Mapping, Union

from .transformer import (
    LayerConfig,
    RotaryConfig,
    RoutedExpertsConfig,
    TransformerConfig,
    YarnConfig,
)

ATTENTION_KINDS = {"full_attention": "full", "sliding_attention": "sliding"}
MLP_KINDS = {"dense": "dense", "sparse": "routed"}

# read below
READ = {
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "rms_norm_eps", "tie_word_embeddings", "gating",
    "sliding_window", "layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
    "rope_parameters", "rope_theta", "partial_rotary_factor", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "shared_expert_intermediate_size", "moe_routed_scaling_factor",
    "expert_share", "max_position_embeddings",
}
# must have the one value this program computes
FIXED = {
    "attention_bias": False, "mlp_bias": False, "hidden_act": "silu",
    "moe_apply_router_weight_on_input": False, "norm_topk_prob": True,
    "moe_router_logit_softcapping": 0, "attention_dropout": 0.0, "clip_qkv": None,
}
# say nothing about the program: provenance, what a file was cut from and why,
# what runs it, what a checkpoint would need
SILENT = {
    "source", "family", "model_type", "architectures", "published", "reduced", "why_reduced",
    "chips_sharing_a_layer", "deployment", "assumed", "departures", "compute_dtype",
    "parameter_dtype", "torch_dtype", "dtype", "transformers_version", "use_cache",
    "initializer_range", "bos_token_id", "eos_token_id", "pad_token_id",
}


class ArchitectureRefused(ValueError):
    """The architecture asks for something this program does not compute."""


def _rotary(group: Mapping[str, Any], what: str) -> RotaryConfig:
    kind = group.get("rope_type", "default")
    known = {"rope_type", "rope_theta", "partial_rotary_factor"}
    yarn = None
    if kind == "yarn":
        known |= {"factor", "original_max_position_embeddings", "beta_fast", "beta_slow",
                  "attention_factor"}
        yarn = YarnConfig(
            factor=float(group["factor"]),
            original_positions=int(group["original_max_position_embeddings"]),
            beta_fast=float(group.get("beta_fast", 32)), beta_slow=float(group.get("beta_slow", 1)),
            attention_factor=float(group["attention_factor"]),
        )
    elif kind != "default":
        raise ArchitectureRefused(f"{what}: rope_type {kind!r} (default and yarn are computed)")
    unknown = sorted(set(group) - known)
    if unknown:
        raise ArchitectureRefused(f"{what}: keys {unknown} are not read")
    return RotaryConfig(
        theta=float(group.get("rope_theta", 10000.0)),
        fraction=float(group.get("partial_rotary_factor", 1.0)), yarn=yarn,
    )


def architecture_config(arch: Mapping[str, Any], max_seq_len: int) -> TransformerConfig:
    """The TransformerConfig that ``arch`` (published keys) describes."""
    unknown = sorted(set(arch) - READ - set(FIXED) - SILENT)
    if unknown:
        raise ArchitectureRefused(
            f"keys {unknown} are neither read nor known to be without effect")
    for key, value in FIXED.items():
        if key in arch and arch[key] != value:
            raise ArchitectureRefused(f"{key} = {arch[key]!r}: only {value!r} is computed")
    if max_seq_len > arch.get("max_position_embeddings", max_seq_len):
        raise ArchitectureRefused(
            f"{max_seq_len} positions are more than max_position_embeddings")

    depth = int(arch["num_hidden_layers"])
    heads = int(arch["num_attention_heads"])
    attention = arch.get("layer_types", ["full_attention"] * depth)
    mlp = arch.get("mlp_layer_types", ["dense"] * depth)
    heads_by_layer = arch.get("num_attention_heads_per_layer", [heads] * depth)
    for name, listed in (("layer_types", attention), ("mlp_layer_types", mlp),
                         ("num_attention_heads_per_layer", heads_by_layer)):
        if len(listed) != depth:
            raise ArchitectureRefused(f"{name} lists {len(listed)} layers of {depth}")
    for name, kinds, known in (("layer_types", attention, ATTENTION_KINDS),
                               ("mlp_layer_types", mlp, MLP_KINDS)):
        strange = sorted(set(kinds) - set(known))
        if strange:
            raise ArchitectureRefused(f"{name}: no layer of the kind {strange} is computed")
    layers = tuple(
        LayerConfig(ATTENTION_KINDS[a], int(h), MLP_KINDS[m])
        for a, h, m in zip(attention, heads_by_layer, mlp)
    )
    kv_heads = int(arch.get("num_key_value_heads", heads))
    if any(layer.num_heads % kv_heads for layer in layers):
        raise ArchitectureRefused("a layer's query heads are no multiple of num_key_value_heads")
    window = arch.get("sliding_window")
    if any(layer.attention == "sliding" for layer in layers) and not window:
        raise ArchitectureRefused("sliding_attention layers and no sliding_window")

    groups = arch.get("rope_parameters")
    if groups is None:
        rotary = {"full": RotaryConfig(
            theta=float(arch.get("rope_theta", 10000.0)),
            fraction=float(arch.get("partial_rotary_factor", 1.0)))}
    elif any(isinstance(v, Mapping) for v in groups.values()):
        # one group by attention kind; scalars beside them repeat what the groups say
        rotary = {ATTENTION_KINDS[k]: _rotary(v, f"rope_parameters.{k}")
                  for k, v in groups.items() if isinstance(v, Mapping)}
    else:
        rotary = {"full": _rotary(groups, "rope_parameters")}
    missing = sorted({layer.attention for layer in layers} - set(rotary))
    if missing:
        raise ArchitectureRefused(f"rope_parameters has no group for {missing} layers")

    routed = None
    if any(layer.mlp == "routed" for layer in layers):
        share = arch.get("expert_share", {"first": 0, "of": arch["num_experts"]})
        if set(share) != {"first", "of"} or share["first"] + arch["num_experts"] > share["of"]:
            raise ArchitectureRefused(
                'expert_share is {"first": i, "of": n} with first + num_experts <= of')
        routed = RoutedExpertsConfig(
            router_width=int(share["of"]), experts_per_token=int(arch["num_experts_per_tok"]),
            hidden=int(arch["moe_intermediate_size"]), num_experts=int(arch["num_experts"]),
            first_expert=int(share["first"]),
            routed_scale=float(arch.get("moe_routed_scaling_factor", 1.0)),
            shared_hidden=int(arch.get("shared_expert_intermediate_size", 0)),
        )
    gating = arch.get("gating", False)
    if gating not in (False, True, "per-head"):
        raise ArchitectureRefused(f"gating = {gating!r}: a gate a head, or none, is computed")
    return TransformerConfig(
        vocab_size=int(arch["vocab_size"]), embed_dim=int(arch["hidden_size"]), num_layers=depth,
        num_heads=heads, max_seq_len=max_seq_len,
        head_size=int(arch.get("head_dim", arch["hidden_size"] // heads)), num_kv_heads=kv_heads,
        mlp_hidden=int(arch["intermediate_size"]), window=int(window) if window else None,
        layers=layers, rotary=tuple(sorted(rotary.items())), attention_gate=bool(gating),
        tied_head=bool(arch.get("tie_word_embeddings", True)), routed=routed,
        rms_eps=float(arch.get("rms_norm_eps", 1e-6)),
    )


def load_architecture(source: Union[str, os.PathLike]) -> Dict[str, Any]:
    """The published-key object, read from the JSON file at a path."""
    with open(os.fspath(source)) as f:
        arch = json.load(f)
    if not isinstance(arch, dict):
        raise ArchitectureRefused(f"{source}: not a JSON object")
    return arch
