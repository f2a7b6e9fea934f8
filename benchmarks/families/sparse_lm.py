"""The sparse decoder family: layers whose kinds differ by depth (full and
sliding-window attention with their own head counts over grouped KV heads, a
gate a head, rotary by kind; a dense SwiGLU or drop-free routed experts with a
shared one), an untied head — a chip's share of a stated deployment, handed to
``run_lm_trial`` whole through its ``architecture`` assignment.

What a family answers is said in families/dense_lm.py. The plain reference is
reference_sparse_lm.py. Operations and bytes are counted from shapes alone
(flops.py says why).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Tuple

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYOUT = {"tensor_parallel": 1}  # a cell's "layout" overrides and adds to it
BYTES = 2                        # an operand of a product: bfloat16


def validate(cell: Dict[str, Any], config: Dict[str, Any]) -> None:
    """Refuses, before anything is started, a configuration the installed
    program cannot build: a program without the architecture hand-off (an
    earlier commit) is told so in a sentence, not by a trial that fails."""
    try:
        from katib_tpu.models.architecture import architecture_config
    except ImportError as e:
        raise ValueError(
            "the installed katib_tpu cannot be handed an architecture whole "
            f"(katib_tpu.models.architecture: {e}); this configuration needs it") from e
    architecture_config(config, cell["seq_len"])  # raises ValueError with what is refused
    if config["tie_word_embeddings"] or not config["gating"]:
        raise ValueError("the sparse family's reference has an untied head and a gate a head")
    if cell["seq_len"] < config["sliding_window"]:
        raise ValueError("the cell's sequence is shorter than the window: no layer would be windowed")


def architecture_path(cell: Dict[str, Any]) -> str:
    return os.path.join(HERE, "configs", f"{cell['config']}.json")


def trial_parameters(cell: Dict[str, Any], config: Dict[str, Any]) -> Dict[str, str]:
    fixed = {
        "architecture": architecture_path(cell), "seq_len": str(cell["seq_len"]),
        "batch_size": str(cell["batch_size"]), "num_steps": str(cell["num_steps"]),
    }
    fixed.update({k: str(v) for k, v in dict(LAYOUT, **cell.get("layout", {})).items()})
    return fixed


def device_memory_bytes():
    """What one device may hold, or None where the backend does not say (the CPU)."""
    import jax

    return (jax.local_devices()[0].memory_stats() or {}).get("bytes_limit")


def reference(cell: Dict[str, Any], config: Dict[str, Any], **variant: Any):
    """``variant``: ``precision``, ``rows``, ``frozen`` (reference_sparse_lm.Reference).
    The reference's state is 16 B a parameter (float32 parameter, gradient and
    AdamW's two moments); where that is over half of the device's memory, too
    little is left for a row's activations and the moments wait on the host."""
    import reference_sparse_lm  # jax, so not at import

    limit = device_memory_bytes()
    return reference_sparse_lm.Reference(
        config, cell["batch_size"], cell["seq_len"],
        moments_on_host=bool(limit) and 16 * lm_parameters(config)["total"] > limit / 2, **variant)


# -- counts -------------------------------------------------------------------------------

def router_width(config: Dict) -> int:
    """Experts the router scores: the published count where a share is held."""
    return config.get("expert_share", {"of": config["num_experts"]})["of"]


def layer_parameters(config: Dict, layer: int) -> Dict[str, int]:
    """Matrix parameters of one layer, by part. ``routed`` counts every expert
    held here, ``routed_read`` what one token reads of them in expectation:
    ``num_experts_per_tok`` experts of the published count, of which the share
    held here — one expert's worth at 8 x 32 / 256."""
    e, d = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads_per_layer"][layer], config["num_key_value_heads"]
    out = {"attention": e * d * (2 * h + 2 * kv) + e * h}  # q, out, k, v and the gate
    if config["mlp_layer_types"][layer] == "sparse":
        expert = 3 * e * config["moe_intermediate_size"]
        share = router_width(config)
        out.update(
            router=e * share, shared=3 * e * config["shared_expert_intermediate_size"],
            routed=config["num_experts"] * expert,
            routed_read=expert * config["num_experts_per_tok"] * config["num_experts"] / share)
    else:
        out["dense"] = 3 * e * config["intermediate_size"]
    return out


def lm_parameters(config: Dict) -> Dict[str, float]:
    layers = [layer_parameters(config, i) for i in range(config["num_hidden_layers"])]
    table = config["vocab_size"] * config["hidden_size"]
    norms = (2 * config["num_hidden_layers"] + 1) * config["hidden_size"]
    held = sum(sum(v for k, v in part.items() if k != "routed_read") for part in layers)
    return {
        "head": table,
        "experts": sum(part.get("routed", 0) for part in layers),
        # what a product reads once a token, forward (the embedding is a look-up)
        "matmul": table + sum(sum(v for k, v in part.items() if k != "routed") for part in layers),
        "total": 2 * table + norms + held,
    }


def attended_pairs(seq_len: int, window=None) -> float:
    """(query, key) pairs one sequence's mask lets through: the causal half, or the band."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq_len - window) * float(window)


def _window(config: Dict, layer: int):
    return config["sliding_window"] if config["layer_types"][layer] == "sliding_attention" else None


def train_step_flops(config: Dict, batch: int, seq_len: int) -> float:
    """Operations one training step requires: 6 per matrix parameter a token
    reads (2 forward, 4 backward; routed experts at the expected load) plus
    attention over the causal half or the band — QKᵀ and PV forward, twice
    that backward: 12 per pair, head and head dimension. No recomputation."""
    tokens = batch * seq_len
    attention = sum(
        12.0 * batch * attended_pairs(seq_len, _window(config, i))
        * config["num_attention_heads_per_layer"][i] * config["head_dim"]
        for i in range(config["num_hidden_layers"]))
    return 6.0 * lm_parameters(config)["matmul"] * tokens + attention


def _flash_costs(batch, seq_len, heads, kv_heads, head_dim, window):
    """(operations, bytes) of one call of the forward kernel and of each
    backward kernel, as flops.flash_attention_cost counts them (two products
    forward; five backward, split evenly), over the pairs the mask lets through;
    k and v are read, and dk and dv written, at the KV heads' size."""
    product = 2.0 * batch * heads * attended_pairs(seq_len, window) * head_dim
    q_like = float(batch) * seq_len * heads * head_dim * BYTES
    kv_like = float(batch) * seq_len * kv_heads * head_dim * BYTES
    # forward: q, o and k, v; a backward kernel: q, o, do and its result at one size, k, v at the other
    return {"forward": (2.0 * product, 2 * q_like + 2 * kv_like),
            "dq": (2.5 * product, 4 * q_like + 2 * kv_like),
            "dkv": (2.5 * product, 3 * q_like + 4 * kv_like)}


def _kind_heads(config: Dict, kind: str) -> int:
    heads = {h for h, k in zip(config["num_attention_heads_per_layer"], config["layer_types"]) if k == kind}
    if len(heads) != 1:
        raise ValueError(f"{kind} layers with head counts {sorted(heads)}: one kernel name, one shape")
    return heads.pop()


def kernel_costs(config: Dict, batch: int, seq_len: int, landed=None) -> Dict[str, Tuple[float, float]]:
    """(operations, bytes) of one call of each kernel the program names: the
    full-attention kernels at the full layers' heads, the windowed ones at the
    sliding layers', and one sparse layer's grouped products by pass — at the
    expected load, or at ``landed``, the program's count of a step's
    assignments on held experts, summed over the sparse layers."""
    kv, d = config["num_key_value_heads"], config["head_dim"]
    full = _flash_costs(batch, seq_len, _kind_heads(config, "full_attention"), kv, d, None)
    band = _flash_costs(batch, seq_len, _kind_heads(config, "sliding_attention"), kv, d,
                        config["sliding_window"])
    costs = {
        "flash_fwd": full["forward"], "flash_bwd_dq": full["dq"], "flash_bwd_dkv": full["dkv"],
        "flash_window_fwd": band["forward"], "flash_window_bwd_dq": band["dq"],
        "flash_window_bwd_dkv": band["dkv"],
    }
    rows = None
    if landed is not None:
        rows = landed / sum(kind == "sparse" for kind in config["mlp_layer_types"])
    costs.update(expert_product_costs(config, batch, seq_len, rows))
    return costs


def expert_product_costs(config: Dict, batch: int, seq_len: int, rows=None) -> Dict[str, Tuple[float, float]]:
    """One grouped product of a sparse layer (a pass makes three alike: gate,
    up, down) over ``rows`` assignments, the expected load where none are
    given: operations from the rows, bytes the held experts' matrix once (as
    the product reads it, bfloat16; the weight gradient writes float32) plus
    the rows in and out."""
    e, f = config["hidden_size"], config["moe_intermediate_size"]
    if rows is None:
        rows = batch * seq_len * config["num_experts_per_tok"] * config["num_experts"] / router_width(config)
    weights = config["num_experts"] * float(e) * f
    operations = 2.0 * rows * e * f
    row_bytes = rows * (e + f) * BYTES
    return {
        "expert_gmm_fwd": (operations, weights * BYTES + row_bytes),
        "expert_gmm_dlhs": (operations, weights * BYTES + row_bytes),
        "expert_gmm_dw": (operations, weights * 4 + row_bytes),
    }
