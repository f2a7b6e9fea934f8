"""Operations and bytes the work needs, from shapes alone.

Never from ``cost_analysis()``: that counts what the compiler emitted
(recomputation, padding, masked halves), and a program that wastes more would
read as busier. A configuration is the JSON object under ``benchmarks/configs``
(the published ``config.json`` keys).
"""

from __future__ import annotations

from typing import Dict, Tuple


def lm_parameters(config: Dict) -> Dict[str, int]:
    """Parameters of the decoder as the trial builds it: per layer the fused
    q/k/v and the output projection (4·E²), SwiGLU's three matrices (3·E·F) and
    two norm scales; the embedding, which is also the output head when tied;
    the final norm."""
    e, f = config["hidden_size"], config["intermediate_size"]
    layers, vocab = config["num_hidden_layers"], config["vocab_size"]
    per_layer = 4 * e * e + 3 * e * f
    head = vocab * e
    embed = head if config.get("tie_word_embeddings", True) else 2 * head
    return {
        "per_layer_matmul": per_layer,
        "head": head,
        # every parameter that a matrix product reads once per token, forward
        "matmul": layers * per_layer + head,
        "total": layers * (per_layer + 2 * e) + embed + e,
    }


def attention_flops_per_token(config: Dict, seq_len: int) -> float:
    """Forward and backward of causal attention, per token, over all layers:
    QKᵀ and PV are 2·T·E each when every key is read, half of that under the
    causal mask, and the backward pass twice the forward: 6·L·T·E."""
    return 6.0 * config["num_hidden_layers"] * seq_len * config["hidden_size"]


def train_step_flops(config: Dict, batch: int, seq_len: int) -> float:
    """Operations one training step requires: 6 per matrix parameter and token
    (2 forward, 4 backward) plus causal attention. No recomputation counted;
    the embedding look-up, norms, softmax and AdamW are left out (they are
    bytes, not matrix operations)."""
    per_token = 6.0 * lm_parameters(config)["matmul"] + attention_flops_per_token(config, seq_len)
    return per_token * batch * seq_len


def flash_attention_cost(batch: int, seq_len: int, heads: int, head_dim: int,
                         bytes_per_element: int = 2) -> Dict[str, Tuple[float, float]]:
    """(operations, bytes) that one call of the causal flash kernels needs, for
    one layer. One matrix product over the causal half is B·H·T²·D operations.
    Forward: QKᵀ and PV, two products. Backward: S again (it is never stored),
    dP, dV, dK and dQ, five products, split evenly between the program's two
    backward kernels (each of which recomputes S and dP: what it computes twice
    is not counted). Bytes: every operand read once and every result written
    once (q, k, v, o forward; q, k, v, o, do in and dq, dk, dv out backward),
    the f32 row statistics left out."""
    product = float(batch) * heads * seq_len * seq_len * head_dim
    tensor = float(batch) * seq_len * heads * head_dim * bytes_per_element
    return {
        "forward": (2.0 * product, 4.0 * tensor),
        "backward_each": (2.5 * product, 4.0 * tensor),
    }


def roofline_seconds(operations: float, nbytes: float, peaks: Dict) -> Tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    compute = operations / peaks["bf16_flops_per_s"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
