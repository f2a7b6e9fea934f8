"""Plain reference of the sparse decoder: the yardstick ``correct`` is held to
for a configuration of the ``sparse_lm`` family.

Pre-norm decoder (RMSNorm with a learned scale; ``x`` is [T, E]):

    h = x + Attn_l(norm(x));   y = h + FFN_l(norm(h));   logits = norm(x) Whead

- ``Attn_l``: ``H = num_attention_heads_per_layer[l]`` query heads over
  ``num_key_value_heads`` KV heads of ``head_dim``; query head ``j`` reads KV
  head ``j // (H / KV)``; rotary by the layer's kind (a fraction of each head,
  its own theta, YaRN where the group says so: cos and sin carry the attention
  factor); key ``s`` is visible to query ``t`` iff ``0 <= t - s`` and, in a
  sliding layer, ``t - s < sliding_window``; a sigmoid gate a head, computed
  from the layer's input, on the attention output; then the output projection.
- ``FFN_l``, dense: SwiGLU of ``intermediate_size``. Sparse: ``s = sigmoid(x
  Wr)`` over the published number of experts; ``S`` the ``num_experts_per_tok``
  largest; ``w_e = scale * s_e / sum_{j in S} s_j``; the result is the shared
  expert plus ``sum_{e in S, e held here} w_e SwiGLU_e(x)``. No token is
  dropped; what experts held elsewhere would add is left out (the chip's share,
  the ``model-configs`` guide's section 4).
- The head is its own [V, E] matrix; the loss is the mean cross-entropy.

``jax.numpy``, float32, every product at precision "highest"; no flax, no
optax, no kernel, nothing imported from the program: a loop over the held
experts with a mask (no sort, no grouped product), attention by an explicit
mask. Weights and batch come from the seeds the trial uses, under the
program's leaf names. ``adamw_update``, ``leaf_norms``, ``make_batch`` and the
products' precisions are reference_lm.py's, by import.

The batch is walked a row at a time and each layer is rematerialised. The
moments can be kept on the host (``moments_on_host``), for a size whose state
does not fit a chip beside one row's activations.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from reference_lm import (EMBED_STD, RMS_EPS, _mm, _path_key, _rms_norm, adamw_update,
                          leaf_norms, make_batch)

ATTENTION = {"full_attention", "sliding_attention"}


# -- rotary positions ---------------------------------------------------------------------

def yarn_frequencies(rotated: int, group: Mapping[str, Any]) -> np.ndarray:
    """The ``rotated / 2`` frequencies of a YaRN group (arXiv:2309.00071, as
    published modelling code computes them): a frequency that turns more than
    ``beta_fast`` times over the original positions is kept, one that turns
    fewer than ``beta_slow`` times is divided by ``factor``, between them a ramp."""
    theta, original = float(group["rope_theta"]), group["original_max_position_embeddings"]
    half = rotated // 2
    kept = 1.0 / theta ** (np.arange(half) * 2.0 / rotated)

    def dimension_turning(turns):
        return rotated * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dimension_turning(group["beta_fast"])), 0)
    high = min(math.ceil(dimension_turning(group["beta_slow"])), rotated - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return (kept / group["factor"] * ramp + kept * (1.0 - ramp)).astype(np.float32)


def _rope(x, group: Mapping[str, Any]):
    """[T, H, D]: the first ``partial_rotary_factor`` of D rotated (its two
    halves are the pairs), the rest passed through."""
    t, _, d = x.shape
    rotated = int(d * group.get("partial_rotary_factor", 1.0))
    half = rotated // 2
    if group.get("rope_type", "default") == "yarn":
        freqs, scale = jnp.asarray(yarn_frequencies(rotated, group)), group["attention_factor"]
    else:
        freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (math.log(group["rope_theta"]) / half))
        scale = 1.0
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(angles)[:, None, :] * scale, jnp.cos(angles)[:, None, :] * scale
    x1, x2 = x[..., :half], x[..., half:rotated]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., rotated:]], axis=-1)


# -- the model ----------------------------------------------------------------------------

class SparseLM:
    """The published keys, read once."""

    def __init__(self, config: Mapping[str, Any]):
        c = config
        self.vocab, self.embed, self.depth = c["vocab_size"], c["hidden_size"], c["num_hidden_layers"]
        self.head_dim, self.kv_heads = c["head_dim"], c["num_key_value_heads"]
        self.heads: List[int] = list(c["num_attention_heads_per_layer"])
        self.attention: List[str] = list(c["layer_types"])
        self.sparse: List[bool] = [kind == "sparse" for kind in c["mlp_layer_types"]]
        self.window = c["sliding_window"]
        self.rope = {k: v for k, v in c["rope_parameters"].items() if isinstance(v, Mapping)}
        self.dense_width, self.expert_width = c["intermediate_size"], c["moe_intermediate_size"]
        self.shared_width = c["shared_expert_intermediate_size"]
        self.held = c["num_experts"]
        share = c.get("expert_share", {"first": 0, "of": self.held})
        self.first, self.router_width = share["first"], share["of"]
        self.per_token, self.scale = c["num_experts_per_tok"], c["moe_routed_scaling_factor"]
        if c["tie_word_embeddings"] or not c["gating"] or set(self.attention) - ATTENTION:
            raise ValueError("the sparse reference has an untied head, a gate a head, full and sliding layers")
        if c["rms_norm_eps"] != RMS_EPS:
            raise ValueError(f"the reference's norm has eps {RMS_EPS}")


def init_params(m: SparseLM, seed: int = 0) -> Dict[str, Any]:
    """Float32 parameters under the program's leaf names: embedding and head
    normal(0.02); every projection LeCun-normal over its flattened [in, out]
    matrix, an expert's over its own; norm scales one. Keys as flax derives
    them (reference_lm._path_key)."""
    key = jax.random.PRNGKey(seed)
    e, d, kv = m.embed, m.head_dim, m.kv_heads
    lecun = jax.nn.initializers.lecun_normal()
    by_expert = jax.nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=(0,))

    def dense(path, flat, full):
        return {"kernel": lecun(_path_key(key, path + (1,)), flat, jnp.float32).reshape(full)}

    def swiglu(path, width):
        return {"up": dense(path + ("up",), (e, width), (e, width)),
                "gate": dense(path + ("gate",), (e, width), (e, width)),
                "down": dense(path + ("down",), (width, e), (width, e))}

    params: Dict[str, Any] = {
        "embed": jax.random.normal(_path_key(key, (1,)), (m.vocab, e), jnp.float32) * EMBED_STD,
        "head": jax.random.normal(_path_key(key, (2,)), (m.vocab, e), jnp.float32) * EMBED_STD,
        "ln_f": {"scale": jnp.ones((e,), jnp.float32)},
    }
    for i in range(m.depth):
        b, h = f"block{i}", m.heads[i]
        block = {
            "ln1": {"scale": jnp.ones((e,), jnp.float32)},
            "ln2": {"scale": jnp.ones((e,), jnp.float32)},
            "attn": {
                "qkv": {"q": dense((b, "attn", "qkv", "q"), (e, h * d), (e, h, d)),
                        "k": dense((b, "attn", "qkv", "k"), (e, kv * d), (e, kv, d)),
                        "v": dense((b, "attn", "qkv", "v"), (e, kv * d), (e, kv, d))},
                "gate": dense((b, "attn", "gate"), (e, h), (e, h)),
                "out": dense((b, "attn", "out"), (h * d, e), (h, d, e)),
            },
        }
        if m.sparse[i]:
            f, path = m.expert_width, (b, "experts")
            block["experts"] = {
                "router": dense(path + ("router",), (e, m.router_width), (e, m.router_width)),
                "gate": by_expert(_path_key(key, path + (1,)), (m.held, e, f), jnp.float32),
                "up": by_expert(_path_key(key, path + (2,)), (m.held, e, f), jnp.float32),
                "down": by_expert(_path_key(key, path + (3,)), (m.held, f, e), jnp.float32),
                "shared": swiglu(path + ("shared",), m.shared_width),
            }
        else:
            block["mlp"] = swiglu((b, "mlp"), m.dense_width)
        params[b] = block
    return params


def _swiglu(x, p, precision):
    up = _mm("te,ef->tf", x, p["up"]["kernel"], precision)
    gate = _mm("te,ef->tf", x, p["gate"]["kernel"], precision)
    return _mm("tf,fe->te", jax.nn.silu(gate) * up, p["down"]["kernel"], precision)


def chosen_experts(x, p, m: SparseLM):
    """(weights [T, k], experts [T, k]) of the normed input ``x``: router and
    weights in float32 at "highest" whatever the products' precision."""
    scores = jax.nn.sigmoid(jnp.einsum("te,er->tr", x, p["router"]["kernel"],
                                       precision=jax.lax.Precision.HIGHEST))
    top, experts = jax.lax.top_k(scores, m.per_token)
    return m.scale * top / jnp.sum(top, axis=-1, keepdims=True), experts


def _experts(x, p, m: SparseLM, precision):
    weights, experts = chosen_experts(x, p, m)

    def one_expert(x, w, up, gate, down):
        return w * _swiglu(x, {"up": {"kernel": up}, "gate": {"kernel": gate}, "down": {"kernel": down}},
                           precision)

    def add_one(out, held):  # every held expert over every token, masked: no sort, no gather
        e, up, gate, down = held
        w = jnp.sum(jnp.where(experts == m.first + e, weights, 0.0), axis=-1, keepdims=True)
        return out + jax.checkpoint(one_expert)(x, w, up, gate, down), None

    out, _ = jax.lax.scan(add_one, _swiglu(x, p["shared"], precision),
                          (jnp.arange(m.held), p["up"], p["gate"], p["down"]))
    return out, experts


def _attention(x, p, m: SparseLM, layer: int, precision):
    t, h = x.shape[0], m.heads[layer]
    kind = m.attention[layer]
    q = _rope(_mm("te,ehd->thd", x, p["qkv"]["q"]["kernel"], precision), m.rope[kind])
    k = _rope(_mm("te,ehd->thd", x, p["qkv"]["k"]["kernel"], precision), m.rope[kind])
    v = _mm("te,ehd->thd", x, p["qkv"]["v"]["kernel"], precision)
    group = h // m.kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    gap = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    seen = (gap >= 0) & (gap < m.window) if kind == "sliding_attention" else gap >= 0

    def one_head(qkv):  # a head at a time: [T, T] scores are all that is alive
        q, k, v = qkv
        s = _mm("qd,kd->qk", q, k, precision) / math.sqrt(m.head_dim)
        return _mm("qk,kd->qd", jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), v, precision)

    o = jax.lax.map(jax.checkpoint(one_head), tuple(a.transpose(1, 0, 2) for a in (q, k, v)))
    gate = jax.nn.sigmoid(jnp.einsum("te,eh->th", x, p["gate"]["kernel"],
                                     precision=jax.lax.Precision.HIGHEST))
    return _mm("hqd,hde->qe", o * gate.T[..., None], p["out"]["kernel"], precision)


def _block(x, p, m: SparseLM, layer: int, precision):
    x = x + _attention(_rms_norm(x, p["ln1"]["scale"]), p["attn"], m, layer, precision)
    h = _rms_norm(x, p["ln2"]["scale"])
    if m.sparse[layer]:
        out, chosen = _experts(h, p["experts"], m, precision)
        return x + out, chosen
    return x + _swiglu(h, p["mlp"], precision), jnp.full((x.shape[0], m.per_token), -1)


def row_forward(params, tokens, m: SparseLM, precision: str = "float32"):
    """(the final norm's input [T, E], the experts each token chose by layer
    [depth, T, k]; -1 in a dense layer) of one row [T]."""
    x = params["embed"][tokens]
    chosen = []
    for i in range(m.depth):
        x, c = jax.checkpoint(lambda x, p, i=i: _block(x, p, m, i, precision))(x, params[f"block{i}"])
        chosen.append(c)
    return x, jnp.stack(chosen)


def row_loss(params, tokens, targets, m: SparseLM, precision: str = "float32"):
    """(summed cross-entropy of one row [T], assignments that landed on held experts)."""
    x, chosen = row_forward(params, tokens, m, precision)
    landed = jnp.sum((chosen >= m.first) & (chosen < m.first + m.held))
    x = _rms_norm(x, params["ln_f"]["scale"])
    logits = _mm("te,ve->tv", x, params["head"], precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).sum(), landed


def loss_and_grads(params, tokens, targets, m: SparseLM, precision: str = "float32", rows: int = 0):
    """Mean cross-entropy, its gradients and the landed assignments, a row at
    a time (no accumulator beside the one row's gradients when there is one row). ``rows`` > 0: the fault "only the first ``rows`` rows, the mean over them"."""
    if rows:
        tokens, targets = tokens[:rows], targets[:rows]
    count = tokens.shape[0] * tokens.shape[1]
    loss, landed, grads = 0.0, 0, None
    for row in range(tokens.shape[0]):  # unrolled: a cell's batch is a few long rows
        (row_l, row_n), row_g = jax.value_and_grad(row_loss, has_aux=True)(
            params, tokens[row], targets[row], m, precision)
        loss, landed = loss + row_l, landed + row_n
        grads = row_g if grads is None else jax.tree.map(jnp.add, grads, row_g)
    return loss / count, jax.tree.map(lambda g: g / count, grads), landed


class Reference:
    """Three (or ``steps``) AdamW steps from the seed, compiled once; the
    learning rate is a traced argument. ``run`` gives what reference_lm's does
    and ``landed``, the assignments on held experts at each step."""

    def __init__(self, config: Mapping[str, Any], batch: int, seq_len: int,
                 precision: str = "float32", rows: int = 0, frozen: bool = False,
                 moments_on_host: bool = False):
        self.model = m = SparseLM(config)
        self.tokens, self.targets = make_batch(m.vocab, batch, seq_len)
        self.moments_on_host = moments_on_host
        self._init = jax.jit(lambda: init_params(m))
        self._grads = jax.jit(
            lambda params, tokens, targets: loss_and_grads(params, tokens, targets, m, precision, rows))

        def update(params, mu, nu, grads, i, lr):
            new_params, mu, nu = adamw_update(params, mu, nu, grads, i, lr)
            if frozen:  # fault: a step that returns its state unchanged
                new_params = params
            return new_params, mu, nu, leaf_norms(grads)

        self._update = jax.jit(update, donate_argnums=(0, 1, 2))
        self._delta = jax.jit(lambda p: leaf_norms(jax.tree.map(jnp.subtract, p, init_params(m))))

    def run(self, learning_rate: float, steps: int = 3) -> Dict[str, Any]:
        params = self._init()
        if self.moments_on_host:  # on the device only while AdamW reads them
            zeros, keep = (lambda p: np.zeros(p.shape, p.dtype)), jax.device_get
        else:
            zeros, keep = jnp.zeros_like, (lambda tree: tree)
        mu, nu = jax.tree.map(zeros, params), jax.tree.map(zeros, params)
        tokens, targets = jnp.asarray(self.tokens), jnp.asarray(self.targets)
        losses, landed, grad_norm = [], [], None
        for i in range(1, steps + 1):
            loss, grads, n = self._grads(params, tokens, targets)
            params, mu, nu, gn = self._update(
                params, mu, nu, grads, jnp.float32(i), jnp.float32(learning_rate))
            del grads
            if i < steps:
                mu, nu = keep(mu), keep(nu)
            losses.append(float(loss))
            landed.append(int(n))
            if i == 1:
                grad_norm = {k: float(v) for k, v in gn.items()}
        delta = {k: float(v) for k, v in self._delta(params).items()}
        del params, mu, nu
        return {"loss": losses, "grad_norm": grad_norm, "delta_norm": delta, "landed": landed}
