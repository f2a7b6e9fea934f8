"""Plain reference of the language-model trial: the yardstick `correct` is held to.

Decoder-only LM as ``run_lm_trial`` builds it — token embedding, pre-norm
blocks of (RMSNorm with a learned scale, causal multi-head attention with
rotary positions, RMSNorm, SwiGLU), a final RMSNorm and the tied output head
— its mean cross-entropy, the gradients, and AdamW, in ``jax.numpy`` and
float32 with every matrix product at precision "highest". No flax, no optax,
no kernel, nothing imported from the program; the weights and the batch are
made here from the same fixed seeds the trial uses (``train.py``: weight seed
0, data seed 0), the learning rate is an argument.

Departures from the published OLMo-1B block, which the program makes and the
reference follows: RMSNorm with a learned scale (eps 1e-6) where OLMo-1B has a
non-parametric LayerNorm.

``precision`` selects the arithmetic of the matrix products only:
"float32" (the reference), and the lower ones used as *controls* in
``calibrate.py`` and the tests: "bfloat16" (operands rounded to bfloat16, f32
accumulation — what the configuration states) and "float8" (operands rounded to
float8_e4m3fn — the step below it, which `correct` has to refuse).

The batch is walked row by row and each block is rematerialised, so the float32
step fits beside nothing else on one chip at the published widths.
"""

from __future__ import annotations

import dataclasses
import hashlib
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# AdamW as the trial's optimizer is set (optax.adamw(lr, weight_decay=0.01)
# with optax's defaults for the rest)
B1, B2, EPS, WEIGHT_DECAY = 0.9, 0.999, 1e-8, 0.01
RMS_EPS = 1e-6
ROPE_THETA = 10000.0
EMBED_STD = 0.02


@dataclasses.dataclass(frozen=True)
class LMShape:
    vocab_size: int
    embed_dim: int
    num_layers: int
    num_heads: int
    mlp_ratio: int = 4

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads


# -- weights and data from the seed ---------------------------------------------

def _path_key(seed_key, path: Tuple[Any, ...]):
    """The key flax derives for the first parameter of the module at ``path``:
    SHA-1 of the path's names and the parameter counter, folded into the seed
    key (flax.core.scope._fold_in_static)."""
    m = hashlib.sha1()
    for x in path:
        if isinstance(x, str):
            m.update(x.encode("utf-8"))
        else:
            m.update(x.to_bytes((x.bit_length() + 7) // 8, byteorder="big"))
    return jax.random.fold_in(seed_key, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def init_params(shape: LMShape, seed: int = 0) -> Dict[str, Any]:
    """Float32 parameters, named and shaped as the trial's own tree: embedding
    normal(0.02); every projection LeCun-normal over its flattened [in, out]
    matrix; norm scales one."""
    key = jax.random.PRNGKey(seed)
    e, h, d, f = shape.embed_dim, shape.num_heads, shape.head_dim, shape.embed_dim * shape.mlp_ratio
    lecun = jax.nn.initializers.lecun_normal()

    def dense(path, flat, full):
        return {"kernel": lecun(_path_key(key, path + (1,)), flat, jnp.float32).reshape(full)}

    params: Dict[str, Any] = {
        "embed": jax.random.normal(_path_key(key, (1,)), (shape.vocab_size, e), jnp.float32) * EMBED_STD,
        "ln_f": {"scale": jnp.ones((e,), jnp.float32)},
    }
    for i in range(shape.num_layers):
        b = f"block{i}"
        params[b] = {
            "ln1": {"scale": jnp.ones((e,), jnp.float32)},
            "ln2": {"scale": jnp.ones((e,), jnp.float32)},
            "attn": {
                "qkv": dense((b, "attn", "qkv"), (e, 3 * h * d), (e, 3, h, d)),
                "out": dense((b, "attn", "out"), (h * d, e), (h, d, e)),
            },
            "mlp": {
                "up": dense((b, "mlp", "up"), (e, f), (e, f)),
                "gate": dense((b, "mlp", "gate"), (e, f), (e, f)),
                "down": dense((b, "mlp", "down"), (f, e), (f, e)),
            },
        }
    return params


def make_batch(vocab_size: int, batch: int, seq_len: int, seed: int = 0):
    """(tokens, targets), each [batch, seq_len] int32: one draw of uniform ids,
    shifted by one — the trial's constant synthetic batch."""
    data = np.random.default_rng(seed).integers(
        0, vocab_size, size=(batch, seq_len + 1), dtype=np.int32
    )
    return data[:, :-1], data[:, 1:]


# -- forward ------------------------------------------------------------------------

def _mm(spec: str, a, b, precision: str):
    if precision == "float32":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "bfloat16":
        lo = (a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))
    elif precision == "float8":
        lo = tuple(x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16) for x in (a, b))
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(spec, *lo, preferred_element_type=jnp.float32)


def _rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + RMS_EPS) * scale


def _rope(x):
    """Rotary positions on [T, H, D]: the two halves of D are the pairs."""
    t, _, d = x.shape
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (math.log(ROPE_THETA) / half))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(angles)[:, None, :], jnp.cos(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _block(x, p, precision: str):
    t = x.shape[0]
    h = _rms_norm(x, p["ln1"]["scale"])
    qkv = _mm("te,eshd->tshd", h, p["attn"]["qkv"]["kernel"], precision)
    q, k, v = _rope(qkv[:, 0]), _rope(qkv[:, 1]), qkv[:, 2]
    s = _mm("qhd,khd->hqk", q, k, precision) / math.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = _mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, precision)
    x = x + _mm("thd,hde->te", o, p["attn"]["out"]["kernel"], precision)
    h = _rms_norm(x, p["ln2"]["scale"])
    up = _mm("te,ef->tf", h, p["mlp"]["up"]["kernel"], precision)
    gate = _mm("te,ef->tf", h, p["mlp"]["gate"]["kernel"], precision)
    return x + _mm("tf,fe->te", jax.nn.silu(gate) * up, p["mlp"]["down"]["kernel"], precision)


def row_loss(params, tokens, targets, shape: LMShape, precision: str = "float32"):
    """Summed cross-entropy of one row [T] (the caller divides by the count)."""
    x = params["embed"][tokens]
    for i in range(shape.num_layers):
        x = jax.checkpoint(lambda x, p: _block(x, p, precision))(x, params[f"block{i}"])
    x = _rms_norm(x, params["ln_f"]["scale"])
    logits = _mm("te,ve->tv", x, params["embed"], precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).sum()


def loss_and_grads(params, tokens, targets, shape: LMShape, precision: str = "float32",
                   rows: int = 0):
    """Mean cross-entropy over the batch and its gradients, one row at a time.
    ``rows`` > 0 is the *fault* "half of the batch left out, the mean taken
    over the rest": only the first ``rows`` rows are used."""
    if rows:
        tokens, targets = tokens[:rows], targets[:rows]
    count = tokens.shape[0] * tokens.shape[1]

    def one(carry, row):
        loss, grads = jax.value_and_grad(row_loss)(params, row[0], row[1], shape, precision)
        return (carry[0] + loss, jax.tree.map(jnp.add, carry[1], grads)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(one, zero, (tokens, targets))
    return loss / count, jax.tree.map(lambda g: g / count, grads)


# -- AdamW ------------------------------------------------------------------------------

def adamw_update(params, mu, nu, grads, step, learning_rate):
    """One AdamW update; ``step`` counts from 1."""
    mu = jax.tree.map(lambda m, g: B1 * m + (1 - B1) * g, mu, grads)
    nu = jax.tree.map(lambda n, g: B2 * n + (1 - B2) * g * g, nu, grads)
    c1, c2 = 1 - B1 ** step, 1 - B2 ** step

    def new(p, m, n):
        return p - learning_rate * ((m / c1) / (jnp.sqrt(n / c2) + EPS) + WEIGHT_DECAY * p)

    return jax.tree.map(new, params, mu, nu), mu, nu


def leaf_norms(tree) -> Dict[str, Any]:
    """{"block0/attn/qkv/kernel": l2 norm, ...} of a parameter-shaped tree."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {
        "/".join(str(getattr(k, "key", k)) for k in path): jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
        for path, v in flat
    }


class Reference:
    """Three (or ``steps``) AdamW steps from the seed, compiled once; the
    learning rate is a traced argument, so one compile serves every trial."""

    def __init__(self, shape: LMShape, batch: int, seq_len: int, precision: str = "float32",
                 rows: int = 0, frozen: bool = False):
        self.shape, self.precision = shape, precision
        self.tokens, self.targets = make_batch(shape.vocab_size, batch, seq_len)
        self._init = jax.jit(lambda: init_params(shape))

        def step(params, mu, nu, tokens, targets, i, lr):
            loss, grads = loss_and_grads(params, tokens, targets, shape, precision, rows)
            new_params, mu, nu = adamw_update(params, mu, nu, grads, i, lr)
            if frozen:  # fault: a step that returns its state unchanged
                new_params = params
            return new_params, mu, nu, loss, leaf_norms(grads)

        self._step = jax.jit(step, donate_argnums=(0, 1, 2))
        self._delta = jax.jit(
            lambda p: leaf_norms(jax.tree.map(jnp.subtract, p, init_params(shape)))
        )

    def run(self, learning_rate: float, steps: int = 3) -> Dict[str, Any]:
        """{"loss": [per step], "grad_norm": {leaf: first gradient's norm},
        "delta_norm": {leaf: norm of the parameters' change after the steps}}."""
        params = self._init()
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        tokens, targets = jnp.asarray(self.tokens), jnp.asarray(self.targets)
        losses, grad_norm = [], None
        for i in range(1, steps + 1):
            params, mu, nu, loss, gn = self._step(
                params, mu, nu, tokens, targets, jnp.float32(i), jnp.float32(learning_rate)
            )
            losses.append(float(loss))
            if i == 1:
                grad_norm = {k: float(v) for k, v in gn.items()}
        delta = {k: float(v) for k, v in self._delta(params).items()}
        del params, mu, nu
        return {"loss": losses, "grad_norm": grad_norm, "delta_norm": delta}
