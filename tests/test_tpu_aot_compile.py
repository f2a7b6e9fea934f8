"""The main path's Pallas kernels compile for a TPU v5e that is described,
not attached (libtpu's compiler is installed; no chip is needed).

A compile that passes is not a chip run — it says nothing about results or
times. It catches what interpret mode cannot: a block the chip's tiling
refuses, a kernel that wants more VMEM than it may have, a kernel that
cannot be partitioned. ``chip_smoke.py`` is the run on the chip.

Everything that touches the TPU compiler happens inside the module-scoped
``topo`` fixture or a test — nothing at import time (only one process may
hold libtpu, and every xdist worker imports every test file). Keep these
tests in this one file for the same reason.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# [BH, T, D] as the kernels see it. The first is the LM-large attention
# shape (batch 4 x 16 heads, T 2048, head_dim 64); d=128 is the head width
# of the benchmark's configurations.
SHAPES = [
    pytest.param((64, 2048, 64), jnp.bfloat16, id="lm_large-bh64-t2048-d64-bf16"),
    pytest.param((64, 1024, 64), jnp.bfloat16, id="bh64-t1024-d64-bf16"),
    pytest.param((8, 2048, 128), jnp.bfloat16, id="bh8-t2048-d128-bf16"),
    pytest.param((8, 2048, 64), jnp.float32, id="bh8-t2048-d64-f32"),
]


def _blocks(t):
    from katib_tpu.ops import flash_attention as fa

    return (
        fa._auto_block(t, fa.FWD_BLOCK_Q_CAP),
        fa._auto_block(t, fa.FWD_BLOCK_K_CAP),
        fa._auto_block(t, fa.BWD_BLOCK_CAP),
    )


def _compile(fn, *avals):
    lowered = jax.jit(fn).lower(*avals)
    text = lowered.as_text()
    compiled = lowered.compile()  # raises what the chip's compiler would raise
    assert compiled.memory_analysis() is not None
    return text


@pytest.mark.parametrize("shape,dtype", SHAPES)
def test_flash_forward_kernel_compiles_for_v5e(one_chip, shape, dtype):
    from katib_tpu.ops import flash_attention as fa

    bq, bk, _ = _blocks(shape[1])
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    fwd = functools.partial(
        fa._fwd, causal=True, sm_scale=shape[2] ** -0.5,
        block_q=bq, block_k=bk, interpret=False,
    )
    text = _compile(fwd, x, x, x)
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("shape,dtype", SHAPES)
def test_flash_backward_kernels_compile_for_v5e(one_chip, shape, dtype):
    """Both backward kernels (dq; dk/dv) at the tiles the VJP picks."""
    from katib_tpu.ops import flash_attention as fa

    _, _, bb = _blocks(shape[1])
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    lse = jax.ShapeDtypeStruct(shape[:2] + (1,), jnp.float32, sharding=one_chip)
    bwd = functools.partial(
        fa._bwd, causal=True, sm_scale=shape[2] ** -0.5,
        block_q=bb, block_k=bb, interpret=False,
    )
    text = _compile(bwd, x, x, x, x, lse, x)  # q k v o lse do
    assert text.count("tpu_custom_call") == 2


@pytest.mark.parametrize("shape,dtype", SHAPES)
def test_flash_value_and_grad_compiles_for_v5e(one_chip, shape, dtype):
    """The custom-VJP wrapper as a train step differentiates it: forward,
    residuals, and both backward kernels in one program."""
    from katib_tpu.ops import flash_attention as fa

    bq, bk, _ = _blocks(shape[1])
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v):
        o = fa._flash_bhtd(q, k, v, True, shape[2] ** -0.5, bq, bk, False)
        return o.astype(jnp.float32).sum()

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), x, x, x)
    assert text.count("tpu_custom_call") == 3


def entry_ops_by_estimated_cycles(compiled_text):
    """[(cycles, instruction, its result type, op_name)] of the
    compiled module's entry computation, the compiler's costliest first.
    Pallas calls carry no estimate and are left out. At the v5e's 1.5 GHz
    the estimates read within 4 % of the chip on the head's products and
    the embedding's AdamW, and 60 % over it on the convolution this file
    guards against (PERF.md, PR 28): good for an order, not for a time."""
    entry = re.search(r"^ENTRY .*?\{\n(.*?)^\}", compiled_text, re.S | re.M).group(1)
    rows = []
    for line in entry.splitlines():
        cycles = re.search(r'"estimated_cycles":"(\d+)"', line)
        head = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\S+)", line)
        if cycles and head:
            op_name = re.search(r'op_name="([^"]*)"', line)
            rows.append((int(cycles.group(1)), head.group(1), head.group(2),
                         op_name.group(1) if op_name else ""))
    return sorted(rows, reverse=True)


def _adamw_step_text(module, one_chip, batch, tokens, embed, dtype):
    """The compiled text of one step of ``module(x, positions)`` summed as a
    loss, with its input's gradient, AdamW and donated state, as a train step
    has them, for the described chip."""
    import optax

    tx = optax.adamw(1e-3, weight_decay=0.01)
    init_x = jnp.zeros((1, 16, embed), dtype)
    init_pos = jnp.zeros((1, 16), jnp.int32)
    params = jax.eval_shape(
        lambda k: module.init(k, init_x, init_pos)["params"], jax.random.PRNGKey(0)
    )
    opt_state = jax.eval_shape(tx.init, params)

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
        )

    def step(params, opt_state, x, positions):
        def loss_fn(p, x):
            return module.apply({"params": p}, x, positions).astype(jnp.float32).sum()

        loss, (grads, d_x) = jax.value_and_grad(loss_fn, argnums=(0, 1))(params, x)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss, d_x

    return jax.jit(step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(opt_state),
        jax.ShapeDtypeStruct((batch, tokens, embed), dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((batch, tokens), jnp.int32, sharding=one_chip),
    ).compile().as_text()


def test_qkv_weight_gradient_is_no_windowed_convolution_on_v5e(one_chip, monkeypatch):
    """One Block + AdamW at the benchmark cells' widths (E 2048, 16 heads of
    128, batch 4 x 2048). With q, k and v sliced out of one DenseGeneral's
    result, XLA made the weight gradient one convolution over the tokens
    with [3, H] as its window (``window={size=3x16x4 ...}``), a third as
    fast as the three products it stands for (PERF.md, PR 28)."""
    from katib_tpu.models.transformer import Block, TransformerConfig
    from katib_tpu.ops import flash_attention as fa

    # the CPU backend is the default one here: take the chip's branch
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    heads, b, t, e = 16, 4, 2048, 2048
    cfg = TransformerConfig(embed_dim=e, num_heads=heads, max_seq_len=t)
    text = _adamw_step_text(Block(cfg), one_chip, b, t, e, cfg.dtype)
    assert text.count("tpu_custom_call") >= 3  # the flash kernels are in it
    windowed = [
        line.strip()[:200] for line in text.splitlines()
        if " convolution(" in line and f"window={{size=3x{heads}x" in line
    ]
    assert windowed == []
    ranked = entry_ops_by_estimated_cycles(text)
    qkv_grad = [r for r in ranked if "transpose(jvp" in r[3] and "attn/attention/qkv" in r[3]]
    mlp_grad = [r for r in ranked if "transpose(jvp" in r[3] and "/mlp/" in r[3]]
    assert qkv_grad and mlp_grad, ranked[:10]
    # the MLP's weight gradients do 1.3 times the arithmetic of q/k/v's
    assert qkv_grad[0][0] < mlp_grad[0][0], (qkv_grad[0], mlp_grad[0])


# The sparse cell's kernels at its own shapes (PR 30): T 8192, heads of 128,
# 8 KV heads under 48 query heads (full layers) and 64 (sliding layers, window
# 512); one routed layer's grouped products, 32 experts of 2048 x 512.
GROUPED = [
    pytest.param(48, None, id="full-48over8-t8192"),
    pytest.param(64, 512, id="window512-64over8-t8192"),
]


def _kernel_grids(lowered_text):
    """{kernel name: its grid} of every Pallas call in a lowered module: the
    call's Mosaic body (MLIR bytecode, base64) names itself and carries the
    grid as ``iteration_bounds``."""
    import base64

    from jax._src.lib.mlir import ir

    grids = {}
    with ir.Context() as ctx:
        ctx.allow_unregistered_dialects = True  # the serialised dialect has a name of its own
        for body in re.findall(r'body\\22: \\22([A-Za-z0-9+/=]+)', lowered_text):
            asm = ir.Module.parse(base64.b64decode(body)).operation.get_asm(enable_debug_info=False)
            name = re.search(r"module @([\w.]+)", asm).group(1)
            bounds = re.search(r"iteration_bounds = array<i64: ([\d, ]+)>", asm).group(1)
            grids[name] = tuple(int(n) for n in bounds.split(","))
    return grids


@pytest.mark.parametrize("heads,window", GROUPED)
def test_grouped_and_windowed_flash_kernels_compile_for_v5e(one_chip, monkeypatch, heads, window):
    from katib_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    q = jax.ShapeDtypeStruct((1, 8192, heads, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window).astype(jnp.float32).sum()

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert text.count("tpu_custom_call") == 3
    names = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv") if window is None else (
        "flash_window_fwd", "flash_window_bwd_dq", "flash_window_bwd_dkv")
    for name in names:
        assert name in text
    grids = _kernel_grids(text)
    if window is None:  # the last axis walks the kv blocks (dk/dv: the q blocks of the group's six heads)
        assert grids == {"flash_fwd": (48, 16, 8), "flash_bwd_dq": (48, 8, 8), "flash_bwd_dkv": (8, 8, 48)}
    else:  # no axis over the band: a step holds it whole (dk/dv: one step a query head of the group)
        assert grids == {"flash_window_fwd": (64, 8), "flash_window_bwd_dq": (64, 8),
                         "flash_window_bwd_dkv": (8, 8, 8)}


@pytest.mark.parametrize("heads,window", GROUPED)
def test_grouped_q_kernel_s_update_costs_what_out_s_does_on_v5e(one_chip, monkeypatch, heads, window):
    """One Attention layer + AdamW at the sparse cell's widths (E 2048, heads
    of 128 over 8 KV heads, window 512, gate, batch 1 x 8192). The q and out
    kernels' updates are the same arithmetic over the same bytes. Left to
    nn.DenseGeneral, q's was one fusion of a head-windowed convolution, three
    transposing copies and AdamW writing head-major, with three copies of
    ``f32[2048, H, 128]`` behind it: 2.18 times out's by the compiler's
    estimate, 21.0 ms a step against 8.4 on the chip (PERF.md, PR 33)."""
    from katib_tpu.models.transformer import Attention, LayerConfig, TransformerConfig
    from katib_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    e, d, t = 2048, 128, 8192
    cfg = TransformerConfig(
        embed_dim=e, num_heads=heads, head_size=d, num_kv_heads=8, max_seq_len=t, window=window,
        attention_gate=True)
    layer = Attention(
        cfg, layer=LayerConfig(attention="sliding" if window else "full", num_heads=heads))
    text = _adamw_step_text(layer, one_chip, 1, t, e, cfg.dtype)
    assert text.count("tpu_custom_call") == 3
    ranked = entry_ops_by_estimated_cycles(text)
    # every instruction behind the forward pass that makes an array of the q
    # kernel's shape: its weight gradient, its AdamW (fused with it or apart),
    # every copy or turn of the kernel, a moment or the gradient
    kernel_shaped = re.compile(rf"\w+\[{e},{heads},{d}\]")
    q_update = [r for r in ranked if kernel_shaped.search(r[2]) and "/jvp(" not in r[3]]
    q_grad = [r for r in q_update if "transpose(jvp" in r[3] and "/qkv/q/" in r[3]]
    out_update = [r for r in ranked if "transpose(jvp" in r[3] and "/out/" in r[3]
                  and re.search(rf"f32\[{heads},{d},{e}\]", r[2])]
    assert q_grad and len(out_update) == 1, ranked[:12]
    assert any(r[2].lstrip("(").startswith("f32[") for r in q_update), q_update   # AdamW is among them
    ratio = sum(r[0] for r in q_update) / out_update[0][0]
    assert ratio <= 1.6, (ratio, q_update, out_update)


def test_grouped_expert_products_compile_for_v5e(one_chip, monkeypatch):
    from katib_tpu.ops import flash_attention as fa
    from katib_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    rows_n = (8192 * 8 // gm.TILE + 32 + gm.CHUNK_TILES - 1) * gm.TILE
    rows = jax.ShapeDtypeStruct((rows_n, 2048), jnp.bfloat16, sharding=one_chip)
    w_in = jax.ShapeDtypeStruct((32, 2048, 512), jnp.float32, sharding=one_chip)
    w_out = jax.ShapeDtypeStruct((32, 512, 2048), jnp.float32, sharding=one_chip)
    tile_group = jax.ShapeDtypeStruct((rows_n // gm.TILE,), jnp.int32, sharding=one_chip)
    num_tiles = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def loss(rows, w_in, w_out, tile_group, num_tiles):
        hidden = gm.grouped_matmul(rows, w_in, tile_group, num_tiles)
        return gm.grouped_matmul(hidden, w_out, tile_group, num_tiles).astype(jnp.float32).sum()

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), rows, w_in, w_out, tile_group, num_tiles)
    assert text.count("tpu_custom_call") == 6
    for name in ("expert_gmm_fwd", "expert_gmm_dlhs", "expert_gmm_dw"):
        assert name in text


def _instructions(compiled_text, opcode):
    """Result types (``bf16[65536,2048]``) of every ``opcode`` instruction of a
    compiled module, those inside fusions among them."""
    return re.findall(rf"= (\w+\[[\d,]*\])\S* {opcode}\(", compiled_text)


def test_routed_layer_moves_its_rows_once_each_way_on_v5e(one_chip, monkeypatch):
    """One routed layer's value and gradients at the sparse cell's shapes
    ([1, 8192, 2048], 32 of 256 experts, 8 a token, width 512; no shared
    expert). With the combine written as a gather and a weighted sum,
    autodiff kept the ``[65536, 2048]`` gather for the weights' gradient, so
    the rematerialised forward made it again (3 gathers of N k rows a layer,
    ~2.4 ms each on the chip), and every gather was followed by a select over
    its whole result (PERF.md, PR 31). ``combine_rows``' own gradient works
    over the buffer's rows, and the masks sit on scalars."""
    from katib_tpu.models.transformer import RoutedExperts, RoutedExpertsConfig, TransformerConfig
    from katib_tpu.ops import flash_attention as fa
    from katib_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    n, k, e, held = 8192, 8, 2048, 32
    m = (n * k // gm.TILE + held + gm.CHUNK_TILES - 1) * gm.TILE       # 69,888 rows
    cfg = TransformerConfig(embed_dim=e, routed=RoutedExpertsConfig(
        router_width=256, experts_per_token=k, hidden=512, num_experts=held, routed_scale=2.5))
    layer = RoutedExperts(cfg)
    params = jax.eval_shape(
        lambda key: layer.init(key, jnp.zeros((1, 256, e), cfg.dtype))["params"], jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), params)
    x = jax.ShapeDtypeStruct((1, n, e), cfg.dtype, sharding=one_chip)

    def loss(params, x):
        return layer.apply({"params": params}, x).astype(jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(params, x).compile().as_text()
    per_assignment = (f"bf16[{n * k},{e}]", f"bf16[{n},{k},{e}]")
    gathers, selects = _instructions(text, "gather"), _instructions(text, "select")
    # the combine's forward and the dispatch's gradient; not the combine again
    assert sorted(g for g in gathers if g in per_assignment) == sorted(per_assignment)
    # the dispatch, the dispatch again (rematerialised), dy for the combine's gradient
    assert gathers.count(f"bf16[{m},{e}]") == 3
    # only the dispatch's gradient selects over rows: it reads what the kernels left unwritten
    rows = (f"[{n * k},{e}]", f"[{n},{k},{e}]", f"[{m},{e}]")
    assert [s for s in selects if s[s.index("["):] in rows] == [f"bf16[{n},{k},{e}]"]
    assert text.count("tpu_custom_call") == 12
    for name in ("expert_gmm_fwd", "expert_gmm_dlhs", "expert_gmm_dw"):
        assert name in text


def test_kda_kernels_compile_for_v5e(one_chip, monkeypatch):
    """The chunked delta-rule scan with its gradient at the hybrid cell's shape
    ([1, 8192, 32 heads, 128], bfloat16; the decay a channel in float32): the
    chunk parts (forward and backward), the three walks and the parts'
    pullback named for the trace, each a Pallas call; no triangular solve is
    left to XLA."""
    from katib_tpu.ops import flash_attention as fa
    from katib_tpu.ops.kda import kda

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)

    def loss(q, k, v, g, beta):
        return kda(q, k, v, g, beta).astype(jnp.float32).sum()

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)), *_kda_inputs(one_chip))
    assert text.count("tpu_custom_call") == 6
    for name in ("kda_chunks", "kda_fwd", "kda_bwd_states", "kda_bwd_grads", "kda_chunks_bwd"):
        assert name in text
    assert "triangular" not in text


def _kda_inputs(one_chip):
    x = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16, sharding=one_chip)
    g = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.float32, sharding=one_chip)
    beta = jax.ShapeDtypeStruct((1, 8192, 32), jnp.float32, sharding=one_chip)
    return x, x, x, g, beta


def test_kda_forward_leaves_its_chunk_parts_to_the_kernel(one_chip, monkeypatch):
    """The forward alone, compiled: the parts come from ``kda_chunks``, so no
    triangular solve is left to XLA and no float32 column decays
    [., 4 sub-chunks, 64, 128] are written around it."""
    from katib_tpu.ops import flash_attention as fa
    from katib_tpu.ops.kda import kda

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    text = jax.jit(kda).lower(*_kda_inputs(one_chip)).compile().as_text()
    assert "kda_chunks" in text and "kda_fwd" in text
    assert "triangular" not in text
    assert not re.findall(r"f32\[\d+,4,64,128\]", text)


def test_latent_flash_kernels_compile_for_v5e(one_chip, monkeypatch):
    """Latent attention's flash call: 32 heads, q and k of 192, v of 128, T 8192, causal."""
    from katib_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    qk = jax.ShapeDtypeStruct((1, 8192, 32, 192), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), qk, qk, v)
    assert text.count("tpu_custom_call") == 3
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in text
