"""Drop-free routed experts (models/transformer.py RoutedExperts) and the
grouped products they run on (ops/grouped_matmul.py): against the plain form —
a loop over the experts with a mask — under a deliberately skewed router; the
shares of a deployment adding up to the uncut layer; the Pallas kernels in
interpret mode against the same products written out."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from katib_tpu.models.transformer import (
    RoutedExperts, RoutedExpertsConfig, TransformerConfig, combine_rows, dispatch_plan, gather_rows,
    route)
from katib_tpu.ops import grouped_matmul as gm

E, F, WIDTH, PER_TOKEN = 32, 16, 16, 3


def _config(held=WIDTH, first=0, shared=0):
    return TransformerConfig(
        embed_dim=E, dtype=jnp.float32,
        routed=RoutedExpertsConfig(router_width=WIDTH, experts_per_token=PER_TOKEN, hidden=F,
                                   num_experts=held, first_expert=first, routed_scale=2.5,
                                   shared_hidden=shared))


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _plain(x, params, config):
    """Every held expert over every token, masked; the shared expert added."""
    routed = config.routed
    tokens = x.reshape(-1, E)
    scores = jax.nn.sigmoid(jnp.dot(tokens, params["router"]["kernel"], precision="highest"))
    top, chosen = jax.lax.top_k(scores, routed.experts_per_token)
    weights = routed.routed_scale * top / top.sum(-1, keepdims=True)
    out = jnp.zeros_like(tokens)
    landed = 0
    for e in range(routed.held):
        mine = chosen == routed.first_expert + e
        landed += int(mine.sum())
        w = jnp.where(mine, weights, 0.0).sum(-1, keepdims=True)
        out = out + w * _swiglu(tokens, params["gate"][e], params["up"][e], params["down"][e])
    if routed.shared_hidden:
        s = params["shared"]
        out = out + _swiglu(tokens, s["gate"]["kernel"], s["up"]["kernel"], s["down"]["kernel"])
    return out.reshape(x.shape), landed


def _skewed(params, x):
    """A router under which expert 1 is among the chosen of about half the
    tokens and expert 2 of none."""
    kernel = params["router"]["kernel"]
    tokens = x.reshape(-1, E)
    half = (jnp.arange(tokens.shape[0]) % 2 == 0).astype(jnp.float32)
    # a direction that separates even tokens from odd ones, found by least squares
    direction = jnp.linalg.lstsq(tokens, 40.0 * half - 20.0)[0]
    # the last feature is 1 for every token (the fixture sets it): a bias far below every score
    kernel = kernel.at[:, 1].set(direction).at[:, 2].set(0.0).at[-1, 2].set(-1e3)
    return dict(params, router={"kernel": kernel})


@pytest.fixture(scope="module")
def layer():
    config = _config(shared=F)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, E), jnp.float32).at[..., -1].set(1.0)
    params = RoutedExperts(config).init(jax.random.PRNGKey(0), x)["params"]
    return config, x, params


def test_routed_layer_is_the_loop_over_experts_under_a_skewed_router(layer):
    config, x, params = layer
    params = _skewed(params, x)
    out, mutated = RoutedExperts(config).apply({"params": params}, x, mutable=["intermediates"])
    want, landed = _plain(x, params, config)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    routing = mutated["intermediates"]["routing"][0]
    load = np.bincount(np.asarray(routing["chosen"]).reshape(-1), minlength=WIDTH + 1)[:WIDTH]
    n = x.shape[0] * x.shape[1]
    assert load[2] == 0 and abs(load[1] - n / 2) <= n / 8          # one expert starved, one with half the tokens
    assert load.sum() == n * PER_TOKEN == landed == int(routing["landed"])  # no token lost
    assert int(routing["load_max"]) == load.max() and float(routing["load_mean"]) == pytest.approx(load.mean())


def test_gradients_are_the_loop_s_too(layer):
    config, x, params = layer
    params = _skewed(params, x)

    def program(params, x):
        return jnp.sum(RoutedExperts(config).apply({"params": params}, x) ** 2)

    def plain(params, x):
        return jnp.sum(_plain(x, params, config)[0] ** 2)

    got, want = jax.grad(program, argnums=(0, 1))(params, x), jax.grad(plain, argnums=(0, 1))(params, x)
    flat_got, flat_want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for a, b in zip(flat_got, flat_want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer(layer):
    """The model-configs guide's test of a chip's share: 4 chips hold 4 of the
    16 experts each; what each adds, with the shared expert counted once,
    sums to the layer that holds them all."""
    config, x, params = layer
    whole = RoutedExperts(config).apply({"params": params}, x)
    shared_only = _plain(x, dict(params, gate=params["gate"][:0], up=params["up"][:0],
                                 down=params["down"][:0]), _config(held=0, shared=F))[0]
    total = shared_only
    landed = 0
    for chip in range(4):
        share = _config(held=4, first=4 * chip, shared=F)
        mine = dict(params, **{k: params[k][4 * chip: 4 * chip + 4] for k in ("gate", "up", "down")})
        out, mutated = RoutedExperts(share).apply({"params": mine}, x, mutable=["intermediates"])
        landed += int(mutated["intermediates"]["routing"][0]["landed"])
        total = total + (out - shared_only)
    np.testing.assert_allclose(total, whole, rtol=2e-5, atol=2e-5)
    assert landed == x.shape[0] * x.shape[1] * PER_TOKEN


def test_dispatch_plan_places_every_landed_assignment_once_on_its_expert_s_tiles():
    routed = _config(held=5, first=3).routed
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(3), (200, WIDTH)))
    _, local, _ = route(scores, routed)
    plan = jax.tree.map(np.asarray, dispatch_plan(local, 5, 8))
    n, k = local.shape
    assert plan["source"].shape[0] == (-(-n * k // 8) + 5) * 8      # room for any routing: no capacity
    dest, landed = plan["dest"][plan["landed"]], plan["landed"]
    assert len(set(dest.tolist())) == landed.sum() == (np.asarray(local) < 5).sum()
    # a row holds the assignment that was sent to it, on a tile of that assignment's expert
    assert np.array_equal(plan["source"][plan["dest"].reshape(-1)[landed.reshape(-1)]],
                          np.flatnonzero(landed.reshape(-1)))
    assert np.array_equal(plan["tile_group"][dest // 8], np.asarray(local)[landed])
    assert (plan["source"] < n * k).sum() == landed.sum()
    tiles_of = np.maximum(-(-plan["load"] // 8), 1)
    assert plan["num_tiles"] == tiles_of.sum() and plan["load"].sum() == landed.sum()
    used = plan["tile_group"][: plan["num_tiles"]]
    assert np.array_equal(np.bincount(used, minlength=5), tiles_of) and np.all(np.diff(used) >= 0)


def test_gather_rows_has_a_gather_for_a_gradient():
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 4))
    index = jnp.array([2, 2, 5, 0, 9])
    valid = jnp.array([True, True, True, False, False])
    readers = jnp.array([[0, 0], [0, 0], [0, 1], [0, 0], [0, 0], [2, 0]])
    readers_valid = jnp.array([[False, False]] * 2 + [[True, True]] + [[False, False]] * 2 + [[True, False]])
    want = jnp.where(valid[:, None], x[jnp.minimum(index, 5)], 0)
    np.testing.assert_array_equal(gather_rows(x, index, valid, readers, readers_valid), want)
    dy = jax.random.normal(jax.random.PRNGKey(1), (5, 4))
    got = jax.grad(lambda x: jnp.sum(gather_rows(x, index, valid, readers, readers_valid) * dy))(x)
    np.testing.assert_allclose(
        got, jax.grad(lambda x: jnp.sum(jnp.where(valid[:, None], x[jnp.minimum(index, 5)], 0) * dy))(x),
        rtol=1e-6)
    assert "scatter" not in str(jax.make_jaxpr(jax.grad(
        lambda x: jnp.sum(gather_rows(x, index, valid, readers, readers_valid))))(x))


# held experts, the expert given no token: one starved beside one of many tiles; expert 0 starved, so
# that row 0 pads its one tile; a share of the experts, so that most assignments land elsewhere
PLANS = {"expert_2_starved": (WIDTH, 2), "row_0_is_padding": (WIDTH, 0), "a_share_of_the_experts": (4, 2)}
PLAN_TILE = 8


def _skewed_plan(layer, case):
    """The plan of the fixture's tokens under the skewed router for a case of
    PLANS, and an ``out`` buffer that is zero where no assignment sits, as the
    kernels leave the padding rows of tiles in use."""
    config, x, params = layer
    held, starved = PLANS[case]
    kernel = _skewed(params, x)["router"]["kernel"]
    kernel = kernel.at[:, starved].set(0.0).at[-1, starved].set(-1e3)
    tokens = x.reshape(-1, E)
    scores = jax.nn.sigmoid(jnp.dot(tokens, kernel, precision="highest"))
    weights, local, _ = route(scores, _config(held=held).routed)
    plan = dispatch_plan(local, held, PLAN_TILE, spare=2)
    filled = plan["source"] < local.size
    load = np.asarray(plan["load"])
    assert load[starved] == 0 and load[1] > 4 * PLAN_TILE       # an empty expert, one of many tiles
    assert bool(filled[0]) == (starved != 0)
    assert bool(plan["landed"].all()) == (held == WIDTH)
    out = jnp.where(filled[:, None], jax.random.normal(jax.random.PRNGKey(5), (filled.shape[0], E)), 0.0)
    return tokens, weights, plan, filled, out


def _combine_written_out(out, weights, dest, landed):
    back = jnp.where(landed[..., None], jnp.take(out, dest, axis=0), 0.0)
    return jnp.sum(weights[..., None] * back, axis=1)


@pytest.mark.parametrize("case", sorted(PLANS))
def test_combine_rows_is_take_and_a_masked_weighted_sum_whatever_unused_tiles_hold(layer, case):
    """Value and both gradients against autodiff of the written-out form; then
    the same with NaN in every row the kernels never write."""
    _, weights, plan, filled, out = _skewed_plan(layer, case)
    dest, landed, source = plan["dest"], plan["landed"], plan["source"]
    cot = jax.random.normal(jax.random.PRNGKey(6), (weights.shape[0], E))

    def program(out, weights):
        return combine_rows(out, weights, dest, landed, source, filled)

    want, pullback_want = jax.vjp(lambda o, w: _combine_written_out(o, w, dest, landed), out, weights)
    got, pullback = jax.vjp(program, out, weights)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    (d_out, d_w), (d_out_want, d_w_want) = pullback(cot), pullback_want(cot)
    np.testing.assert_allclose(d_out, d_out_want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(d_w, d_w_want, rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(d_out[~filled]).max()) == 0.0
    assert landed.all() or float(jnp.abs(d_w[~landed]).max()) == 0.0

    unwritten = (jnp.arange(out.shape[0]) // PLAN_TILE >= plan["num_tiles"])[:, None]
    assert int(unwritten.sum()) >= 2 * PLAN_TILE                # the spare tiles at least
    poisoned = jnp.where(unwritten, jnp.nan, out)
    got_p, pullback_p = jax.vjp(program, poisoned, weights)
    d_out_p, d_w_p = pullback_p(cot)
    for a, b in ((got_p, got), (d_out_p, d_out), (d_w_p, d_w)):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("case", sorted(PLANS))
def test_the_dispatch_s_unfilled_rows_are_exactly_zero_and_no_select_passes_over_its_rows(layer, case):
    tokens, _, plan, filled, _ = _skewed_plan(layer, case)
    k = plan["dest"].shape[1]
    tokens = tokens.at[-1].set(jnp.inf)                         # what an unfilled row's clipped index would read

    def dispatch(tokens):
        return gather_rows(tokens, plan["source"] // k, filled, plan["dest"], plan["landed"])

    rows = dispatch(tokens)
    assert rows.shape == (filled.shape[0], E) and rows.dtype == tokens.dtype
    assert int((~filled).sum()) > 0
    np.testing.assert_array_equal(rows[~filled], 0.0)
    assert not bool(jnp.signbit(rows[~filled]).any())           # +0, to the bit
    np.testing.assert_array_equal(rows[filled], tokens[(plan["source"] // k)[filled]])
    wide_selects = [eqn for eqn in jax.make_jaxpr(dispatch)(tokens).eqns
                    if eqn.primitive.name == "select_n" and eqn.outvars[0].aval.ndim == 2]
    assert wide_selects == []


def _gathers(jaxpr, found):
    """Result shapes of every gather in ``jaxpr`` and the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            found.append(eqn.outvars[0].aval.shape)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)          # a ClosedJaxpr's jaxpr
                if hasattr(sub, "eqns"):
                    _gathers(sub, found)
    return found


def test_the_rematerialised_layer_gathers_the_assignments_rows_twice_not_three_times(layer):
    """Under ``jax.checkpoint`` the combine's own gradient leaves the second
    forward no use for the ``[N k, E]`` gather: the gradient's program holds
    the combine's gather (forward), the dispatch's gradient, and nothing else
    of N k rows; over the buffer's M rows the dispatch twice and ``dy`` once."""
    config, x, params = layer
    n = x.shape[0] * x.shape[1]
    m = (-(-n * PER_TOKEN // gm.TILE) + WIDTH + gm.CHUNK_TILES - 1) * gm.TILE

    def loss(params, x):
        return jnp.sum(RoutedExperts(config).apply({"params": params}, x) ** 2)

    shapes = _gathers(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x).jaxpr, [])
    assert sorted(s for s in shapes if len(s) > 1 and s[-1] == E) == sorted(
        [(n * PER_TOKEN, E), (n, PER_TOKEN, E)] + 3 * [(m, E)])


# -- the kernels, in interpret mode -------------------------------------------------------------

def _aligned(groups=4, k=64, n=32, seed=0, loads=(200, 0, 131, 128)):
    """Tile-aligned rows for ``loads`` rows a group (one empty, one that ends on a tile)."""
    tiles_of = [max(1, -(-load // gm.TILE)) for load in loads]
    tile_group = np.repeat(np.arange(groups), tiles_of)
    spare = gm.CHUNK_TILES - 1  # tiles past the ones in use, never computed: a chunk may reach into them
    rows = np.zeros(((len(tile_group) + spare) * gm.TILE, k), np.float32)
    rng = np.random.default_rng(seed)
    start = 0
    for load, tiles in zip(loads, tiles_of):
        rows[start: start + load] = rng.normal(size=(load, k))
        start += tiles * gm.TILE
    tile_group = np.concatenate([tile_group, np.zeros(spare, np.int64)]).astype(np.int32)
    w = rng.normal(size=(groups, k, n)).astype(np.float32)
    return jnp.asarray(rows), jnp.asarray(w), jnp.asarray(tile_group), jnp.int32(sum(tiles_of))


def _by_tile(rows, w, tile_group, num_tiles):
    tiles = rows.reshape(-1, gm.TILE, rows.shape[1])
    out = jnp.einsum("tmk,tkn->tmn", tiles, w[tile_group], precision="highest")
    return jnp.where(jnp.arange(tiles.shape[0])[:, None, None] < num_tiles, out, 0).reshape(rows.shape[0], -1)


# a group of one chunk or less; groups of several chunks, whole and not (a chunk is CHUNK_TILES tiles)
LOADS = {"within_a_chunk": (200, 0, 131, 128), "over_chunks": (900, 0, 3 * 128 + 1, 2 * 3 * 128)}


@pytest.mark.parametrize("loads", sorted(LOADS))
@pytest.mark.parametrize("interpret", [True, False], ids=["kernels", "off_the_chip"])
def test_grouped_products_forward_and_both_gradients(interpret, loads):
    rows, w, tile_group, num_tiles = _aligned(loads=LOADS[loads])
    used = (jnp.arange(rows.shape[0]) < num_tiles * gm.TILE)[:, None]
    cot = jax.random.normal(jax.random.PRNGKey(2), (rows.shape[0], w.shape[2]))

    def program(rows, w):  # rows past the tiles in use are unspecified: never read
        return jnp.where(used, gm.grouped_matmul(rows, w, tile_group, num_tiles, interpret=interpret), 0)

    out, pullback = jax.vjp(program, rows, w)
    want, pullback_want = jax.vjp(lambda rows, w: _by_tile(rows, w, tile_group, num_tiles), rows, w)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    (drows, dw), (drows_want, dw_want) = pullback(cot), pullback_want(cot)
    np.testing.assert_allclose(jnp.where(used, drows, 0), drows_want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dw, dw_want, rtol=1e-5, atol=1e-4)
    assert float(jnp.abs(dw[1]).max()) == 0.0  # the empty group's gradient is written, as zeros


@pytest.mark.parametrize("loads", sorted(LOADS))
def test_chunk_plan_walks_every_tile_in_use_once_and_never_leaves_a_group(loads):
    rows, w, tile_group, num_tiles = _aligned(loads=LOADS[loads])
    group, tile, valid, num = jax.tree.map(np.asarray, gm.chunk_plan(tile_group, num_tiles.reshape(1), w.shape[0]))
    assert len(group) == -(-len(tile_group) // gm.CHUNK_TILES) + w.shape[0]  # room for any routing
    seen = []
    for c in range(int(num[0])):
        assert 1 <= valid[c] <= gm.CHUNK_TILES
        assert tile[c] + gm.CHUNK_TILES <= len(tile_group)  # a step reads CHUNK_TILES tiles: inside the buffer
        own = list(range(tile[c], tile[c] + valid[c]))
        assert all(np.asarray(tile_group)[t] == group[c] for t in own)
        seen += own
    assert seen == list(range(int(num_tiles)))
    assert sorted(set(group[: int(num[0])])) == list(range(w.shape[0]))  # every group has a chunk, the empty one too


def test_the_kernels_are_named_for_the_trace():
    rows, w, tile_group, num_tiles = _aligned()
    text = str(jax.make_jaxpr(jax.grad(
        lambda rows, w: gm.grouped_matmul(rows, w, tile_group, num_tiles, interpret=True).sum(),
        argnums=(0, 1)))(rows, w))
    for name in ("expert_gmm_fwd", "expert_gmm_dlhs", "expert_gmm_dw"):
        assert name in text


def test_rows_that_are_not_whole_tiles_are_refused():
    rows, w, tile_group, num_tiles = _aligned()
    with pytest.raises(ValueError, match="whole tiles"):
        gm.grouped_matmul(rows[:-1], w, tile_group, num_tiles)
