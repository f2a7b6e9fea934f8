"""Batched, jitted suggestion kernels — the vectorized suggestion plane.

ISSUE 10 tentpole: the hot suggesters (TPE, CMA-ES, GP-BO) are per-call
NumPy loops — TPE re-runs its constant-liar KDE scoring once per requested
assignment, CMA-ES replays every folded generation through a Python
``update`` with an eigendecomposition each, and BO grid-searches 18 kernel
hyperparameter combos with one O(n^3) Cholesky apiece before brute-forcing
the acquisition one pick at a time. At production trial rates that is the
control-plane bottleneck (ROADMAP item 5). This module re-expresses the
identical math as batched jitted programs:

- :func:`tpe_batch` — ONE ``lax.scan`` emits a whole suggestion batch: the
  good/bad Parzen log-densities are scored for all M candidates of all B
  picks against all history centers as masked matrix ops, and the
  constant-liar feedback (pick i's selection becomes a bad-set kernel
  center for picks > i) is a carry update inside the scan, not a Python
  ``np.vstack`` loop.
- :func:`cma_replay` — the full generation-replay fold (mean/sigma/C/paths)
  runs as one ``lax.scan`` over the padded per-generation populations with
  exactly one eigendecomposition per generation.
- :func:`bo_mle` / :func:`bo_batch` — the marginal-likelihood grid is one
  vmapped Cholesky over all (length, noise) combos, and the per-pick GP
  posterior + EI/PI/LCB (or gp_hedge nomination) acquisition argmax is a
  single jitted scan with the constant-liar rows activated in-carry.

Parity contract: the legacy NumPy implementations stay the oracle. Every
stochastic draw (candidate sampling, local jitter, hedge member choice,
CMA z) is made on the host with the SAME numpy Generator calls in the SAME
order as the legacy loop, so the vectorized kernels reproduce the oracle's
selections up to floating-point tolerance (tests/test_suggest_vectorized.py
asserts this per algorithm). Kernels run in float64 via the
``jax.enable_x64`` scope so that tolerance is ~1e-12, not
float32 noise. Inputs are padded to power-of-two shape buckets so history
growth retraces O(log n) times per experiment, not per call.

Gating: ``runtime.vector_suggest`` / ``KATIB_TPU_VECTOR_SUGGEST`` (default
on); an install without JAX runs the legacy path. Each entry point returns
``None`` whenever the call falls outside its parity-exact fast path (cold
history, degenerate good/bad split, restart strategies) and the caller runs
the NumPy oracle.
"""

from __future__ import annotations

import functools
import math
import os
from typing import List, Optional, Sequence, Tuple

import numpy as np

_FALSY = ("0", "false", "off")

ENV_FLAG = "KATIB_TPU_VECTOR_SUGGEST"

# None = consult the environment (standalone suggester use); the controller
# stamps the runtime.vector_suggest knob here at construction.
_ENABLED: Optional[bool] = None

_LOG_2PI = math.log(2.0 * math.pi)


def set_enabled(on: bool) -> None:
    """One switch for every kernel consumer (the semantic_analysis /
    fused_population pattern): ExperimentController stamps the
    runtime.vector_suggest knob; tests flip it around the parity oracle."""
    global _ENABLED
    _ENABLED = bool(on)


def enabled() -> bool:
    if _ENABLED is not None:
        return _ENABLED
    return os.environ.get(ENV_FLAG, "1").lower() not in _FALSY


@functools.lru_cache(maxsize=1)
def _jax():
    """(jax, jnp) or None — an install without JAX gates to the legacy NumPy
    path. The persistent compile cache is switched on here as well: JAX
    decides at the process's first compile whether the cache is in use, and
    with warm-start history that first compile can be a suggestion kernel."""
    try:
        import jax
        import jax.numpy as jnp
    except ImportError:
        return None
    from ..utils.compilation import enable_compilation_cache

    enable_compilation_cache()
    return jax, jnp


def available() -> bool:
    return _jax() is not None


def use_vectorized() -> bool:
    return enabled() and available()


def _bucket(n: int, minimum: int = 8) -> int:
    """Shape bucket ladder: powers of two up to 64, then ~1.25x geometric
    steps rounded to multiples of 32. History growth retraces O(log n)
    times per experiment (the KTC1xx recompile-hazard discipline applied
    to the suggestion plane) while capping padding waste at ~25% — a
    straight power-of-two ladder wastes up to 2x on the O(n^2) GP solves."""
    b = max(1, minimum)
    while b < n:
        b = b * 2 if b < 64 else int(math.ceil(b * 1.25 / 32) * 32)
    return b


# ---------------------------------------------------------------------------
# TPE: batched good/bad KDE scoring with in-scan constant liar
# ---------------------------------------------------------------------------


# Refinement width: when a pick's float32 screening margin is too small to
# certify the argmax, the f64 pass rescores this many shortlisted
# candidates (per dimension for independent TPE, jointly for multivariate).
TPE_TOP_K = 2
# Screening-confidence margin: the f32 direct-sum density scores carry
# ~n·eps32 ≈ 3e-5 absolute error on the log scale; a best-vs-runner-up gap
# above this threshold (~300x that error) certifies that the f32 argmax is
# the f64 argmax and the refinement branch is skipped entirely
# (lax.cond — the skipped branch never executes on CPU).
TPE_SCREEN_MARGIN = 1e-2


@functools.lru_cache(maxsize=None)
def _tpe_program(multivariate: bool):
    jax, jnp = _jax()

    def run(xs0, cands, good_mask, bad_mask, bw_good, bw_bad, n_good, n_bad):
        # xs0 [Np, D] f64 padded history; cands [Bp, M, D] f64; masks
        # [Bp, Np]; bw/n arrays [Bp]. Mixed-precision screening: the
        # O(B·M·N·D) density work runs once, batched, in float32 (XLA's
        # f32 transcendentals vectorize; f64 ones do not) and with ONE exp
        # per (pick, candidate, center, dim) — each center is either good
        # or bad, so the per-center inverse bandwidth is selected by mask
        # and the two densities are two masked sums over the same kernel
        # array. exp(-z²/2) with z ≤ 1/0.05 never underflows to a degree
        # that matters: the direct sum needs no max shift.
        bp, m, d = cands.shape
        f32 = jnp.float32

        xs32 = xs0.astype(f32)
        c32 = cands.astype(f32)
        inv2g = (0.5 / (bw_good**2)).astype(f32)           # [Bp]
        inv2b = (0.5 / (bw_bad**2)).astype(f32)
        s_pc = jnp.where(
            good_mask, inv2g[:, None], inv2b[:, None]
        )                                                   # [Bp, Np]

        diff2 = (c32[:, :, None, :] - xs32[None, None, :, :]) ** 2
        kern = jnp.exp(-diff2 * s_pc[:, None, :, None])     # [Bp, M, Np, D]
        tiny = jnp.asarray(1e-30, f32)
        sum_g = (kern * good_mask[:, None, :, None]).sum(axis=2)
        sum_b = (kern * bad_mask[:, None, :, None]).sum(axis=2)

        def _logmeansum64(points, mask, c, bw, n):
            """Legacy _kde_logpdf (max-shift log-mean-exp) in f64 over the
            center axis: points [P, D], mask [P], c [K, D] per-dim values.
            Returns the UN-combined log(sum exp / n) [K, D]; all-masked
            columns (zero active liars) yield -inf, not NaN."""
            diff = c[None, :, :] - points[:, None, :]        # [P, K, D]
            logk = (-0.5 * _LOG_2PI - jnp.log(bw)) - 0.5 * (diff / bw) ** 2
            logk = jnp.where(mask[:, None, None], logk, -jnp.inf)
            mx = jnp.max(logk, axis=0)
            mx_safe = jnp.where(jnp.isfinite(mx), mx, 0.0)
            return mx_safe + jnp.log(
                jnp.sum(jnp.exp(logk - mx_safe[None]), axis=0) / n
            )

        def step(liars, per_pick):
            (cands_i, c32_i, sg32_i, sb32_i, gm_i, bm_i,
             bwg_i, bwb_i, inv2b_i, ng, nb, idx) = per_pick
            liar_on = jnp.arange(bp) < idx
            # f32 liar correction: [M, Bp, D] direct kernel sums
            diffl2 = (c32_i[:, None, :] - liars.astype(f32)[None, :, :]) ** 2
            kern_l = jnp.exp(-diffl2 * inv2b_i.astype(f32))
            sum_l = (kern_l * liar_on.astype(f32)[None, :, None]).sum(axis=1)
            score32 = (
                jnp.log(sg32_i + tiny)
                - jnp.log(bwg_i.astype(f32))
                - jnp.log(ng).astype(f32)
            ) - (
                jnp.log(sb32_i + sum_l + tiny)
                - jnp.log(bwb_i.astype(f32))
                - jnp.log(nb).astype(f32)
            )                                                   # [M, D]

            # confidence gate: a screening margin far above the f32 error
            # certifies the argmax; only uncertain picks pay the f64
            # refinement (the untaken cond branch never executes)
            if multivariate:
                joint32 = score32.sum(axis=1)
                top2_v, top2_i = jax.lax.top_k(joint32, min(2, m))
            else:
                top2_v, top2_i = jax.lax.top_k(score32.T, min(2, m))  # [D, 2]
            margin_ok = jnp.all(
                (top2_v[..., 0] - top2_v[..., -1]) > TPE_SCREEN_MARGIN
            ) & jnp.all(jnp.isfinite(top2_v))

            def certified(_):
                if multivariate:
                    return cands_i[top2_i[0]]
                return jnp.take_along_axis(
                    cands_i.T, top2_i[:, :1], axis=1
                )[:, 0]

            def refine(_):
                # f64 rescoring of the shortlist; indices re-sorted
                # ascending so the final argmax keeps the legacy
                # first-index tie-break. ck [K, D]: per-dim values for
                # independent TPE (column d mixes candidates), full
                # candidate vectors for multivariate.
                kk = min(TPE_TOP_K, m)
                if multivariate:
                    _, top = jax.lax.top_k(joint32, kk)
                    ck = cands_i[jnp.sort(top)]                 # [K, D]
                else:
                    _, top = jax.lax.top_k(score32.T, kk)       # [D, K]
                    ck = jnp.take_along_axis(
                        cands_i.T, jnp.sort(top, axis=1), axis=1
                    ).T                                         # [K, D]
                lg = _logmeansum64(xs0, gm_i, ck, bwg_i, ng)
                lse_b = _logmeansum64(xs0, bm_i, ck, bwb_i, nb)
                lse_l = _logmeansum64(liars, liar_on, ck, bwb_i, nb)
                per_dim64 = lg - jnp.logaddexp(lse_b, lse_l)    # [K, D]
                if multivariate:
                    return ck[jnp.argmax(per_dim64.sum(axis=1))]
                return jnp.take_along_axis(
                    ck, jnp.argmax(per_dim64, axis=0)[None, :], axis=0
                )[0]

            u = jax.lax.cond(margin_ok, certified, refine, None)
            return liars.at[idx].set(u), u

        per_pick = (
            cands, c32, sum_g, sum_b, good_mask, bad_mask,
            bw_good, bw_bad, inv2b, n_good, n_bad, jnp.arange(bp),
        )
        _, us = jax.lax.scan(step, jnp.zeros((bp, d)), per_pick)
        return us

    return jax.jit(run)


def _parzen_bw(n: int) -> float:
    """Legacy _kde_logpdf / _sample_from_kernels bandwidth, exactly."""
    return max(max(n, 1) ** (-0.2) * 0.5, 0.05)


def tpe_batch(
    xs: np.ndarray,
    ys: np.ndarray,
    minimize: bool,
    gamma: float,
    n_candidates: int,
    batch: int,
    rng: np.random.Generator,
    multivariate: bool,
) -> Optional[np.ndarray]:
    """Vectorized equivalent of ``batch`` sequential ``_tpe_point`` picks
    with the constant-liar append. Returns the selected unit-cube points
    [batch, D], or None when the call falls outside the parity-exact fast
    path (the caller runs the legacy loop).

    Why the fast path is exact: the liar rows always carry the worst
    observed objective, so a stable argsort keeps them at the tail of the
    good/bad split — the good set of pick i is a pure function of the
    ORIGINAL history and i, which lets every pick's candidate batch be
    drawn up front with the identical rng call sequence
    (``integers(0, n_good_i, M)`` then ``normal(0, bw, (M, D))``). Only the
    bad-set density depends on earlier selections, and that dependence is
    the scan carry. The path is declined when a pick's good set would have
    to include liar rows (n_good_i > n0) or its bad set would be empty —
    both only reachable with degenerate gamma/history combinations.
    """
    if not use_vectorized():
        return None
    n0, d = xs.shape
    if n0 == 0 or batch <= 0:
        return None
    m = int(n_candidates)
    order0 = np.argsort(ys if minimize else -ys, kind="stable")

    n_goods = []
    for i in range(batch):
        ng = max(1, int(np.ceil(gamma * (n0 + i))))
        if ng > n0 or (n0 - ng + i) < 1:
            return None  # liar would enter the good set / bad set empty
        n_goods.append(ng)

    np_pad = _bucket(n0)
    bp = _bucket(batch, minimum=1)
    cands = np.empty((bp, m, d), dtype=np.float64)
    good_mask = np.zeros((bp, np_pad), dtype=bool)
    bad_mask = np.zeros((bp, np_pad), dtype=bool)
    bw_good = np.empty(bp, dtype=np.float64)
    bw_bad = np.empty(bp, dtype=np.float64)
    n_good = np.empty(bp, dtype=np.float64)
    n_bad = np.empty(bp, dtype=np.float64)
    for i in range(batch):
        ng = n_goods[i]
        nb = n0 - ng + i
        good = xs[order0[:ng]]
        bw = _parzen_bw(ng)
        # exact legacy rng sequence: _sample_from_kernels(good, rng, m)
        centers = good[rng.integers(0, ng, size=m)]
        samples = centers + rng.normal(0.0, bw, size=(m, d))
        samples = np.abs(samples)
        samples = 1.0 - np.abs(1.0 - samples)
        cands[i] = np.clip(samples, 0.0, 1.0 - 1e-9)
        good_mask[i, order0[:ng]] = True
        bad_mask[i, order0[ng:]] = True
        bw_good[i] = bw
        bw_bad[i] = _parzen_bw(nb)
        n_good[i] = float(ng)
        n_bad[i] = float(nb)
    for i in range(batch, bp):  # inactive pad picks replay the last real one
        cands[i] = cands[batch - 1]
        good_mask[i] = good_mask[batch - 1]
        bad_mask[i] = bad_mask[batch - 1]
        bw_good[i] = bw_good[batch - 1]
        bw_bad[i] = bw_bad[batch - 1]
        n_good[i] = n_good[batch - 1]
        n_bad[i] = n_bad[batch - 1]
    xs_pad = np.zeros((np_pad, d), dtype=np.float64)
    xs_pad[:n0] = xs

    with _jax()[0].enable_x64():
        us = _tpe_program(multivariate)(
            xs_pad, cands, good_mask, bad_mask, bw_good, bw_bad, n_good, n_bad
        )
        out = np.asarray(us, dtype=np.float64)
    return out[:batch]


# ---------------------------------------------------------------------------
# CMA-ES: generation replay as one scan, one eigendecomposition per step
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _cma_program(dim: int, mu0: int):
    jax, jnp = _jax()
    d = float(dim)
    chi_n = math.sqrt(d) * (1 - 1 / (4 * d) + 1 / (21 * d * d))

    def step(carry, per_gen):
        mean, sigma, C, p_sigma, p_c, gen = carry
        xs_g, ys_g, count = per_gen
        k = jnp.minimum(mu0, count)
        # legacy weights()[:mu] renormalized == masked prefix renormalized
        w_base = jnp.log(mu0 + 0.5) - jnp.log(jnp.arange(1, mu0 + 1))
        w_base = w_base / w_base.sum()
        w = jnp.where(jnp.arange(mu0) < k, w_base, 0.0)
        w = w / jnp.maximum(w.sum(), 1e-300)
        mu_eff = 1.0 / jnp.maximum((w**2).sum(), 1e-300)

        c_sigma = (mu_eff + 2) / (d + mu_eff + 5)
        d_sigma = (
            1
            + 2 * jnp.maximum(0.0, jnp.sqrt((mu_eff - 1) / (d + 1)) - 1)
            + c_sigma
        )
        c_c = (4 + mu_eff / d) / (d + 4 + 2 * mu_eff / d)
        c_1 = 2 / ((d + 1.3) ** 2 + mu_eff)
        c_mu = jnp.minimum(
            1 - c_1, 2 * (mu_eff - 2 + 1 / mu_eff) / ((d + 2) ** 2 + mu_eff)
        )

        order = jnp.argsort(ys_g)  # +inf pads sort last
        ys_sel = (xs_g[order[:mu0]] - mean) / sigma
        y_w = (w[:, None] * ys_sel).sum(axis=0)
        mean_new = mean + sigma * y_w

        eigval, eigvec = jnp.linalg.eigh(C)
        eigval = jnp.maximum(eigval, 1e-20)
        inv_sqrt = (eigvec * (eigval**-0.5)[None, :]) @ eigvec.T

        p_sigma_new = (1 - c_sigma) * p_sigma + jnp.sqrt(
            c_sigma * (2 - c_sigma) * mu_eff
        ) * (inv_sqrt @ y_w)
        ps_norm = jnp.linalg.norm(p_sigma_new)
        h_sigma = ps_norm / jnp.sqrt(
            1 - jnp.power(1 - c_sigma, 2.0 * (gen + 1.0))
        ) < (1.4 + 2 / (d + 1)) * chi_n
        p_c_new = (1 - c_c) * p_c + jnp.where(
            h_sigma, jnp.sqrt(c_c * (2 - c_c) * mu_eff), 0.0
        ) * y_w

        rank_mu = (
            w[:, None, None] * (ys_sel[:, :, None] @ ys_sel[:, None, :])
        ).sum(axis=0)
        delta_h = (1 - h_sigma.astype(C.dtype)) * c_c * (2 - c_c)
        C_new = (
            (1 - c_1 - c_mu) * C
            + c_1 * (jnp.outer(p_c_new, p_c_new) + delta_h * C)
            + c_mu * rank_mu
        )
        sigma_new = sigma * jnp.exp((c_sigma / d_sigma) * (ps_norm / chi_n - 1))
        sigma_new = jnp.clip(sigma_new, 1e-8, 1e4)

        # an empty generation only advances the counter (legacy mu == 0 /
        # `if done:` else branch)
        empty = count == 0
        mean = jnp.where(empty, mean, mean_new)
        sigma = jnp.where(empty, sigma, sigma_new)
        C = jnp.where(empty, C, C_new)
        p_sigma = jnp.where(empty, p_sigma, p_sigma_new)
        p_c = jnp.where(empty, p_c, p_c_new)
        return (mean, sigma, C, p_sigma, p_c, gen + 1.0), None

    def run(mean0, sigma0, xs_gens, ys_gens, counts):
        carry = (
            mean0,
            sigma0,
            jnp.eye(dim, dtype=mean0.dtype),
            jnp.zeros(dim, dtype=mean0.dtype),
            jnp.zeros(dim, dtype=mean0.dtype),
            jnp.asarray(0.0, dtype=mean0.dtype),
        )
        (mean, sigma, C, p_sigma, p_c, _gen), _ = jax.lax.scan(
            step, carry, (xs_gens, ys_gens, counts)
        )
        return mean, sigma, C, p_sigma, p_c

    return jax.jit(run)


def cma_replay(
    generations: Sequence[Tuple[np.ndarray, np.ndarray]],
    dim: int,
    popsize: int,
    sigma0: float,
    mean0: np.ndarray,
) -> Optional[Tuple[np.ndarray, float, np.ndarray, np.ndarray, np.ndarray]]:
    """Fold every completed generation in one compiled scan. ``generations``
    is the ordered list of (xs [n_g, D], internal-minimize fitness [n_g])
    pairs, possibly empty per slot. Returns (mean, sigma, C, p_sigma, p_c)
    after the fold, or None outside the fast path (no folded generations,
    or JAX unavailable). Restart strategies are the caller's problem: the
    scan models the restart-free trajectory only."""
    if not use_vectorized() or not generations:
        return None
    mu0 = popsize // 2
    if mu0 < 1:
        return None
    g = len(generations)
    p_max = max(popsize, max((len(y) for _, y in generations), default=1), 1)
    xs_gens = np.zeros((g, p_max, dim), dtype=np.float64)
    ys_gens = np.full((g, p_max), np.inf, dtype=np.float64)
    counts = np.zeros(g, dtype=np.float64)
    for i, (xg, yg) in enumerate(generations):
        n = len(yg)
        if n:
            xs_gens[i, :n] = xg
            ys_gens[i, :n] = yg
        counts[i] = float(n)

    with _jax()[0].enable_x64():
        mean, sigma, C, p_sigma, p_c = _cma_program(dim, mu0)(
            np.asarray(mean0, dtype=np.float64),
            np.float64(sigma0),
            xs_gens,
            ys_gens,
            counts,
        )
        return (
            np.asarray(mean, dtype=np.float64),
            float(sigma),
            np.asarray(C, dtype=np.float64),
            np.asarray(p_sigma, dtype=np.float64),
            np.asarray(p_c, dtype=np.float64),
        )


# ---------------------------------------------------------------------------
# GP-BO: vmapped marginal-likelihood grid + jitted acquisition scan
# ---------------------------------------------------------------------------


def _matern52_jnp(jnp, a, b, length):
    # ||a-b||² via the gemm identity: the [n, m] inner-product matrix is
    # one dot_general instead of an [n, m, D] broadcast-reduce — the
    # difference between BLAS speed and an elementwise walk for the big
    # candidate cross-covariance blocks. Cancellation can go slightly
    # negative; the 1e-300 clamp (shared with the legacy kernel) absorbs it.
    d2 = (
        (a**2).sum(-1)[:, None]
        + (b**2).sum(-1)[None, :]
        - 2.0 * (a @ b.T)
    )
    dist = jnp.sqrt(jnp.maximum(d2, 1e-300)) / length
    s5 = math.sqrt(5.0)
    return (1.0 + s5 * dist + 5.0 / 3.0 * dist * dist) * jnp.exp(-s5 * dist)


@functools.lru_cache(maxsize=None)
def _bo_mle_program():
    jax, jnp = _jax()
    s5 = math.sqrt(5.0)

    def run(xs, ys, mask, n, lengths, noises):
        mean = (ys * mask).sum() / n
        std = jnp.sqrt((mask * (ys - mean) ** 2).sum() / n) + 1e-12
        ysn = jnp.where(mask, (ys - mean) / std, 0.0)
        # the pairwise distances are length-independent: computed once and
        # shared by all 18 (length, noise) combos (the legacy grid rebuilds
        # the [n, n, D] differences per combo)
        d2 = ((xs[:, None, :] - xs[None, :, :]) ** 2).sum(-1)
        dist0 = jnp.sqrt(jnp.maximum(d2, 1e-300))
        both = mask[:, None] & mask[None, :]

        def lml_one(length, noise):
            dd = dist0 / length
            k = (1.0 + s5 * dd + 5.0 / 3.0 * dd * dd) * jnp.exp(-s5 * dd)
            k = jnp.where(both, k, 0.0)
            # padded rows collapse to the identity block: unit pivots add
            # zero log-det and zero alpha, so the masked lml is exact
            diag = jnp.where(mask, jnp.diag(k) + noise, 1.0)
            k = k - jnp.diag(jnp.diag(k)) + jnp.diag(diag)
            chol = jnp.linalg.cholesky(k)
            ok = ~jnp.any(jnp.isnan(chol))
            alpha = jax.scipy.linalg.cho_solve((chol, True), ysn)
            log_det = 2.0 * jnp.log(jnp.maximum(jnp.diag(chol), 1e-300)).sum()
            lml = -0.5 * ysn @ alpha - 0.5 * log_det - 0.5 * n * _LOG_2PI
            return jnp.where(ok, lml, -jnp.inf)

        return jax.vmap(lml_one)(lengths, noises)

    return jax.jit(run)


def bo_mle(
    xs: np.ndarray,
    ys: np.ndarray,
    length_grid: Sequence[float],
    noise_grid: Sequence[float],
) -> Optional[Tuple[float, float]]:
    """All 18 (length, noise) marginal-likelihood fits as ONE vmapped
    Cholesky batch. Returns the argmax combo in the legacy grid order
    (length-major, first-best wins), or None off the fast path."""
    if not use_vectorized():
        return None
    n = len(ys)
    if n < 2:
        return None
    np_pad = _bucket(n)
    d = xs.shape[1]
    xs_pad = np.zeros((np_pad, d), dtype=np.float64)
    xs_pad[:n] = xs
    ys_pad = np.zeros(np_pad, dtype=np.float64)
    ys_pad[:n] = ys
    mask = np.zeros(np_pad, dtype=bool)
    mask[:n] = True
    combos = [(l, s) for l in length_grid for s in noise_grid]
    lengths = np.array([c[0] for c in combos], dtype=np.float64)
    noises = np.array([c[1] for c in combos], dtype=np.float64)

    with _jax()[0].enable_x64():
        lmls = np.asarray(
            _bo_mle_program()(
                xs_pad, ys_pad, mask, np.float64(n), lengths, noises
            )
        )
    if not np.isfinite(lmls).any():
        return None  # every combo failed; legacy falls back to defaults
    best = int(np.argmax(lmls))
    return combos[best]


@functools.lru_cache(maxsize=None)
def _bo_acquire_program(acq: str):
    jax, jnp = _jax()
    from jax.scipy.linalg import solve_triangular
    from jax.scipy.stats import norm

    members = ("ei", "pi", "lcb") if acq == "gp_hedge" else (acq,)

    def scores(kind, mu, sigma, y_best):
        if kind == "lcb":
            return -(mu - 1.96 * sigma)
        imp = y_best - mu
        z = imp / sigma
        if kind == "pi":
            return norm.cdf(z)
        return imp * norm.cdf(z) + sigma * norm.pdf(z)  # ei

    def run(
        xs0, ys0, mask0, n0, cands, member_idx,
        length, noise, liar_y, y_best,
    ):
        # Incremental block-Cholesky formulation, f64 end to end: the liar
        # rows a pick adds are a bordered extension of the base kernel
        # matrix, so the O(n^3) factorization and the O(n^2·B·M) candidate
        # solves happen ONCE for the whole batch and each pick only
        # factors/solves the tiny [Bp, Bp] liar block — against the legacy
        # loop's per-pick full refit (B·O(n^3)) and both-triangle
        # cho_solves (4x the solve flops). Block Cholesky IS the Cholesky
        # of the extended matrix, so the posterior is the exact legacy one.
        # No float32 screening here: GP variances with noise ~1e-6 sit
        # below f32 resolution (cond ~ 1/noise), and LCB/EI rankings near
        # exploited clusters genuinely depend on them.
        bp, m, d = cands.shape

        k0 = _matern52_jnp(jnp, xs0, xs0, length)
        both = mask0[:, None] & mask0[None, :]
        k0 = jnp.where(both, k0, 0.0)
        diag = jnp.where(mask0, jnp.diag(k0) + noise, 1.0)
        k0 = k0 - jnp.diag(jnp.diag(k0)) + jnp.diag(diag)
        L0 = jnp.linalg.cholesky(k0)

        ys0m = jnp.where(mask0, ys0, 0.0)
        ones0 = mask0.astype(ys0.dtype)
        cy0 = solve_triangular(L0, ys0m, lower=True)      # L0^-1 y_raw
        c10 = solve_triangular(L0, ones0, lower=True)     # L0^-1 1
        s_y0 = ys0m.sum()
        s_y2_0 = (ys0m**2).sum()

        # every pick's candidate cross-covariances in one batched solve
        ks_all = _matern52_jnp(jnp, cands.reshape(bp * m, d), xs0, length)
        ks_all = jnp.where(mask0[None, :], ks_all, 0.0)
        w_all = solve_triangular(L0, ks_all.T, lower=True)  # [Np, Bp*M]
        w_all = jnp.moveaxis(w_all.reshape(-1, bp, m), 1, 0)  # [Bp, Np, M]

        eye_b = jnp.eye(bp, dtype=ys0.dtype)

        def step(carry, per_pick):
            # m_mat = L0^-1 k(X0, liars) is carried and grown one column
            # per pick (a single-rhs solve) instead of being re-derived
            # from scratch — the bordered factorization is incremental by
            # construction. Inactive columns are zero.
            liars, i, m_mat = carry  # i: int32 pick index (liars < i live)
            cands_i, w_i, midx = per_pick  # w_i [Np, M]
            liar_on = jnp.arange(bp) < i
            onf = liar_on.astype(ys0.dtype)

            # bordered extension: K_ext = [[K0, B],[B^T, C]]
            c_small = _matern52_jnp(jnp, liars, liars, length) + noise * eye_b
            on2 = liar_on[:, None] & liar_on[None, :]
            schur = c_small - m_mat.T @ m_mat
            schur = jnp.where(on2, schur, eye_b)  # inactive rows: identity
            Lc = jnp.linalg.cholesky(schur)

            k_lc = _matern52_jnp(jnp, cands_i, liars, length)  # [M, Bp]
            k_lc = jnp.where(liar_on[None, :], k_lc, 0.0)
            w_bot = solve_triangular(
                Lc, k_lc.T - m_mat.T @ w_i, lower=True
            )                                                   # [Bp, M]
            cy_bot = solve_triangular(Lc, onf * liar_y - m_mat.T @ cy0, lower=True)
            c1_bot = solve_triangular(Lc, onf - m_mat.T @ c10, lower=True)

            # posterior over this pick's candidates: mu needs no y-scale —
            # A = Ks K^-1 y_raw, Bv = Ks K^-1 1, mu = A + mean·(1 - Bv)
            a_vec = w_i.T @ cy0 + w_bot.T @ cy_bot
            b_vec = w_i.T @ c10 + w_bot.T @ c1_bot
            n = n0 + i
            sum_y = s_y0 + onf.sum() * liar_y
            sum_y2 = s_y2_0 + onf.sum() * liar_y**2
            mean = sum_y / n
            std = jnp.sqrt(jnp.maximum(sum_y2 / n - mean**2, 0.0)) + 1e-12
            mu = a_vec + mean * (1.0 - b_vec)
            var = jnp.maximum(
                1.0 - (w_i**2).sum(axis=0) - (w_bot**2).sum(axis=0), 1e-12
            )
            sigma = jnp.sqrt(var) * std

            noms = jnp.stack(
                [
                    cands_i[jnp.argmax(scores(a, mu, sigma, y_best))]
                    for a in members
                ]
            )
            u = noms[midx] if acq == "gp_hedge" else noms[0]
            # grow the carried factor by the new liar's column
            b_col = _matern52_jnp(jnp, xs0, u[None, :], length)[:, 0]
            b_col = jnp.where(mask0, b_col, 0.0)
            m_col = solve_triangular(L0, b_col, lower=True)
            return (liars.at[i].set(u), i + 1, m_mat.at[:, i].set(m_col)), u

        np_pad = xs0.shape[0]
        (_, _, _), us = jax.lax.scan(
            step,
            (
                jnp.zeros((bp, d), dtype=cands.dtype),
                jnp.asarray(0, jnp.int32),
                jnp.zeros((np_pad, bp), dtype=ys0.dtype),
            ),
            (cands, w_all, member_idx),
        )
        return us

    return jax.jit(run)


def bo_batch(
    xs: np.ndarray,
    ys: np.ndarray,
    cands: np.ndarray,
    member_idx: Optional[np.ndarray],
    acq: str,
    length: float,
    noise: float,
) -> Optional[np.ndarray]:
    """One jitted scan over a whole BO suggestion batch: per pick, the
    Matérn-5/2 GP posterior over all candidates plus the acquisition argmax
    (or the three gp_hedge nominations with the host-drawn member choice),
    with the constant-liar rows (y = worst seen) activated in-carry.
    ``cands`` [B, M, D] and ``member_idx`` [B] carry the host rng draws in
    legacy call order. Returns the selected points [B, D] or None."""
    if not use_vectorized():
        return None
    n0, d = xs.shape
    batch = cands.shape[0]
    if n0 < 2 or batch <= 0:
        return None
    np_pad = _bucket(n0)
    bp = _bucket(batch, minimum=1)
    xs_pad = np.zeros((np_pad, d), dtype=np.float64)
    xs_pad[:n0] = xs
    ys_pad = np.zeros(np_pad, dtype=np.float64)
    ys_pad[:n0] = ys
    mask = np.zeros(np_pad, dtype=bool)
    mask[:n0] = True
    cands_pad = np.empty((bp,) + cands.shape[1:], dtype=np.float64)
    cands_pad[:batch] = cands
    cands_pad[batch:] = cands[batch - 1]
    midx = np.zeros(bp, dtype=np.int32)
    if member_idx is not None:
        midx[:batch] = member_idx

    with _jax()[0].enable_x64():
        us = _bo_acquire_program(acq)(
            xs_pad, ys_pad, mask, np.float64(n0), cands_pad, midx,
            np.float64(length), np.float64(noise),
            np.float64(ys.max()), np.float64(ys.min()),
        )
        out = np.asarray(us, dtype=np.float64)
    return out[:batch]
