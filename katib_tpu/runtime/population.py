"""Fused on-device population loops — PBT and ENAS as single compiled
generation programs (ISSUE 9 / ROADMAP 2).

PR 1 vmapped K compatible trials into one program and PR 8 made sure that
program is compiled before chips are allocated — but a population-based
sweep still round-tripped suggestion → dispatch → report through the Python
controller EVERY generation, so per-generation host latency (suggestion
sync, queue walk, thread spawn, DB commit), not device math, bounded
generations/sec. Following the Anakin pattern ("Podracer architectures for
scalable Reinforcement Learning", PAPERS.md) the whole
mutate → train → evaluate → select cycle moves inside one jitted
``lax.scan`` over generations with the K-member population vmapped across
the mesh:

- a :class:`PopulationProgram` is a *pure* description of one generation:
  ``init_carry(seed)`` builds the scan carry (hyperparameters ``f32[K,P]``,
  stacked member state, scores, an ``active`` mask, a threaded
  ``jax.random`` key) and ``generation_step(carry) -> (carry, summary)``
  advances one generation. Membership masking is **traceable**: ``active``
  is a carried ``jnp`` bool array consulted inside the scan via
  ``jnp.where`` (a frozen member's state, score and hyperparameters are
  held constant and it is excluded from selection) — not a host-side numpy
  sweep;
- :func:`pbt_program` builds the PBT step — truncation-quantile
  segmentation exactly mirroring ``suggest/pbt.py`` (bottom
  ``truncation_threshold`` fraction exploits, the rest explores), exploit
  as a ``jnp.take``/``jnp.where`` gather of a random upper-quantile
  member's hyperparameters AND state, explore as the ×0.8/×1.2
  perturbation (or grid resample with ``resample_probability``), all
  driven by the threaded key;
- :func:`enas_program` builds the ENAS step — the controller LSTM
  (``suggest/nas/enas._sample_and_score``) samples K architectures, a
  weight-shared child supernet trains and evaluates them, and a REINFORCE
  loop updates the controller, all inside the scan body;
- only per-generation summaries ({score[K], best, median, lineage}) leave
  the device: they accumulate in the scan output and are demuxed into the
  PR 3 obslog after the chunk returns. An optional ``io_callback`` stream
  (``runtime.population_stream_telemetry``) surfaces {generation, best,
  median} live — both for ``katib-tpu top`` visibility and as the watchdog
  heartbeat during chunks longer than ``runtime.stall_seconds``;
- the scan runs in chunks of ``runtime.population_chunk_generations`` so
  the PR 2 cooperative-preemption invariant holds at chunk granularity:
  the carry (including the PRNG key) is checkpointed atomically at every
  chunk boundary, metrics are persisted before a preempted sweep requeues,
  and a resumed sweep continues the exact key stream — bit-identical to an
  uninterrupted run.

Trial templates opt in via ``fn.population_program(spec) ->
PopulationProgram`` (the fused analogue of PR 7's ``fn.abstract_program``)
plus an explicit spec opt-in (algorithm setting ``fused`` / ``fused_-
generations``); ``runtime.fused_population=false`` or
``KATIB_TPU_FUSED_POPULATION=0`` restores the legacy per-generation
job-queue driver byte-identically.
"""

from __future__ import annotations

import json
import logging
import os
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

log = logging.getLogger("katib_tpu.population")

# Label stamped on every member trial of a fused sweep (value = member
# index); its presence is how the scheduler routes the formed pack to the
# FusedPopulationExecutor instead of the PackedTrialExecutor.
FUSED_LABEL = "fusedpop.katib-tpu/member"

# Sweep-carry checkpoint files inside the sweep's checkpoint directory.
CARRY_FILE = "population_carry.npz"
CARRY_META_FILE = "population_carry.json"

# Algorithm settings recognized by the fused driver (spec-side opt-in).
SETTING_FUSED = "fused"
SETTING_GENERATIONS = "fused_generations"
SETTING_POPULATION = "n_population"

_TRUTHY = ("1", "true", "on", "yes")


# ---------------------------------------------------------------------------
# Program description
# ---------------------------------------------------------------------------

@dataclass
class PopulationProgram:
    """One population workload, described as pure jittable functions.

    ``init_carry(seed)`` returns the scan carry: a pytree of concrete jnp
    arrays that MUST contain ``active`` (bool[K]), ``key`` (PRNG key) and
    ``generation`` (int32 scalar). ``generation_step(carry)`` returns
    ``(carry', summary)`` where ``summary`` holds at least ``score``
    (f32[K], the raw objective value each member achieved this generation),
    ``best`` and ``median`` (f32 scalars, already in objective units).
    Everything else in the summary (lineage, architectures, perturb
    factors) is program-specific and rides along to the tests/bench."""

    name: str                           # target label ("module:fn style")
    metric: str                         # objective metric name for the obslog
    n_population: int                   # K
    init_carry: Callable[[int], Any]
    generation_step: Callable[[Any], Tuple[Any, Dict[str, Any]]]
    hyperparam_names: List[str] = field(default_factory=list)
    # per-member initial parameter assignments ({name: str-value}) used to
    # label the K member trials; values must parse as floats (packability)
    initial_assignments: Optional[Callable[[int], List[Dict[str, str]]]] = None
    seed: int = 0


# ---------------------------------------------------------------------------
# Masked statistics (selection must see only ACTIVE members)
# ---------------------------------------------------------------------------

def masked_quantile(values, mask, q):
    """``np.quantile(values[mask], q)`` (linear interpolation), traceable:
    inactive entries sort to +inf and the interpolation index is computed
    from the active count. Meaningless when no member is active — the
    drivers stop the scan before that can happen."""
    import jax.numpy as jnp

    k = values.shape[0]
    s = jnp.sort(jnp.where(mask, values, jnp.inf))
    n = jnp.sum(mask)
    pos = q * jnp.maximum(n - 1, 0).astype(jnp.float32)
    lo = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, k - 1)
    hi = jnp.clip(jnp.ceil(pos).astype(jnp.int32), 0, k - 1)
    frac = pos - lo.astype(jnp.float32)
    return s[lo] * (1.0 - frac) + s[hi] * frac


def masked_median(values, mask):
    return masked_quantile(values, mask, 0.5)


def masked_best(values, mask, goal_scale):
    """Best raw objective over active members (max for goal_scale=+1, min
    for -1)."""
    import jax.numpy as jnp

    scaled = jnp.where(mask, values * goal_scale, -jnp.inf)
    return values[jnp.argmax(scaled)]


# ---------------------------------------------------------------------------
# PBT: truncation selection + explore/exploit as one traced step
# ---------------------------------------------------------------------------

def pbt_program(
    *,
    name: str,
    metric: str,
    n_population: int,
    hyperparams: List[str],
    lower,
    upper,
    grid_step=None,
    truncation: float = 0.2,
    resample_probability: Optional[float] = None,
    goal_scale: float = 1.0,
    init_member: Callable[[Any, Any], Any] = None,
    member_step: Callable[[Any, Any, Any], Tuple[Any, Any]] = None,
    seed: int = 0,
    stream: Optional[Callable[[Any, Any, Any], None]] = None,
) -> PopulationProgram:
    """Build the generic fused PBT program.

    ``init_member(key, hp_row) -> state`` and ``member_step(state, hp_row,
    key) -> (state, raw_score)`` describe ONE member; both are vmapped
    across the K-member population. ``lower``/``upper``/``grid_step`` are
    per-hyperparameter bounds ([P] float arrays; ``grid_step[j] > 0``
    quantizes seeding/resampling to the ``suggest/pbt.py`` sample grid).
    Selection mirrors the job-queue suggester: members below the
    ``truncation`` quantile of the (goal-scaled) score exploit a uniformly
    random member at or above the ``1 - truncation`` quantile — copying its
    hyperparameters AND its training state — while every other active
    member explores by perturbing each hyperparameter ×0.8/×1.2 (clipped to
    bounds), or, when ``resample_probability`` is set, by resampling each
    hyperparameter from the grid with that probability and keeping it
    otherwise. Frozen (inactive) members take no part: their state,
    score and hyperparameters are held constant via ``jnp.where`` and they
    are masked out of the quantiles and the replacement pool."""
    import jax
    import jax.numpy as jnp

    k = int(n_population)
    p = len(hyperparams)
    lo_b = jnp.asarray(np.asarray(lower, dtype=np.float32).reshape(p))
    hi_b = jnp.asarray(np.asarray(upper, dtype=np.float32).reshape(p))
    steps = np.asarray(
        grid_step if grid_step is not None else np.zeros((p,)), dtype=np.float32
    ).reshape(p)
    # grid sizes are static program constants (the suggest/pbt.py sample
    # lists): n_vals[j] points from lower with spacing grid_step[j]
    n_vals = np.where(
        steps > 0,
        np.floor((np.asarray(upper) - np.asarray(lower)) / np.where(steps > 0, steps, 1.0) + 1e-9) + 1,
        0,
    ).astype(np.int32)
    n_vals_j = jnp.asarray(n_vals)
    steps_j = jnp.asarray(steps)
    tt = float(truncation)
    scale = float(goal_scale)

    def _grid_sample(key):
        """One [K, P] draw from the quantized sample grid (continuous
        uniform where no grid step is configured)."""
        k_grid, k_cont = jax.random.split(key)
        idx = jax.random.randint(
            k_grid, (k, p), 0, jnp.maximum(n_vals_j, 1)[None, :]
        )
        gridded = lo_b[None, :] + idx.astype(jnp.float32) * steps_j[None, :]
        cont = jax.random.uniform(
            k_cont, (k, p), minval=lo_b[None, :], maxval=hi_b[None, :]
        )
        return jnp.where(n_vals_j[None, :] > 0, gridded, cont)

    def init_carry(seed_val: int):
        key = jax.random.PRNGKey(int(seed_val))
        key, k_hp, k_init = jax.random.split(key, 3)
        hp = _grid_sample(k_hp)
        state = jax.vmap(init_member)(jax.random.split(k_init, k), hp)
        return {
            "hparams": hp,
            "state": state,
            "score": jnp.zeros((k,), jnp.float32),
            "active": jnp.ones((k,), bool),
            "key": key,
            "generation": jnp.asarray(0, jnp.int32),
        }

    def generation_step(carry):
        active = carry["active"]
        key, k_train, k_choice, k_factor, k_rs_gate, k_rs = jax.random.split(
            carry["key"], 6
        )

        # -- train + evaluate one generation (vmapped, mask-frozen) ---------
        new_state, raw = jax.vmap(member_step)(
            carry["state"], carry["hparams"], jax.random.split(k_train, k)
        )
        exp_mask = lambda m, leaf: m.reshape((k,) + (1,) * (leaf.ndim - 1))
        state = jax.tree_util.tree_map(
            lambda n, o: jnp.where(exp_mask(active, n), n, o),
            new_state, carry["state"],
        )
        score = jnp.where(active, raw, carry["score"])

        # -- truncation segmentation (suggest/pbt.py _segment) --------------
        scaled = score * scale
        q_lo = masked_quantile(scaled, active, tt)
        q_hi = masked_quantile(scaled, active, 1.0 - tt)
        exploit = active & (scaled < q_lo)
        upper_pool = active & (scaled >= q_hi)
        explore = active & ~exploit
        # replacement pool fallback mirrors _generate: upper, else explore,
        # else exploit survivors (degenerate all-equal populations)
        pool = jnp.where(
            jnp.any(upper_pool), upper_pool,
            jnp.where(jnp.any(explore), explore, active),
        )
        logits = jnp.where(pool, 0.0, -jnp.inf)
        replacement = jax.random.categorical(k_choice, logits, shape=(k,))
        parent = jnp.where(exploit, replacement, jnp.arange(k))

        # -- exploit: gather the replacement's hyperparams AND state --------
        next_hp = jnp.take(carry["hparams"], parent, axis=0)
        next_state = jax.tree_util.tree_map(
            lambda leaf: jnp.take(leaf, parent, axis=0), state
        )
        next_score = jnp.take(score, parent)

        # -- explore: perturb ×0.8/×1.2 or grid-resample --------------------
        factors = jnp.where(
            jax.random.bernoulli(k_factor, 0.5, (k, p)), 1.2, 0.8
        )
        perturbed = jnp.clip(next_hp * factors, lo_b[None, :], hi_b[None, :])
        if resample_probability is not None:
            gate = jax.random.bernoulli(
                k_rs_gate, float(resample_probability), (k, p)
            )
            explored_hp = jnp.where(gate, _grid_sample(k_rs), next_hp)
            applied_factors = jnp.where(gate, 0.0, 1.0)
        else:
            explored_hp = perturbed
            applied_factors = factors
        explore_col = explore[:, None]
        next_hp = jnp.where(explore_col, explored_hp, next_hp)
        lineage_factors = jnp.where(explore_col, applied_factors, 1.0)

        # -- freeze: inactive members keep everything -----------------------
        next_hp = jnp.where(active[:, None], next_hp, carry["hparams"])
        next_state = jax.tree_util.tree_map(
            lambda n, o: jnp.where(exp_mask(active, n), n, o),
            next_state, state,
        )
        next_score = jnp.where(active, next_score, score)

        best = masked_best(score, active, scale)
        median = masked_median(score * scale, active) * scale
        generation = carry["generation"]
        if stream is not None:
            _emit_stream(stream, generation, best, median)
        summary = {
            "score": score,
            "best": best,
            "median": median,
            "hparams": carry["hparams"],
            "parent": jnp.where(exploit, parent, -1).astype(jnp.int32),
            "exploited": exploit,
            "factors": lineage_factors,
            "active": active,
        }
        next_carry = {
            "hparams": next_hp,
            "state": next_state,
            "score": next_score,
            "active": active,
            "key": key,
            "generation": generation + 1,
        }
        return next_carry, summary

    def initial_assignments(seed_val: int) -> List[Dict[str, str]]:
        hp = np.asarray(init_carry(seed_val)["hparams"])
        return [
            {hyperparams[j]: repr(float(hp[i, j])) for j in range(p)}
            for i in range(k)
        ]

    return PopulationProgram(
        name=name,
        metric=metric,
        n_population=k,
        init_carry=init_carry,
        generation_step=generation_step,
        hyperparam_names=list(hyperparams),
        initial_assignments=initial_assignments,
        seed=int(seed),
    )


# ---------------------------------------------------------------------------
# ENAS: controller-LSTM sample → shared-child train/eval → REINFORCE update
# ---------------------------------------------------------------------------

def enas_program(
    *,
    name: str,
    metric: str,
    n_population: int,
    num_layers: int,
    num_ops: int,
    child_init: Callable[[Any], Any],
    child_train_eval: Callable[[Any, Any, Any, Any], Tuple[Any, Any]],
    hidden_size: int = 64,
    temperature: Optional[float] = 5.0,
    tanh_const: Optional[float] = 2.25,
    entropy_weight: Optional[float] = 1e-5,
    baseline_decay: float = 0.999,
    learning_rate: float = 5e-5,
    skip_target: float = 0.4,
    skip_weight: Optional[float] = 0.8,
    controller_steps: int = 10,
    goal_scale: float = 1.0,
    seed: int = 0,
    stream: Optional[Callable[[Any, Any, Any], None]] = None,
) -> PopulationProgram:
    """Build the fused ENAS program: one generation = sample K
    architectures with the controller LSTM (the exact
    ``suggest/nas/enas._sample_and_score`` rollout, vmapped over K keys),
    train the weight-shared child on them and evaluate each
    (``child_train_eval(child_state, arcs, key, active) -> (child_state,
    acc[K])``), then run ``controller_steps`` REINFORCE updates with
    reward = masked mean child metric — the whole cycle inside the scan
    body, so G generations are ONE compiled program instead of G
    suggestion-service round-trips."""
    import jax
    import jax.numpy as jnp
    import optax

    from ..suggest.nas.enas import _init_params, _sample_and_score

    k = int(n_population)
    tx = optax.adam(float(learning_rate))
    scale = float(goal_scale)

    def sample_one(params, key):
        return _sample_and_score(
            params, key, int(num_layers), temperature, tanh_const,
            float(skip_target),
        )

    def init_carry(seed_val: int):
        key = jax.random.PRNGKey(int(seed_val))
        key, k_ctrl, k_child = jax.random.split(key, 3)
        params = _init_params(k_ctrl, int(num_ops), int(hidden_size))
        return {
            "ctrl": params,
            "opt": tx.init(params),
            "baseline": jnp.asarray(0.0, jnp.float32),
            "child": child_init(k_child),
            "score": jnp.zeros((k,), jnp.float32),
            "active": jnp.ones((k,), bool),
            "key": key,
            "generation": jnp.asarray(0, jnp.int32),
        }

    def generation_step(carry):
        active = carry["active"]
        key, k_sample, k_child, k_train = jax.random.split(carry["key"], 4)

        # -- controller rollout: K architectures from the LSTM sampler ------
        arcs, *_ = jax.vmap(lambda kk: sample_one(carry["ctrl"], kk))(
            jax.random.split(k_sample, k)
        )
        arcs = arcs.astype(jnp.int32)

        # -- weight-shared child: train on + evaluate the K archs -----------
        child_state, raw = child_train_eval(carry["child"], arcs, k_child, active)
        score = jnp.where(active, raw, carry["score"])
        reward_base = (
            jnp.sum(jnp.where(active, score, 0.0))
            / jnp.maximum(jnp.sum(active), 1)
        ) * scale

        # -- REINFORCE controller update (enas._train_controller, traced) ---
        def ctrl_step(_, st):
            params, opt_state, baseline, kk = st
            kk, sub = jax.random.split(kk)

            def loss_fn(p):
                _, log_prob, entropy, skip_penalty, _ = sample_one(p, sub)
                reward = reward_base
                if entropy_weight is not None:
                    reward = reward + float(entropy_weight) * entropy
                new_baseline = baseline - (1.0 - float(baseline_decay)) * (
                    baseline - reward
                )
                loss = log_prob * (reward - new_baseline)
                if skip_weight is not None:
                    loss = loss + float(skip_weight) * skip_penalty
                return loss, new_baseline

            (_, new_baseline), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state, new_baseline, kk)

        params, opt_state, baseline, _ = jax.lax.fori_loop(
            0, int(controller_steps), ctrl_step,
            (carry["ctrl"], carry["opt"], carry["baseline"], k_train),
        )

        best = masked_best(score, active, scale)
        median = masked_median(score * scale, active) * scale
        generation = carry["generation"]
        if stream is not None:
            _emit_stream(stream, generation, best, median)
        summary = {
            "score": score,
            "best": best,
            "median": median,
            "arc": arcs,
            "active": active,
        }
        next_carry = {
            "ctrl": params,
            "opt": opt_state,
            "baseline": baseline,
            "child": child_state,
            "score": score,
            "active": active,
            "key": key,
            "generation": generation + 1,
        }
        return next_carry, summary

    def initial_assignments(_seed_val: int) -> List[Dict[str, str]]:
        # architectures are sampled inside the program; member trials are
        # labeled by population slot only
        return [{"member": str(i)} for i in range(k)]

    return PopulationProgram(
        name=name,
        metric=metric,
        n_population=k,
        init_carry=init_carry,
        generation_step=generation_step,
        hyperparam_names=["member"],
        initial_assignments=initial_assignments,
        seed=int(seed),
    )


def _emit_stream(sink, generation, best, median) -> None:
    """Per-generation host stream from inside the scan body (io_callback):
    ordered so the live view advances monotonically."""
    from jax.experimental import io_callback

    io_callback(sink, None, generation, best, median, ordered=True)


# ---------------------------------------------------------------------------
# Live stream registry (the katib-tpu top hook)
# ---------------------------------------------------------------------------

_LIVE_LOCK = threading.Lock()
_LIVE: Dict[str, Dict[str, float]] = {}


def stream_sink(experiment: str, heartbeat: Optional[Callable[[], None]] = None):
    """Host-side sink for the in-scan io_callback stream: records the
    latest {generation, best, median} under the experiment name (surfaced
    by :func:`live_status`) and fires the telemetry heartbeat so a chunk
    longer than ``runtime.stall_seconds`` cannot trip the PR 5 watchdog."""

    def sink(generation, best, median):
        with _LIVE_LOCK:
            _LIVE[experiment] = {
                "generation": int(generation),
                "best": float(best),
                "median": float(median),
            }
        if heartbeat is not None:
            heartbeat()

    return sink


def live_status(experiment: Optional[str] = None) -> Dict[str, Any]:
    """Latest streamed per-generation summary (all experiments, or one)."""
    with _LIVE_LOCK:
        if experiment is not None:
            return dict(_LIVE.get(experiment, {}))
        return {k: dict(v) for k, v in _LIVE.items()}


def clear_live_status() -> None:
    with _LIVE_LOCK:
        _LIVE.clear()


# ---------------------------------------------------------------------------
# Chunked drivers (fused = one compiled scan; legacy = chunk of 1)
# ---------------------------------------------------------------------------

def build_chunk_fn(
    program: PopulationProgram,
    length: int,
    stream: Optional[Callable[[Any, Any, Any], None]] = None,
):
    """The fused chunk program: ``carry -> (carry, ys)`` scanning
    ``generation_step`` over ``length`` generations, optionally emitting
    the per-generation {generation, best, median} io_callback stream.
    Callers jit (or AOT compile) the returned function ONCE and reuse it
    for every equal-length chunk — creating it inside a chunk loop would
    re-trace per chunk, the exact KTC101/KTC105 hazard the analyzer exists
    to catch."""
    import jax

    def body(carry, _):
        next_carry, summary = program.generation_step(carry)
        if stream is not None:
            _emit_stream(
                stream, carry["generation"], summary["best"], summary["median"]
            )
        return next_carry, summary

    def chunk(carry):
        return jax.lax.scan(body, carry, None, length=int(length))

    return chunk


def chunk_lengths(span: int, chunk: int) -> List[int]:
    """The distinct scan lengths a chunked drive of ``span`` generations
    uses: the chunk body and, when it does not divide evenly, the tail
    remainder — at most two compiled programs per sweep."""
    span, chunk = int(span), max(1, int(chunk))
    if span <= 0:
        return []
    if span <= chunk:
        return [span]
    rem = span % chunk
    return [chunk] if rem == 0 else [chunk, rem]


def run_generations(
    program: PopulationProgram,
    generations: int,
    chunk: Optional[int] = None,
    seed: Optional[int] = None,
    on_chunk: Optional[Callable[[Any, Dict[str, np.ndarray], int], Any]] = None,
    carry: Any = None,
    start_generation: int = 0,
) -> Tuple[Any, Dict[str, np.ndarray]]:
    """Drive ``generations`` generations of ``program`` in compiled chunks.

    ``chunk=None`` (or >= generations) is the fully fused mode: ONE
    compiled ``lax.scan`` program executes the whole sweep. ``chunk=1``
    models the per-generation job-queue driver: one compiled call plus a
    host round-trip per generation — the comparison driver for the
    fused-vs-legacy equivalence tests and the throughput bench. Both modes
    run the identical step function on the identical carry, so their
    lineage and metrics match bit-for-bit under a fixed seed.

    ``on_chunk(carry, ys, generation_done)`` runs at every chunk boundary
    (checkpointing, demux, preemption checks); it may return a replacement
    carry (e.g. with the ``active`` mask ANDed against host-side kill
    state) or None to keep the current one. Returns the final carry and
    the stacked per-generation summaries as numpy arrays."""
    import jax

    if carry is None:
        carry = program.init_carry(program.seed if seed is None else seed)
    total = int(generations)
    chunk = total if chunk is None else max(1, min(int(chunk), max(total, 1)))
    collected: List[Dict[str, np.ndarray]] = []
    done = int(start_generation)
    # one jitted callable per distinct chunk length (at most two: the body
    # length and the tail remainder), built BEFORE the loop — jax.jit is
    # lazy, so unused lengths never trace
    jitted = {
        length: jax.jit(build_chunk_fn(program, length))
        for length in chunk_lengths(total - done, chunk)
    }
    while done < total:
        length = min(chunk, total - done)
        fn = jitted[length]
        carry, ys = fn(carry)
        ys_np = {k2: np.asarray(v) for k2, v in ys.items()}
        collected.append(ys_np)
        done += length
        if on_chunk is not None:
            replacement = on_chunk(carry, ys_np, done)
            if replacement is not None:
                carry = replacement
    if not collected:
        return carry, {}
    stacked = {
        k2: np.concatenate([c[k2] for c in collected], axis=0)
        for k2 in collected[0]
    }
    return carry, stacked


# ---------------------------------------------------------------------------
# Sweep-carry checkpointing (chunk-granularity preemption/resume)
# ---------------------------------------------------------------------------

def save_sweep_checkpoint(
    directory: str,
    carry: Any,
    generation_done: int,
    pending_ys: Optional[Dict[str, np.ndarray]] = None,
    reported: int = 0,
) -> None:
    """Atomically persist the sweep state at a chunk boundary: the carry
    pytree (flattened; including the PRNG key, so resume continues the
    exact stream), how many generations have completed on-device, the
    not-yet-demuxed summaries of the interrupted chunk and how many of its
    generations already reached the obslog. tmp + ``os.replace`` — a crash
    mid-write leaves the previous checkpoint intact.

    The meta rides INSIDE the npz (``__meta__``) so carry+meta commit in
    ONE replace: a SIGKILL between two separate file replaces used to
    leave a torn pair (new carry arrays, stale generation counter) and the
    resumed sweep double-reported the stale tail. The json file is still
    written afterwards, but purely as a mirror for watchers/humans —
    loads treat the embedded copy as authoritative."""
    import jax

    os.makedirs(directory, exist_ok=True)
    leaves = jax.tree_util.tree_leaves(carry)
    arrays = {f"c{i}": np.asarray(leaf) for i, leaf in enumerate(leaves)}
    if pending_ys:
        for k2, v in pending_ys.items():
            arrays[f"y_{k2}"] = np.asarray(v)
    meta = {
        "generationDone": int(generation_done),
        "reported": int(reported),
        "pendingKeys": sorted(pending_ys) if pending_ys else [],
        "leaves": len(leaves),
    }
    arrays["__meta__"] = np.frombuffer(
        json.dumps(meta).encode("utf-8"), dtype=np.uint8
    )
    # staging names are dot-prefixed so recovery's checkpoint-instant scan
    # (latest_checkpoint_time matches population_carry*) can never mistake
    # a torn half-written tmp for a durable carry — a SIGKILL mid-savez
    # used to leave a too-new tmp that silently disabled tail truncation
    path = os.path.join(directory, CARRY_FILE)
    tmp = os.path.join(directory, "." + CARRY_FILE + ".tmp")
    with open(tmp, "wb") as f:
        np.savez(f, **arrays)
    os.replace(tmp, path)
    mpath = os.path.join(directory, CARRY_META_FILE)
    mtmp = os.path.join(directory, "." + CARRY_META_FILE + ".tmp")
    with open(mtmp, "w") as f:
        json.dump(meta, f)
    os.replace(mtmp, mpath)


def load_sweep_checkpoint(directory: Optional[str], program: PopulationProgram):
    """Restore a persisted sweep state, or None (no checkpoint / unreadable
    — a corrupt checkpoint falls back to a fresh sweep, loudly). Returns
    ``(carry, generation_done, pending_ys, reported)``."""
    import jax

    if not directory:
        return None
    path = os.path.join(directory, CARRY_FILE)
    mpath = os.path.join(directory, CARRY_META_FILE)
    if not os.path.exists(path):
        return None
    try:
        with np.load(path) as data:
            if "__meta__" in data.files:
                meta = json.loads(bytes(data["__meta__"]).decode("utf-8"))
            else:
                # pre-embedded-meta checkpoint: the sidecar json is all there is
                with open(mpath) as f:
                    meta = json.load(f)
            template = program.init_carry(program.seed)
            t_leaves, treedef = jax.tree_util.tree_flatten(template)
            if meta.get("leaves") != len(t_leaves):
                raise ValueError("carry structure changed")
            import jax.numpy as jnp

            leaves = [
                jnp.asarray(data[f"c{i}"], dtype=t_leaves[i].dtype)
                for i in range(len(t_leaves))
            ]
            carry = jax.tree_util.tree_unflatten(treedef, leaves)
            pending = {
                k2: np.asarray(data[f"y_{k2}"]) for k2 in meta.get("pendingKeys", [])
            }
        return carry, int(meta["generationDone"]), pending, int(meta["reported"])
    except Exception as e:
        log.warning(
            "corrupt population checkpoint under %s (%s: %s); sweep restarts "
            "from scratch", directory, type(e).__name__, e,
        )
        return None


def clear_sweep_checkpoint(directory: Optional[str]) -> None:
    if not directory:
        return
    for name in (CARRY_FILE, CARRY_META_FILE):
        try:
            os.unlink(os.path.join(directory, name))
        except OSError:
            pass


# ---------------------------------------------------------------------------
# Spec-side applicability (the controller consults these)
# ---------------------------------------------------------------------------

_ENABLED: Optional[bool] = None  # None = resolve from the environment


def set_enabled(enabled: bool) -> None:
    """Config hook (runtime.fused_population): ExperimentController calls
    this at construction so every consumer — pack capacity, executor
    selection, the fused reconcile branch — sees one switch (the same
    pattern as analysis.program.set_enabled)."""
    global _ENABLED
    _ENABLED = bool(enabled)


def runtime_enabled() -> bool:
    if _ENABLED is not None:
        return _ENABLED
    return os.environ.get("KATIB_TPU_FUSED_POPULATION", "1").lower() not in (
        "0", "false", "off",
    )


def spec_opted_in(spec) -> bool:
    """The experiment asked for the fused driver: algorithm setting
    ``fused`` truthy or an explicit ``fused_generations``. Opt-in is
    per-spec so every existing PBT/ENAS experiment keeps the job-queue
    path byte-identically."""
    settings = spec.algorithm.settings_dict()
    if settings.get(SETTING_FUSED, "").lower() in _TRUTHY:
        return True
    return SETTING_GENERATIONS in settings


def fused_applicable(spec) -> Optional[str]:
    """None when this spec can dispatch as one fused sweep, else the
    human-readable reason it falls back to the job-queue driver."""
    template = spec.trial_template
    if not runtime_enabled():
        return "fused population runtime disabled (runtime.fused_population)"
    if not spec_opted_in(spec):
        return "spec did not opt in (algorithm setting fused/fused_generations)"
    if template.command is not None:
        return "command templates run as subprocesses"
    if template.resources.num_hosts > 1:
        return "multi-host trials form their own gang"
    fn = _resolved_function(template)
    if fn is None:
        return "trial function cannot be resolved"
    if getattr(fn, "population_program", None) is None:
        return "trial function exposes no population_program probe"
    return None


def _resolved_function(template):
    if getattr(template, "command", None) is not None:
        return None
    if getattr(template, "function", None) is not None:
        return template.function
    if getattr(template, "entry_point", None):
        try:
            from ..controller.executor import resolve_entry_point

            return resolve_entry_point(template)
        except Exception:
            return None
    return None


def build_program(spec) -> PopulationProgram:
    """The spec's fused program (the template must be applicable)."""
    fn = _resolved_function(spec.trial_template)
    return fn.population_program(spec)


def generation_count(spec, program: Optional[PopulationProgram] = None) -> int:
    """G for one sweep: the explicit ``fused_generations`` setting, else
    derived from the legacy budget — ``max_trial_count`` trials at K per
    generation is ``max_trial_count // K`` generations."""
    settings = spec.algorithm.settings_dict()
    if SETTING_GENERATIONS in settings:
        return max(1, int(settings[SETTING_GENERATIONS]))
    k = program.n_population if program is not None else int(
        settings.get(SETTING_POPULATION, "8")
    )
    if spec.max_trial_count:
        return max(1, int(spec.max_trial_count) // max(k, 1))
    return 1


def member_name(spec, index: int) -> str:
    """Deterministic member-trial name — resume after a controller restart
    re-derives the same names."""
    return f"{spec.name}-fused-m{index:02d}"


def fused_group_key(spec, chunk_length: int):
    """Compile-service registry key for the fused chunk program: template
    digest + population size + scan length — the fused analogue of the PR 7
    dispatch-group key, so the sweep's executable is fingerprinted,
    prewarmed and deduplicated like any dispatch group."""
    from ..analysis import program as semantic

    digest = semantic.template_digest(spec.trial_template)
    settings = spec.algorithm.settings_dict()
    return (
        "fusedpop",
        digest,
        settings.get(SETTING_POPULATION, ""),
        int(chunk_length),
    )


def fused_probe(spec, chunk_length: int, program: Optional[PopulationProgram] = None):
    """ProgramProbe describing the fused chunk program abstractly (carry
    avals via ``jax.eval_shape`` over ``init_carry``) — what the PR 8
    compile service AOT-traces and compiles at admission. The executable it
    produces is called with the concrete carry, so a warm sweep starts
    with zero inline compilation."""
    import jax

    from ..analysis.program import ProgramProbe

    program = program or build_program(spec)
    template_carry = program.init_carry(program.seed)
    avals = jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), template_carry
    )
    return ProgramProbe(
        fn=build_chunk_fn(program, chunk_length),
        args=(avals,),
        statics={
            "fused": "population",
            "K": program.n_population,
            "chunk": int(chunk_length),
        },
    )


def prewarm_fused(compile_service, spec, chunk_generations: int) -> Optional[Any]:
    """Admission-time AOT prewarm of the fused chunk program through the
    PR 8 compile service — fingerprinted, cost-ordered and cached exactly
    like a per-trial dispatch group. Best-effort: any failure leaves the
    sweep on the inline-jit path."""
    if compile_service is None or fused_applicable(spec) is not None:
        return None
    try:
        program = build_program(spec)
        total = generation_count(spec, program)
        chunk = min(max(1, int(chunk_generations or total)), total)
        key = fused_group_key(spec, chunk)
        return compile_service.request_group(
            key,
            experiment=spec.name,
            target=f"fusedpop:{program.name}",
            builder=lambda _assignments, _spec=spec, _chunk=chunk, _p=program: (
                fused_probe(_spec, _chunk, _p)
            ),
        )
    except Exception:
        log.debug("fused population prewarm failed", exc_info=True)
        return None
