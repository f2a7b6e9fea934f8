"""DARTS bilevel search trainer — the TPU re-design of the reference's
darts-cnn-cifar10 trial image.

reference examples/v1beta1/trial-images/darts-cnn-cifar10/run_trial.py:29-259
(alternating alpha/weight optimization, SGD+cosine for weights, Adam for
alphas, grad clip, prints Best-Genotype) and architect.py:19-135 (second-order
unrolled alpha gradient).

JAX re-design:
- the whole search step — virtual SGD step w', validation grads at w',
  exact jvp Hessian-vector correction (hessian_mode="jvp"; the reference's
  central difference remains as "fd"), alpha Adam update, then the real
  weight update — is ONE jitted pure function; XLA fuses the
  forward/backward passes and keeps everything resident in HBM;
- second-order terms are plain jax.grad compositions (no parameter copying:
  the virtual model is just a tree_map expression);
- data parallelism: the step is jitted with NamedSharding over a 1-D device
  mesh ('data'); batch-sharded inputs make XLA insert psum for the gradient
  all-reduce over ICI (multi-chip DARTS, SURVEY.md §7 hard part 1);
- bfloat16 matmuls via jax.default_matmul_precision can be toggled by the
  caller; parameters stay f32;
- optimizer hyperparameters (w_lr, alpha_lr, momentum, weight decays) are
  TRACED arguments of the jitted step, not baked-in constants: every trial of
  an HPO sweep over them reuses ONE compiled XLA program — no per-trial
  recompile (the reference pays a fresh CUDA-graph warmup per trial process).

Entry point ``run_darts_trial(assignments, ctx)`` consumes the suggestion's
``algorithm-settings`` / ``search-space`` / ``num-layers`` JSON assignments
exactly like run_trial.py parses its flags.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..utils.datasets import batches, load_cifar10
from .darts_supernet import DartsSupernet, genotype, merge_params, split_params


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()


def _loss_fn(model: DartsSupernet, weights, alphas, batch) -> jnp.ndarray:
    x, y = batch
    logits = model.apply({"params": merge_params(weights, alphas)}, x)
    return cross_entropy(logits, y)


def _tree_norm(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.vdot(l, l) for l in leaves))


def architect_alpha_grad(
    model: DartsSupernet,
    weights,
    alphas,
    momentum_buf,
    train_batch,
    valid_batch,
    xi: float,
    w_momentum: float,
    w_weight_decay: float,
    hessian_mode: str = "jvp",
):
    """Unrolled second-order alpha gradient (architect.py:30-135).

    dalpha L_val(w', a) - xi * d^2/dadw L_train(w, a) . dw' L_val(w', a)

    ``hessian_mode`` selects how the mixed Hessian-vector product is
    computed:

    - ``"jvp"`` (default): EXACT forward-over-reverse ``jax.jvp`` through
      the alpha-gradient map — the idiomatic JAX form, one extra
      forward-mode pass instead of two extra backward passes.
    - ``"fd"``: the reference's central-difference approximation
      (architect.py compute_hessian, eps = 0.01/||dw||), kept for parity
      comparison. Measured against the exact product (f64): because
      dalpha L_train is DISCONTINUOUS in w at every ReLU/pooling
      activation boundary, the finite difference is O(jump/eps) garbage
      whenever the +/-eps probe straddles a boundary — 8-90x relative
      error on a small supernet — while converging to the jvp value when
      eps happens to be smaller than the nearest kink distance. The
      reference tolerates this because xi is small and the noise averages
      out over many alternating steps; the exact product removes it for
      free (torch-era double-backward constraints don't apply to XLA).
    """
    # virtual step: w' = w - xi * (momentum*buf + dw L_train + wd*w)
    g_w = jax.grad(lambda w: _loss_fn(model, w, alphas, train_batch))(weights)
    v_weights = jax.tree.map(
        lambda w, g, m: w - xi * (w_momentum * m + g + w_weight_decay * w),
        weights,
        g_w,
        momentum_buf,
    )

    # validation grads at (w', alpha) — one joint backward pass for both
    # cotangents (graph size == compile time on TPU)
    val_loss = lambda w, a: _loss_fn(model, w, a, valid_batch)
    dw, dalpha = jax.grad(val_loss, argnums=(0, 1))(v_weights, alphas)

    train_alpha_grad = lambda w: jax.grad(
        lambda a: _loss_fn(model, w, a, train_batch)
    )(alphas)
    if hessian_mode == "jvp":
        # exact d^2/dadw L_train . dw via forward-over-reverse
        _, hessian = jax.jvp(train_alpha_grad, (weights,), (dw,))
    elif hessian_mode == "fd":
        # reference central difference (compute_hessian): eps = 0.01 / ||dw||
        eps = 0.01 / (_tree_norm(dw) + 1e-12)
        w_pos = jax.tree.map(lambda w, d: w + eps * d, weights, dw)
        w_neg = jax.tree.map(lambda w, d: w - eps * d, weights, dw)
        a_pos = train_alpha_grad(w_pos)
        a_neg = train_alpha_grad(w_neg)
        hessian = jax.tree.map(lambda p, n: (p - n) / (2.0 * eps), a_pos, a_neg)
    else:
        raise ValueError(f"unknown hessian_mode {hessian_mode!r} (jvp|fd)")

    return jax.tree.map(lambda da, h: da - xi * h, dalpha, hessian)


def _make_w_tx(weight_decay, momentum, lr, grad_clip):
    """SGD momentum + weight decay + clip (run_trial.py w_optim). Pure
    construction — safe to rebuild inside the traced step with traced
    hyperparameter values (state structure is value-independent)."""
    return optax.chain(
        optax.add_decayed_weights(weight_decay),
        optax.clip_by_global_norm(grad_clip),
        optax.sgd(lr, momentum=momentum),
    )


def _make_a_tx(weight_decay, lr):
    """Adam(0.5, 0.999) + weight decay (run_trial.py alpha_optim)."""
    return optax.chain(
        optax.add_decayed_weights(weight_decay),
        optax.adam(lr, b1=0.5, b2=0.999),
    )


@functools.lru_cache(maxsize=16)
def _compiled_search_step(model: "DartsSupernet", total_steps: int,
                          w_lr_min: float, w_grad_clip: float,
                          hessian_mode: str = "jvp"):
    """ONE jitted bilevel step per static configuration, shared across
    DartsSearch instances (flax Modules are frozen dataclasses — hashable
    cache keys). Every trial of an HPO sweep reuses the same Python
    callable, so trials 2+ skip jax retracing entirely on top of the
    persistent-XLA-cache compile hit; hyperparameter VALUES arrive through
    the traced ``hyper`` argument.

    Memory bound: the cache pins up to maxsize compiled executables (plus
    their Module keys) for the life of the process — sized for HPO sweeps,
    which iterate one static config. A controller sweeping MANY distinct
    architectures holds ≤16 programs; lower maxsize (or clear the caches via
    ``_compiled_search_step.cache_clear()``) if device/host memory pressure
    shows up before eviction does."""

    def momentum_of(opt_state):
        # trace of optax.sgd momentum buffer inside the chain
        return opt_state[2][0].trace

    def step(weights, alphas, w_opt_state, a_opt_state, step_idx, hyper, train_batch, valid_batch):
        # cosine decay from the traced base lr (run_trial.py lr_scheduler):
        # lr(t) = w_lr_min + (w_lr - w_lr_min) * 0.5 * (1 + cos(pi t/T))
        frac = jnp.clip(step_idx / total_steps, 0.0, 1.0)
        xi = w_lr_min + (hyper["w_lr"] - w_lr_min) * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
        w_tx = _make_w_tx(hyper["w_weight_decay"], hyper["w_momentum"], xi, w_grad_clip)
        a_tx = _make_a_tx(hyper["alpha_weight_decay"], hyper["alpha_lr"])

        # 1) alpha update from the unrolled objective
        dalpha = architect_alpha_grad(
            model,
            weights,
            alphas,
            momentum_of(w_opt_state),
            train_batch,
            valid_batch,
            xi,
            hyper["w_momentum"],
            hyper["w_weight_decay"],
            hessian_mode=hessian_mode,
        )
        a_updates, a_opt_state = a_tx.update(dalpha, a_opt_state, alphas)
        alphas = optax.apply_updates(alphas, a_updates)

        # 2) weight update on the training batch
        loss, g_w = jax.value_and_grad(
            lambda w: _loss_fn(model, w, alphas, train_batch)
        )(weights)
        w_updates, w_opt_state = w_tx.update(g_w, w_opt_state, weights)
        weights = optax.apply_updates(weights, w_updates)
        return weights, alphas, w_opt_state, a_opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1, 2, 3))


@functools.lru_cache(maxsize=16)
def _compiled_eval_step(model: "DartsSupernet"):
    def evaluate(weights, alphas, batch):
        x, y = batch
        logits = model.apply({"params": merge_params(weights, alphas)}, x)
        return (jnp.argmax(logits, -1) == y).mean()

    return jax.jit(evaluate)


class DartsSearch:
    """Alternating bilevel optimization driver (run_trial.py train loop)."""

    def __init__(
        self,
        primitives: Sequence[str],
        num_layers: int = 8,
        settings: Optional[Dict[str, Any]] = None,
        input_channels: int = 3,
        num_classes: int = 10,
        mesh: Optional[jax.sharding.Mesh] = None,
        seed: int = 0,
    ):
        s = dict(settings or {})
        self.num_epochs = int(s.get("num_epochs", 50) or 50)
        self.w_lr = float(s.get("w_lr", 0.025))
        self.w_lr_min = float(s.get("w_lr_min", 0.001))
        self.w_momentum = float(s.get("w_momentum", 0.9))
        self.w_weight_decay = float(s.get("w_weight_decay", 3e-4))
        self.w_grad_clip = float(s.get("w_grad_clip", 5.0))
        self.alpha_lr = float(s.get("alpha_lr", 3e-4))
        self.alpha_weight_decay = float(s.get("alpha_weight_decay", 1e-3))
        self.batch_size = int(s.get("batch_size", 128) or 128)
        self.init_channels = int(s.get("init_channels", 16))
        self.num_nodes = int(s.get("num_nodes", 4))
        self.stem_multiplier = int(s.get("stem_multiplier", 3))
        self.print_step = int(s.get("print_step", 50))
        # Cosine-schedule horizon override: decouples the lr schedule (and
        # with it the _compiled_search_step cache key, which is static in
        # total_steps) from the actual demo length — a short evidence run
        # pinned to a reference horizon reuses the exact compiled program of
        # a full-length run instead of paying a fresh multi-minute XLA
        # compile for a different schedule constant.
        self.schedule_horizon = int(s.get("schedule_horizon", 0) or 0)
        # "jvp" (exact, default) | "fd" (reference central-difference parity).
        # Normalize + fail fast here: HPO assignments bypass the suggester's
        # validate_algorithm_settings, and a bad value would otherwise only
        # raise at the first jitted step, after dataset load and model init.
        self.hessian_mode = str(s.get("hessian_mode", "jvp") or "jvp").strip().lower()
        if self.hessian_mode not in ("jvp", "fd"):
            raise ValueError(
                f"hessian_mode must be 'jvp' or 'fd', got {s.get('hessian_mode')!r}"
            )
        # settings arrive as strings from HPO assignments: explicit opt-in
        remat = str(s.get("remat_cells", "")).strip().lower() in ("1", "true", "yes", "on")

        prims = list(primitives)
        if "none" not in prims:
            prims.append("none")  # search_space.py appends 'none'
        self.primitives = prims
        self.model = DartsSupernet(
            primitives=tuple(prims),
            init_channels=self.init_channels,
            input_channels=input_channels,
            num_classes=num_classes,
            num_layers=num_layers,
            num_nodes=self.num_nodes,
            stem_multiplier=self.stem_multiplier,
            remat_cells=remat,
        )
        self.mesh = mesh
        self.seed = seed
        self._built = False

    # ------------------------------------------------------------------

    def build(self, sample_shape: Tuple[int, ...], total_steps: int) -> None:
        from ..utils.modelinit import jitted_init

        key = jax.random.PRNGKey(self.seed)
        params = jitted_init(self.model, key, jnp.zeros((2,) + tuple(sample_shape)))
        self.weights, self.alphas = split_params(params)

        self.total_steps = max(self.schedule_horizon or total_steps, 1)
        self.w_opt_state = _make_w_tx(
            self.w_weight_decay, self.w_momentum, self.w_lr, self.w_grad_clip
        ).init(self.weights)
        self.a_opt_state = _make_a_tx(
            self.alpha_weight_decay, self.alpha_lr
        ).init(self.alphas)
        self.step_idx = 0

        # Traced hyperparameters: HPO trials over these share one compiled
        # program (the values are runtime scalars, not HLO constants).
        self.hyper = {
            "w_lr": jnp.float32(self.w_lr),
            "w_momentum": jnp.float32(self.w_momentum),
            "w_weight_decay": jnp.float32(self.w_weight_decay),
            "alpha_lr": jnp.float32(self.alpha_lr),
            "alpha_weight_decay": jnp.float32(self.alpha_weight_decay),
        }

        if self.mesh is not None:
            # Data-parallel bilevel search (SURVEY §7 hard part 1): supernet
            # weights, alphas, and optimizer state are explicitly replicated
            # over the mesh while _epoch_iter shards batches over 'data' —
            # GSPMD then all-reduces both the weight grads and the
            # Hessian-vector terms of the alpha grads, with no
            # involuntary resharding of the replicated state.
            from jax.sharding import NamedSharding, PartitionSpec as P

            replicated = NamedSharding(self.mesh, P())
            (self.weights, self.alphas, self.w_opt_state, self.a_opt_state) = (
                jax.device_put(
                    (self.weights, self.alphas, self.w_opt_state, self.a_opt_state),
                    replicated,
                )
            )

        self._search_step = _compiled_search_step(
            self.model, self.total_steps, self.w_lr_min, self.w_grad_clip,
            self.hessian_mode,
        )
        self._eval_step = _compiled_eval_step(self.model)
        self._built = True

    def _epoch_iter(self, x, y, rng):
        """Epoch iterator with batches staged on device ahead of use
        (double buffering — katib_tpu.utils.prefetch). Meshed runs stage with
        the data-parallel sharding; single-device runs stay uncommitted
        (they then follow jax.default_device, which is how a trial is
        placed on its own chip of a multi-chip host)."""
        from ..utils.prefetch import prefetch_to_device

        base = [(x, y)] if len(x) < self.batch_size else batches(
            x, y, self.batch_size, rng
        )
        sharding = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            sharding = NamedSharding(self.mesh, P("data"))
        return prefetch_to_device(base, sharding=sharding)

    # ------------------------------------------------------------------

    def train_epoch(self, train_data, valid_data, rng: np.random.Generator):
        """One epoch of alternating updates (run_trial.py train())."""
        assert self._built
        x_t, y_t = train_data
        x_v, y_v = valid_data
        losses = []
        train_iter = self._epoch_iter(x_t, y_t, rng)
        valid_iter = self._epoch_iter(x_v, y_v, rng)
        for train_batch in train_iter:
            try:
                valid_batch = next(valid_iter)
            except StopIteration:
                valid_iter = self._epoch_iter(x_v, y_v, rng)
                valid_batch = next(valid_iter)
            (self.weights, self.alphas, self.w_opt_state, self.a_opt_state, loss) = (
                self._search_step(
                    self.weights,
                    self.alphas,
                    self.w_opt_state,
                    self.a_opt_state,
                    self.step_idx,
                    self.hyper,
                    train_batch,
                    valid_batch,
                )
            )
            self.step_idx += 1
            losses.append(loss)
        return float(jnp.stack(losses).mean())

    def validate(self, valid_data, rng: np.random.Generator, max_batches: int = 50) -> float:
        x_v, y_v = valid_data
        accs = []
        for i, batch in enumerate(self._epoch_iter(x_v, y_v, rng)):
            if i >= max_batches:
                break
            accs.append(self._eval_step(self.weights, self.alphas, batch))
        return float(jnp.stack(accs).mean()) if accs else 0.0

    def genotype(self) -> Dict[str, Any]:
        params = merge_params(self.weights, self.alphas)
        return genotype(params, self.primitives, self.num_nodes)


def _search_and_report(search: DartsSearch, train_data, valid_data, ctx) -> float:
    """Shared epoch loop: alternate bilevel updates, validate, report
    per-epoch metrics (run_trial.py train loop + print format)."""
    rng = np.random.default_rng(0)
    best_acc = 0.0
    for _epoch in range(search.num_epochs):
        loss = search.train_epoch(train_data, valid_data, rng)
        acc = search.validate(valid_data, rng)
        best_acc = max(best_acc, acc)
        if ctx is not None:
            ctx.report(**{"Validation-accuracy": acc, "Train-loss": loss})
        else:
            print(f"Validation-accuracy={acc}")
            print(f"Train-loss={loss}")
    return best_acc


DARTS_HPO_DEFAULT_PRIMITIVES = (
    "separable_convolution_3x3",
    "max_pooling_3x3",
    "skip_connection",
)


def run_darts_hpo_trial(assignments: Dict[str, str], ctx=None, **overrides) -> None:
    """HPO entry point: assignments are individual DartsSearch settings
    (w_lr, alpha_lr, w_momentum, ...) from an HPO suggester (tpe/random/...),
    not the darts suggester's config payload. This is the reference's
    pytorch-mnist-style HPO matrix applied to the DARTS workload — and
    because optimizer hyperparameters are traced (see DartsSearch), every
    trial of the sweep reuses one compiled search step."""
    settings: Dict[str, Any] = dict(assignments)
    settings.update(overrides)
    num_layers = int(settings.pop("num_layers", 3))
    primitives = settings.pop("primitives", list(DARTS_HPO_DEFAULT_PRIMITIVES))
    n_train = int(settings.pop("num_train_examples", 0) or 0) or None
    mesh = None
    if ctx is not None and len(ctx.jax_devices()) > 1:
        mesh = ctx.mesh(axis_names=("data",))

    x, y = load_cifar10("train", n=n_train)
    half = len(x) // 2
    train_data, valid_data = (x[:half], y[:half]), (x[half:], y[half:])

    search = DartsSearch(
        primitives=primitives, num_layers=num_layers, settings=settings, mesh=mesh
    )
    steps_per_epoch = max(half // search.batch_size, 1)
    search.build(x.shape[1:], steps_per_epoch * search.num_epochs)
    best_acc = _search_and_report(search, train_data, valid_data, ctx)
    print(f"Best-accuracy={best_acc}")


def run_darts_trial_scaled(assignments: Dict[str, str], ctx=None, **overrides) -> None:
    """run_darts_trial with algorithm-settings overrides merged in — the
    single place that re-encodes the suggester's settings payload (used by
    CI-scale tests and the bench e2e stage)."""
    settings = json.loads(assignments["algorithm-settings"].replace("'", '"'))
    settings.update(overrides)
    assignments = dict(assignments)
    assignments["algorithm-settings"] = json.dumps(settings)
    run_darts_trial(assignments, ctx)


def run_darts_trial(assignments: Dict[str, str], ctx=None) -> None:
    """Trial entry point — parses the DARTS suggestion assignments
    (run_trial.py main argument parsing) and runs the search, reporting
    Best-Genotype + validation accuracy per epoch."""
    settings = json.loads(assignments["algorithm-settings"].replace("'", '"'))
    search_space = json.loads(assignments["search-space"].replace("'", '"'))
    num_layers = int(assignments["num-layers"])

    # dataset size / epochs can be trimmed via settings for CI-scale runs
    n_train = int(settings.get("num_train_examples", 0) or 0) or None
    mesh = None
    if ctx is not None and len(ctx.jax_devices()) > 1:
        mesh = ctx.mesh(axis_names=("data",))

    x, y = load_cifar10("train", n=n_train)
    half = len(x) // 2
    train_data, valid_data = (x[:half], y[:half]), (x[half:], y[half:])

    search = DartsSearch(
        primitives=search_space,
        num_layers=num_layers,
        settings=settings,
        mesh=mesh,
    )
    steps_per_epoch = max(half // search.batch_size, 1)
    search.build(x.shape[1:], steps_per_epoch * search.num_epochs)
    best_acc = _search_and_report(search, train_data, valid_data, ctx)
    gene = search.genotype()
    # reference run_trial.py prints the best accuracy + genotype at the end
    print(f"Best-accuracy={best_acc}")
    print(f"Best-Genotype={gene}")
