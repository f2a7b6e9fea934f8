"""Dataset loading for trial workloads.

The reference trial images download CIFAR-10/MNIST via torchvision/Keras at
container start. This environment has no network egress, so loaders look for
an on-disk copy first and otherwise generate a synthetic stand-in with
identical shapes/dtypes — search dynamics and benchmarks exercise the same
compute graph either way.

The stand-in is deliberately calibrated to *discriminate* (round-4 review:
the earlier single-template-per-class task saturated at val_acc 1.0 for half
of the benchmark's 50 trials, so optimal-trial selection and the suggesters'
rankings were exercised on a degenerate objective). Difficulty comes from
four compounding sources so accuracy tracks model capacity and optimizer
hyperparameters instead of pegging at the ceiling:

- intra-class variation: each class is a bank of prototype patterns and each
  sample a random convex mixture of them, so memorizing one template fails;
- class overlap: consecutive classes share their low-frequency component and
  differ only in the second, finer component;
- nuisance transforms: per-sample random translation (cyclic shift) and
  amplitude jitter, rewarding architectures with spatial pooling;
- distractors + noise: a low-amplitude pattern from a *different* class is
  overlaid and Gaussian pixel noise added.

At the TPU benchmark budget (192 search steps/trial: 6 epochs x 4096
examples, 8-channel supernet — scripts/run_north_star.py uses exactly
this) accuracy spans roughly chance to ~0.9 across an
HPO sweep; measured anchors: a 12/24-channel Adam CNN reaches ~0.9 in 96
steps at lr 3e-3 vs ~0.35 at lr 1e-4, and a 4-channel supernet at 192
steps reaches 0.44 (tests/test_datasets.py pins the contract).
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import numpy as np

CIFAR10_ENV = "KATIB_TPU_CIFAR10"  # path to an .npz with x_train/y_train/x_test/y_test

# Difficulty calibration (see module docstring). Env-overridable so record
# captures can note the exact knobs in provenance. Read ONCE at import —
# set KATIB_TPU_SYNTH_* before importing katib_tpu, not after (a later
# setenv is a silent no-op).
#
# Label noise defaults OFF: every trial workload (darts_trainer, enas_child,
# darts_derived) carves its validation split out of load_*("train"), so
# train-split noise would corrupt the very labels trials are scored on and
# silently cap the reported ceiling. The knob exists for experiments that
# bring their own clean eval split.
SYNTH_NOISE = float(os.environ.get("KATIB_TPU_SYNTH_NOISE", "0.45"))
SYNTH_DISTRACTOR = float(os.environ.get("KATIB_TPU_SYNTH_DISTRACTOR", "0.3"))
SYNTH_VARIANTS = int(os.environ.get("KATIB_TPU_SYNTH_VARIANTS", "4"))
SYNTH_TRAIN_LABEL_NOISE = float(os.environ.get("KATIB_TPU_SYNTH_LABEL_NOISE", "0.0"))


def _prototype_bank(
    num_classes: int, image_size: int, channels: int, variants: int
) -> np.ndarray:
    """[num_classes, variants, S, S, C] bank of class patterns.

    Class c and c+1 share the coarse component (fx, fy); the variant-specific
    fine component carries the class identity, so coarse features alone
    cannot separate neighbours."""
    yy, xx = np.meshgrid(np.arange(image_size), np.arange(image_size), indexing="ij")
    proto_rng = np.random.default_rng(1234)  # bank is fixed; samples vary
    bank = np.zeros(
        (num_classes, variants, image_size, image_size, channels), dtype=np.float32
    )
    for c in range(num_classes):
        shared = c // 2  # consecutive class pairs share the coarse component
        fx, fy = 1 + shared % 3, 1 + (shared // 3) % 3
        coarse = np.sin(2 * np.pi * (fx * xx + fy * yy) / image_size + shared * 0.9)
        for v in range(variants):
            gx = int(proto_rng.integers(3, 7))
            gy = int(proto_rng.integers(3, 7))
            psi = float(proto_rng.uniform(0, 2 * np.pi)) + c * 2.1
            fine = np.sin(2 * np.pi * (gx * xx + gy * yy) / image_size + psi)
            for ch in range(channels):
                chan_gain = 0.6 + 0.4 * ((c + ch) % 2)
                bank[c, v, :, :, ch] = (0.5 * coarse + 1.0 * fine) * chan_gain
    return bank


def _synthetic_images(
    n: int,
    num_classes: int,
    image_size: int,
    channels: int,
    rng: np.random.Generator,
    noise: float = SYNTH_NOISE,
    label_noise: float = 0.0,
) -> Tuple[np.ndarray, np.ndarray]:
    """Capacity-discriminative synthetic image classification task."""
    variants = max(1, SYNTH_VARIANTS)
    bank = _prototype_bank(num_classes, image_size, channels, variants)
    ys = rng.integers(0, num_classes, size=n)

    # random convex mixture over the class's variants (intra-class variation)
    w = rng.dirichlet(np.ones(variants) * 0.7, size=n).astype(np.float32)
    xs = np.einsum("nv,nvhwc->nhwc", w, bank[ys])

    # distractor overlay from a different class, random variant
    offs = rng.integers(1, num_classes, size=n)
    yd = (ys + offs) % num_classes
    vd = rng.integers(0, variants, size=n)
    xs = xs + SYNTH_DISTRACTOR * bank[yd, vd]

    # nuisance transforms: per-sample cyclic translation (bounded to a
    # quarter of the frame, so partial rather than total phase invariance
    # is required) + amplitude jitter
    max_shift = max(1, image_size // 4)
    sh = rng.integers(-max_shift, max_shift + 1, size=(n, 2))
    rows = (np.arange(image_size)[None, :] + sh[:, 0:1]) % image_size  # [n, S]
    cols = (np.arange(image_size)[None, :] + sh[:, 1:2]) % image_size
    xs = xs[np.arange(n)[:, None, None], rows[:, :, None], cols[:, None, :], :]
    xs = xs * rng.uniform(0.7, 1.3, size=(n, 1, 1, 1)).astype(np.float32)

    xs = xs + noise * rng.standard_normal(xs.shape).astype(np.float32)

    if label_noise > 0:
        flip = rng.random(n) < label_noise
        ys = np.where(flip, rng.integers(0, num_classes, size=n), ys)
    return xs.astype(np.float32), ys.astype(np.int32)


def load_cifar10(
    split: str = "train",
    n: Optional[int] = None,
    seed: int = 0,
) -> Tuple[np.ndarray, np.ndarray]:
    """CIFAR-10 (NHWC float32 in [-1,1]-ish, int32 labels). Falls back to a
    synthetic 32x32x3/10-class dataset when no local copy exists."""
    path = os.environ.get(CIFAR10_ENV)
    if path and os.path.exists(path):
        data = np.load(path)
        x = data[f"x_{split}"].astype(np.float32)
        y = data[f"y_{split}"].astype(np.int32).reshape(-1)
        if x.ndim == 4 and x.shape[1] == 3:  # NCHW -> NHWC
            x = x.transpose(0, 2, 3, 1)
        if x.max() > 2.0:
            x = (x / 127.5) - 1.0
        if n is not None:
            x, y = x[:n], y[:n]
        return x, y
    rng = np.random.default_rng(seed if split == "train" else seed + 1)
    count = n if n is not None else (50000 if split == "train" else 10000)
    return _synthetic_images(
        count, 10, 32, 3, rng,
        label_noise=SYNTH_TRAIN_LABEL_NOISE if split == "train" else 0.0,
    )


def load_mnist(
    split: str = "train", n: Optional[int] = None, seed: int = 0
) -> Tuple[np.ndarray, np.ndarray]:
    """MNIST-shaped dataset (28x28x1, 10 classes), synthetic fallback."""
    rng = np.random.default_rng(seed if split == "train" else seed + 1)
    count = n if n is not None else (60000 if split == "train" else 10000)
    return _synthetic_images(
        count, 10, 28, 1, rng,
        label_noise=SYNTH_TRAIN_LABEL_NOISE if split == "train" else 0.0,
    )


DIGITS_PROVENANCE = (
    "real UCI handwritten digits (sklearn.datasets.load_digits: 1797 8x8 "
    "grayscale images, 10 classes) — genuine real-world data bundled with "
    "scikit-learn, the only real image-classification dataset available "
    "in this zero-egress environment"
)


def load_digits(
    split: str = "train",
    n: Optional[int] = None,
    seed: int = 0,
    image_size: int = 8,
    channels: int = 1,
) -> Tuple[np.ndarray, np.ndarray]:
    """REAL image data: the UCI handwritten-digits set bundled with
    scikit-learn (1797 8x8 grayscale images, 10 classes).

    Every other loader in this module falls back to a synthetic stand-in
    because CIFAR-10/MNIST downloads are blocked by zero egress (round-4
    review, Missing #1: 'real-dataset accuracy parity' was the top evidence
    gap). This one never synthesizes: the pixels are genuine scans of
    handwritten digits, so HPO records built on it verify the real-data
    axis — small scale, honestly labeled (see DIGITS_PROVENANCE).

    Deterministic 80/20 shuffle-split (1437 train / 360 val) with a fixed
    split seed so train/val are disjoint across calls regardless of
    ``seed``, which only controls subset sampling when ``n`` is given.
    ``image_size`` (multiple of 8) nearest-neighbour-upsamples for models
    built for larger frames; ``channels`` tiles grayscale for RGB stems.
    Values are scaled from [0, 16] to [-1, 1]. ``n`` is capped at the
    split's true size — 1797 real samples is what exists.
    """
    from sklearn.datasets import load_digits as _sk_digits

    d = _sk_digits()
    x = d.images.astype(np.float32) / 8.0 - 1.0
    y = d.target.astype(np.int32)
    split_rng = np.random.default_rng(7)  # split is fixed; never reseeded
    idx = split_rng.permutation(len(x))
    x, y = x[idx], y[idx]
    n_train = (len(x) * 4) // 5
    if split == "train":
        x, y = x[:n_train], y[:n_train]
    else:
        x, y = x[n_train:], y[n_train:]
    if image_size != 8:
        if image_size % 8:
            raise ValueError(f"image_size must be a multiple of 8, got {image_size}")
        k = image_size // 8
        x = np.kron(x, np.ones((1, k, k), dtype=np.float32))
    x = x[..., None]
    if channels > 1:
        x = np.tile(x, (1, 1, 1, channels))
    if n is not None and n < len(x):
        sel = np.random.default_rng(seed).permutation(len(x))[:n]
        x, y = x[sel], y[sel]
    return np.ascontiguousarray(x), np.ascontiguousarray(y)


def load_dataset(
    name: str,
    split: str = "train",
    n: Optional[int] = None,
    image_size: int = 32,
    channels: int = 3,
) -> Tuple[np.ndarray, np.ndarray]:
    """One-line dataset dispatch for trial workloads: ``"digits"`` is the
    REAL bundled UCI scans adapted to the requested stem shape; anything
    else is the CIFAR-10 loader (real npz when present, calibrated
    synthetic stand-in otherwise). Keeps the digits adapter arguments in
    one place so every record family trains on identically shaped data.
    Unknown names raise — a typo must not silently train on the synthetic
    stand-in while the record claims real-digits provenance."""
    if name == "digits":
        return load_digits(split, n=n, image_size=image_size, channels=channels)
    if name in ("cifar", "cifar10"):
        return load_cifar10(split, n=n)
    raise ValueError(f"unknown dataset {name!r}; expected 'digits' or 'cifar'")


def batches(x: np.ndarray, y: np.ndarray, batch_size: int, rng: np.random.Generator):
    """Shuffled full-epoch batch iterator (drops the ragged tail so shapes
    stay static for jit)."""
    idx = rng.permutation(len(x))
    n_batches = len(x) // batch_size
    for i in range(n_batches):
        sel = idx[i * batch_size : (i + 1) * batch_size]
        yield x[sel], y[sel]
