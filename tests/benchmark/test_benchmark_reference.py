"""reference_lm.py against the program at the tiny size on the CPU, in float32:
the weights from the seed, the loss and three AdamW steps. And its control: the
same reference one precision below the stated one has to be refused."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import check  # noqa: E402
import reference_lm  # noqa: E402

SHAPE = reference_lm.LMShape(vocab_size=256, embed_dim=64, num_layers=2, num_heads=2)
BATCH, SEQ = 4, 32
with open(os.path.join(ROOT, "benchmarks", "workloads", "tiny.steady.json")) as _f:
    LIMITS = json.load(_f)["limits"]


@pytest.fixture(scope="module")
def program():
    """TransformerLM + make_lm_train_step in float32: three steps at lr 1e-3."""
    import jax
    import jax.numpy as jnp

    from katib_tpu.models.transformer import TransformerConfig
    from katib_tpu.parallel.mesh import make_mesh
    from katib_tpu.parallel.train import make_lm_train_step

    config = TransformerConfig(
        vocab_size=256, embed_dim=64, num_layers=2, num_heads=2, max_seq_len=SEQ,
        dtype=jnp.float32)
    params, opt_state, step_fn, put_batch = make_lm_train_step(config, make_mesh(None), 1e-3)
    first = jax.tree.map(jnp.copy, params)
    batch = put_batch(*reference_lm.make_batch(256, BATCH, SEQ))
    losses, grad_norm = [], None
    with jax.default_matmul_precision("highest"):
        for i in range(3):
            params, opt_state, loss = step_fn(params, opt_state, *batch)
            losses.append(float(loss))
            if i == 0:
                grad_norm = {
                    k: float(v) / (1 - reference_lm.B1)
                    for k, v in reference_lm.leaf_norms(opt_state[0].mu).items()}
    delta = reference_lm.leaf_norms(jax.tree.map(jnp.subtract, params, first))
    return {"first": first, "loss": losses, "grad_norm": grad_norm,
            "delta_norm": {k: float(v) for k, v in delta.items()}}


@pytest.fixture(scope="module")
def reference():
    return reference_lm.Reference(SHAPE, BATCH, SEQ).run(1e-3)


def test_weights_from_the_seed_are_the_programs(program):
    import jax
    import numpy as np

    ours = jax.tree_util.tree_flatten_with_path(reference_lm.init_params(SHAPE))[0]
    theirs = jax.tree_util.tree_flatten_with_path(program["first"])[0]
    assert [p for p, _ in ours] == [p for p, _ in theirs]
    for (_, a), (_, b) in zip(ours, theirs):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=1e-7)


def test_loss_of_three_adamw_steps_agrees(program, reference):
    assert reference["loss"] == pytest.approx(program["loss"], rel=2e-6)


def test_gradient_and_change_agree_leaf_by_leaf(program, reference):
    gaps = check.training_gaps(program, reference)
    assert gaps["loss_gap"] < 2e-6 and gaps["grad_norm_gap"] < 1e-4 and gaps["delta_norm_gap"] < 1e-4


@pytest.fixture(scope="module")
def by_precision():
    """One compile of each; the learning rate is an argument."""
    return {p: reference_lm.Reference(SHAPE, BATCH, SEQ, precision=p)
            for p in ("float32", "bfloat16", "float8")}


@pytest.mark.parametrize("learning_rate", [3e-5, 4e-4, 3e-3])
def test_control_one_precision_below_is_refused(by_precision, learning_rate):
    """The configuration states bfloat16; float8 operands are the step below.
    The reference in float8, put in the program's place, must come out as not
    correct by at least one number; in bfloat16 it must pass."""
    ref, stated, below = (by_precision[p].run(learning_rate) for p in ("float32", "bfloat16", "float8"))
    assert check.verdict(check.compare_training([stated], [ref], LIMITS))
    assert not check.verdict(check.compare_training([below], [ref], LIMITS))


@pytest.mark.parametrize("fault", [{"rows": BATCH // 2}, {"frozen": True}], ids=["half_batch", "state_unchanged"])
def test_faults_in_the_reference_put_in_the_programs_place_are_refused(fault, reference):
    broken = reference_lm.Reference(SHAPE, BATCH, SEQ, **fault).run(1e-3)
    assert not check.verdict(check.compare_training([broken], [reference], LIMITS))
