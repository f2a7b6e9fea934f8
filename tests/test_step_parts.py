"""The train step's parts (``tracing.STEP_PARTS``): how an ``op_name`` is read
(``part_of``), the one way a part is opened (``step_part``), and the tiny dense
and hybrid steps compiled on the CPU, whose operations carry the parts the
model uses."""

import collections
import os
import re

import numpy as np
import pytest

from katib_tpu.tracing import STEP_PARTS, part_of, step_part

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PATHS = {
    "bare": ("jit(step)/optimizer/add", ("optimizer", False)),
    "inside_jvp": ("jit(step)/jvp(embed)/gather", ("embed", False)),
    "inside_transpose_of_jvp": ("jit(step)/transpose(jvp(dispatch))/jvp(dispatch)/checkpoint/cos",
                                ("dispatch", True)),
    "innermost_wins": ("jit(step)/jvp(TransformerLM)/block0/attn/attention/flash/transpose",
                       ("flash", False)),
    "backward_from_an_outer_transpose": (
        "jit(step)/transpose(jvp(TransformerLM))/block2/mlp/mlp/up/dot_general", ("mlp", True)),
    "unnamed": ("jit(step)/transpose(jvp(TransformerLM))/block0/add_any", ("unnamed", True)),
    "no_path": ("", ("unnamed", False)),
    "a_function_named_like_a_part_is_no_part": ("jit(step)/jit(combine)/add", ("unnamed", False)),
    "rematted_computation_of_the_tiny_hybrid_step": (
        "jit(step)/transpose(jvp(TransformerLM))/block1/block1._hybrid/checkpoint/"
        "rematted_computation/block1.attend/attn/linear_attention/attn/while/body/kda_parts/"
        "jvp(kda_chunks)", ("kda_parts", True)),
}


@pytest.mark.parametrize("case", sorted(PATHS))
def test_part_of_reads_the_innermost_part_and_the_pass(case):
    op_name, expected = PATHS[case]
    assert part_of(op_name) == expected


def test_step_part_refuses_a_name_outside_the_vocabulary():
    with pytest.raises(ValueError, match="STEP_PARTS"):
        step_part("block")
    assert len(set(STEP_PARTS)) == len(STEP_PARTS) and "unnamed" not in STEP_PARTS


def test_step_part_names_the_operations_and_changes_nothing_else():
    import jax
    import jax.numpy as jnp

    def f(x):
        with step_part("optimizer"):
            return jnp.sin(x) * 2.0

    def g(x):
        return jnp.sin(x) * 2.0

    x = jnp.ones((8,))
    scoped, plain = (jax.jit(h).lower(x).as_text(debug_info=True) for h in (f, g))
    assert "optimizer" in scoped and "optimizer" not in plain
    assert jax.jit(f)(x).tolist() == jax.jit(g)(x).tolist()


# -- the compiled tiny steps ------------------------------------------------

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_FREE = {"parameter", "get-tuple-element", "tuple", "constant", "bitcast", "after-all"}


def _parts_of_compiled(text):
    """{(part, backward): count} over the instructions the step schedules (those
    of fused and applied computations left out), a fusion by its root's
    op_name (else the last one named inside it); instructions the compiler
    made with no op_name at all are counted apart as ``None``."""
    computations, current = {}, None
    for line in text.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{") and "(" in line:
            current = "ENTRY" if line.startswith("ENTRY") else line.split()[0].lstrip("%")
            computations[current] = []
        elif line.startswith("  ") and current and " = " in line:
            computations[current].append(line.strip())
    called = {name.strip().lstrip("%") for lines in computations.values() for line in lines
              for m in re.finditer(r"(?:calls|to_apply|comparator)=([%\w.\-, ]+)", line)
              for name in m.group(1).split(",")}
    counts = collections.Counter()
    for name, lines in computations.items():
        if name in called:
            continue
        for line in lines:
            opcode = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + line.split(" = ", 1)[1]).group(1)
            if opcode in _FREE:
                continue
            found = _OP_NAME.search(line)
            if not found and opcode == "fusion":
                body = computations[re.search(r"calls=%?([\w.\-]+)", line).group(1)]
                roots = [x for x in body if x.startswith("ROOT ") and _OP_NAME.search(x)]
                named = [x for x in body if _OP_NAME.search(x)]
                found = _OP_NAME.search((roots or named[-1:] or [""])[0])
            counts[part_of(found.group(1)) if found else None] += 1
    return counts


def _tiny_step_text(config):
    from katib_tpu.parallel.train import make_lm_train_step

    params, opt_state, step, put_batch = make_lm_train_step(config, None, 1e-3)
    data = np.random.default_rng(0).integers(0, config.vocab_size, size=(2, 65), dtype=np.int32)
    return step.lower(params, opt_state, *put_batch(data[:, :-1], data[:, 1:])).compile().as_text()


def _dense():
    from katib_tpu.models.transformer import TransformerConfig

    return TransformerConfig(vocab_size=256, embed_dim=64, num_layers=2, num_heads=4, max_seq_len=64)


def _hybrid():
    from katib_tpu.models.architecture import architecture_config, load_architecture

    return architecture_config(
        load_architecture(os.path.join(REPO, "benchmarks", "configs", "tiny-hybrid.json")), 64)


USED = {
    "dense": (_dense, {"embed", "attention", "flash", "mlp", "norm_residual", "head_loss", "optimizer"}),
    "hybrid": (_hybrid, set(STEP_PARTS)),
}


@pytest.mark.parametrize("model", sorted(USED))
def test_compiled_tiny_step_names_every_part_it_uses(model):
    """An order check, not a time: every part the model uses is in the
    compiled step, and under 5 % of its instructions that carry an
    ``op_name`` lie outside the vocabulary."""
    build, used = USED[model]
    counts = _parts_of_compiled(_tiny_step_text(build()))
    named = {key: n for key, n in counts.items() if key is not None}
    assert {part for part, _ in named} - {"unnamed"} == used
    assert {part for part, backward in named if backward} >= used - {"optimizer"}
    unnamed = sum(n for (part, _), n in named.items() if part == "unnamed")
    assert unnamed < 0.05 * sum(named.values()), counts
