"""Every shipped example spec must parse, default, and pass admission
validation — the reference's examples/ are exercised by its e2e CI; here a
broken example would otherwise only fail in a user's hands."""

import glob
import json
import os


import pytest

from katib_tpu.api import set_defaults, validate_experiment
from katib_tpu.api.spec import ExperimentSpec
from katib_tpu.earlystop.medianstop import registered_early_stoppers
from katib_tpu.suggest.base import registered_algorithms

# Fast, capability-representative module: part of the -m smoke tier.
pytestmark = pytest.mark.smoke

EXAMPLES_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples"
)

EXAMPLES = sorted(
    p
    for p in glob.glob(os.path.join(EXAMPLES_DIR, "**", "*.json"), recursive=True)
    # examples/records/ holds experiment RESULT records (scripts/run_north_star.py),
    # not submit-able specs
    if os.sep + "records" + os.sep not in p
)


@pytest.mark.parametrize("path", EXAMPLES, ids=[os.path.basename(p) for p in EXAMPLES])
def test_example_spec_is_valid(path):
    with open(path) as f:
        raw = json.load(f)
    spec = ExperimentSpec.from_dict(raw)
    assert spec.name, path
    set_defaults(spec)
    validate_experiment(
        spec,
        known_algorithms=registered_algorithms(),
        known_early_stopping=registered_early_stoppers(),
    )


def test_examples_exist():
    assert len(EXAMPLES) >= 14


YAML_EXAMPLES = sorted(
    glob.glob(os.path.join(EXAMPLES_DIR, "**", "*.yaml"), recursive=True)
)


@pytest.mark.parametrize(
    "path", YAML_EXAMPLES, ids=[os.path.basename(p) for p in YAML_EXAMPLES]
)
def test_yaml_example_spec_is_valid(path):
    """YAML examples (Katib CRD envelope) load through the same
    validate/default pipeline as the JSON ones."""
    from katib_tpu.api.spec import load_experiment_document

    with open(path) as f:
        spec = load_experiment_document(f.read())
    assert spec.name, path
    set_defaults(spec)
    validate_experiment(
        spec,
        known_algorithms=registered_algorithms(),
        known_early_stopping=registered_early_stoppers(),
    )


def test_yaml_examples_exist():
    assert len(YAML_EXAMPLES) >= 1


RECORDS_DIR = os.path.join(EXAMPLES_DIR, "records")

RECORDS = sorted(glob.glob(os.path.join(RECORDS_DIR, "*.json")))


@pytest.mark.parametrize("path", RECORDS, ids=[os.path.basename(p) for p in RECORDS])
def test_record_parses(path):
    with open(path) as f:
        json.load(f)


@pytest.mark.parametrize(
    "name", ["darts_hpo_50trials_cpu.json", "darts_hpo_50trials_tpu.json"]
)
def test_north_star_record_contract(name):
    """The stage-2 derived retrain is gated on
    ``verification == 'ok' and optimal_assignments`` — the contract the north
    star script promises (run_north_star.py 'stable contract' comment) must
    hold in every checked-in artifact."""
    # no skip-on-missing: both records are checked in, and a rename or
    # deletion must fail loudly rather than silently skip the contract
    path = os.path.join(RECORDS_DIR, name)
    with open(path) as f:
        rec = json.load(f)
    for key in ("experiment", "algorithm", "n_trials", "n_succeeded",
                "wallclock_s", "platform", "dataset", "verification",
                "optimal_assignments", "trials"):
        assert key in rec, f"{name} missing {key}"
    assert rec["n_trials"] == 50
    # a checked-in record must be the verified full experiment, and its
    # dataset provenance must state what it actually trained on
    assert rec["verification"] == "ok"
    assert rec["n_succeeded"] == 50
    assert rec["optimal_assignments"]
    # dataset provenance must be one of the two explicit forms
    # cifar10_provenance() emits: real CIFAR-10 (with path) or the
    # stand-in WITH the recorded fetch-blocked reason — not merely any
    # string that mentions cifar
    assert rec["dataset"].startswith("real CIFAR-10 npz") or (
        "stand-in" in rec["dataset"] and "blocked" in rec["dataset"]
    ), rec["dataset"]
    assert len(rec["trials"]) == 50
    # derived retrain block, when present, carries the stage-2 evidence
    if "derived_retrain" in rec:
        d = rec["derived_retrain"]
        assert "genotype" in d and "retrain_val_acc" in d
