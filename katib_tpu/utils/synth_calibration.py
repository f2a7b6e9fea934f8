"""TPU-rung difficulty overrides for the synthetic stand-in.

`utils/datasets.py` reads its KATIB_TPU_SYNTH_* knobs once at import. The
round-5 defaults there are calibrated for the CPU-scale records; at the TPU
benchmark rung (8-channel supernet, 192 search steps —
scripts/run_north_star.py --tpu) those
defaults leave the ceiling too wide: any decent w_lr reaches ~1.0, TPE
exploits into the plateau, and the 50-trial quartiles degenerate
(examples/records/darts_hpo_50trials_tpu.json, 2026-08-01 first recapture).

This module is the single home of the harder TPU-rung knob set, chosen by
the measured sweep in scripts/calibrate_tpu_objective.py. It must stay
import-light (no heavy deps, no katib_tpu.utils.datasets import): callers
apply the overrides to os.environ BEFORE anything imports datasets.

An empty TPU_RUNG_KNOBS means "not yet calibrated" — apply() is a no-op
and the rung runs at the datasets.py defaults.
"""

from __future__ import annotations

import os
from typing import Dict, MutableMapping, Optional

# Chosen by scripts/calibrate_tpu_objective.py (good/mid/bad optimizer
# probes at the exact north-star TPU scale). Values are strings because
# they land in os.environ.
#
# Current set: candidate 1 (noise 1.0 / distractor 0.6 / variants 6).
# Provenance: the on-chip sweep measured candidate 0 (0.8/0.5/6) still
# saturating at the optimum (supernet good-probe 0.983) before the chip
# was lost; a CPU CNN-proxy sweep (2026-08-01, /tmp sweep recorded in the
# round-5 map) placed candidate 1 at 3x candidate 0's difficulty (CNN
# good-probe 0.596 -> 0.203) with candidates 2-3 at chance, bracketing
# the sub-saturating ceiling between 1 and 2. On-chip confirmation
# (ROADMAP R1) re-stamps this block.
TPU_RUNG_KNOBS: Dict[str, str] = {
    "KATIB_TPU_SYNTH_NOISE": "1.0",
    "KATIB_TPU_SYNTH_DISTRACTOR": "0.6",
    "KATIB_TPU_SYNTH_VARIANTS": "6",
}


def apply_tpu_rung_knobs(
    env: Optional[MutableMapping[str, str]] = None,
) -> Dict[str, str]:
    """Set the TPU-rung difficulty knobs into ``env`` (default os.environ),
    set-if-unset so an operator's explicit KATIB_TPU_SYNTH_* override always
    wins. Returns the knobs actually applied. Call BEFORE importing
    katib_tpu.utils.datasets (the knobs are read there at import time)."""
    if env is None:
        env = os.environ
    applied: Dict[str, str] = {}
    for key, value in TPU_RUNG_KNOBS.items():
        if key not in env:
            env[key] = value
            applied[key] = value
    return applied
