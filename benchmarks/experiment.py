"""From a cell's files to the experiment document a user would write
(examples/distributed-lm.json has the same shape), as chip_smoke.py builds it:
the model's sizes as one-value parameters of ``run_lm_trial``.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))

# published config.json key -> the trial's parameter
SIZE_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "embed_dim",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
}
MLP_RATIO = 4  # the only ratio run_lm_trial builds


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(workload: str) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    cell = load_json("workloads", f"{workload}.json")
    config = load_json("configs", f"{cell['config']}.json")
    if config["intermediate_size"] != MLP_RATIO * config["hidden_size"]:
        raise ValueError("run_lm_trial builds an MLP of 4 x hidden only")
    if config.get("num_key_value_heads", config["num_attention_heads"]) != config["num_attention_heads"]:
        raise ValueError("run_lm_trial builds multi-head attention only")
    if cell["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("the cell's sequence is longer than the configuration's positions")
    return cell, config


def fixed_assignments(cell: Dict[str, Any], config: Dict[str, Any]) -> Dict[str, str]:
    fixed = {param: str(config[key]) for key, param in SIZE_KEYS.items()}
    fixed.update(
        seq_len=str(cell["seq_len"]), batch_size=str(cell["batch_size"]),
        num_steps=str(cell["num_steps"]), tensor_parallel="1",
    )
    return fixed


def _one_value(name: str, value: str) -> Dict[str, Any]:
    return {"name": name, "parameterType": "discrete", "feasibleSpace": {"list": [value]}}


def experiment_document(name: str, cell: Dict[str, Any], config: Dict[str, Any],
                        seed: int) -> Dict[str, Any]:
    searched = [
        {"name": p, "parameterType": "double", "feasibleSpace": dict(space)}
        for p, space in cell["search_space"].items()
    ]
    settings = dict(cell["algorithm"].get("algorithmSettings", {}))
    # the seed reaches the program here and nowhere else
    settings["random_state"] = str(seed % 2147483647)
    return {
        "name": name,
        "parameters": searched + [_one_value(k, v) for k, v in fixed_assignments(cell, config).items()],
        "objective": {"type": "minimize", "objectiveMetricName": "loss"},
        "algorithm": {
            "algorithmName": cell["algorithm"]["algorithmName"],
            "algorithmSettings": [{"name": k, "value": str(v)} for k, v in settings.items()],
        },
        "trialTemplate": {
            "entryPoint": cell["entry_point"],
            "trialParameters": [],
            "resources": {"numDevices": cell["chips"], "numHosts": 1},
        },
        "maxTrialCount": cell["maxTrialCount"],
        "parallelTrialCount": cell["parallelTrialCount"],
        "maxFailedTrialCount": 0,
    }
