"""End-to-end metrics, by name. Each takes the run (rundata.RunData) and gives a
number, or None where this cell's window has nothing of the kind."""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple


def train_tokens_per_s(run) -> Optional[float]:
    """Tokens of every step finished inside the window over its seconds."""
    w = run.window
    if run.cell["window"]["closes_on"] != "report" or w.seconds <= 0 or not w.steps:
        return None
    return w.steps * run.cell["batch_size"] * run.cell["seq_len"] / w.seconds


def chip_s_per_trial(run) -> Optional[float]:
    """Chips x window seconds over trials completed in the window."""
    w = run.window
    done = len(w.trials) - len(w.failed)
    if run.cell["window"]["closes_on"] != "trial" or done <= 0:
        return None
    return run.cell["chips"] * w.seconds / done


def setup_s(run) -> Optional[float]:
    """Process start to the opening of the window: imports, backend, controller,
    compile and the warm-up reports or trials."""
    return run.window.t_open - run.t_start


# name -> (function, unit); BENCHMARK.json says which of them a listed cell reports
END_TO_END: Dict[str, Tuple[Callable, str]] = {
    "train_tokens_per_s": (train_tokens_per_s, "tokens/s"),
    "chip_s_per_trial": (chip_s_per_trial, "s"),
    "setup_s": (setup_s, "s"),
}
