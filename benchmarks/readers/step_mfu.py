"""Operations the steps finished inside the window require (flops.py: no
recomputation) over window seconds x chips x the chip's peak, in %."""

import flops


def read(run):
    w = run.window
    if w.seconds <= 0 or not w.steps:
        return None
    need = w.steps * flops.train_step_flops(run.config, run.cell["batch_size"], run.cell["seq_len"])
    return 100.0 * need / (w.seconds * run.cell["chips"] * run.peaks["bf16_flops_per_s"])
