"""Device time of the flash-attention kernels in the traced window against the
least time the chip could take for the same calls (flops.py; the larger of
operations over peak and bytes over peak), in %.

The kernels are found by name: in this program's step every operation whose
name holds one of ``kernels`` is a call of the forward kernel or of one of the
two backward kernels, as often each. So every call is credited a third of what
one layer needs: forward plus the two backward kernels."""

import flops


def read(run, kernels):
    if run.trace is None:
        return None
    cfg = run.config
    cost = flops.flash_attention_cost(
        run.cell["batch_size"], run.cell["seq_len"], cfg["num_attention_heads"],
        cfg["hidden_size"] // cfg["num_attention_heads"],
    )
    forward, _ = flops.roofline_seconds(*cost["forward"], run.peaks)
    backward, _ = flops.roofline_seconds(*cost["backward_each"], run.peaks)
    calls = spent = 0.0
    for op, seconds in run.trace["op_seconds"].items():
        if any(k in op for k in kernels):
            spent += seconds
            calls += run.trace["op_counts"][op]
    if spent <= 0:
        return None
    return 100.0 * calls * (forward + 2.0 * backward) / 3.0 / spent
