"""Device time of one flash-attention kernel, found by the name the program
gives its ``pallas_call`` (the device's operation is ``%<name>.<n>``), against
the least time the chip could take for the same calls, in %. Each call is
credited what the shapes require of that pass (flops.flash_attention_cost's
``forward`` or ``backward_each``), whatever implements it."""

import flops


def read(run, kernel, part):
    if run.trace is None:
        return None
    cfg = run.config
    cost = flops.flash_attention_cost(
        run.cell["batch_size"], run.cell["seq_len"], cfg["num_attention_heads"],
        cfg["hidden_size"] // cfg["num_attention_heads"],
    )
    least, _ = flops.roofline_seconds(*cost[part], run.peaks)
    calls = spent = 0.0
    for op, seconds in run.trace["op_seconds"].items():
        if op.split(" ", 1)[0].lstrip("%").split(".")[0] == kernel:
            spent += seconds
            calls += run.trace["op_counts"][op]
    if spent <= 0:
        return None
    return 100.0 * calls * least / spent
