"""Device time of several kernels together, each found by the name the program
gives it (the device's operation is ``%<name>.<n>``), against the least time
the chip could take for the same calls, in %: the sum over the kernels of calls
x the least time of one call (the family's ``kernel_costs``) over the sum of
their device time. A kernel that the trace does not hold adds nothing to either
sum; none of them there, and there is nothing to read.

``counted`` names counters of the program's step ledger that say how much work
the calls did (a routed layer's ``landed``): the family costs a call at their
mean over the report intervals the trace covers — it is taken as the window
opens — and not at what it expects. Nothing where the program counts no such thing."""

import flops
from readers import step_ledger


def read(run, kernels, counted=()):
    if run.trace is None:
        return None
    counters = {}
    if counted:
        traced_until = run.window.t_open + run.trace["window_s"]
        inside = [i for i in step_ledger.intervals(run)
                  if i["t_end"] - i["seconds"] < traced_until and all(c in i for c in counted)]
        if not inside:
            return None
        counters = {c: sum(i[c] for i in inside) / len(inside) for c in counted}
    costs = run.family.kernel_costs(
        run.config, run.cell["batch_size"], run.cell["seq_len"], **counters)
    least = spent = 0.0
    for op, seconds in run.trace["op_seconds"].items():
        kernel = op.split(" ", 1)[0].lstrip("%").split(".")[0]
        if kernel in kernels:
            spent += seconds
            least += run.trace["op_counts"][op] * flops.roofline_seconds(*costs[kernel], run.peaks)[0]
    if spent <= 0:
        return None
    return 100.0 * least / spent
