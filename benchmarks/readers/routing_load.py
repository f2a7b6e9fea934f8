"""How unevenly the held experts are loaded: the largest load of a held expert
over the mean load, both summed over the routed layers by the program and over
the report intervals inside the window (the step ledger's ``load_max`` and
``load_mean`` counters, katib_tpu/parallel/train.py). 1 is an even load; the
grouped products' time follows the sum, the slowest chip of a deployment the
maximum. Nothing where the program counts no such thing."""

from readers import step_ledger


def read(run):
    inside = [i for i in step_ledger.intervals(run) if "load_max" in i and "load_mean" in i]
    mean = sum(i["load_mean"] for i in inside)
    if not inside or mean <= 0:
        return None
    return sum(i["load_max"] for i in inside) / mean
