"""JAX's own backend-compile events (jax.monitoring) that ended inside the
window: their count, or their seconds per trial completed."""


def read(run, per):
    w = run.window
    inside = [seconds for t, seconds in run.compiles if w.t_open < t <= w.t_close]
    if per == "count":
        return float(len(inside))
    done = len(w.trials) - len(w.failed)
    return sum(inside) / done if done > 0 else None
