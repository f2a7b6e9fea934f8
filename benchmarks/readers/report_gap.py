"""The longest gap between two consecutive reports inside the window, in ms: a
stall of the report path or of the step shows here."""


def read(run):
    w = run.window
    times = [r[0] for r in run.reports if w.t_open <= r[0] <= w.t_close]
    gaps = [b - a for a, b in zip(times, times[1:])]
    return 1e3 * max(gaps) if gaps else None
