"""The program's own step ledger (the ``intervals`` ring on the ``steps`` span
of its Tracer, katib_tpu/tracing.py StepLedger): where the trial thread's time
went between two reports, over the intervals inside the window.

A trial's spans are found by overlap with the window (a window that closes on
a report completes no trial). An interval lies in the window when its middle
does: the window's edges are the harness's stamps of two reports' entries,
microseconds before the program's own, so an edge is never near a middle."""


def intervals(run):
    """The intervals inside the window, each a dict by the ledger's field names;
    [] where the program keeps no ledger."""
    w = run.window
    found = []
    for spans in run.spans.values():
        for s in spans:
            attrs = s.get("attrs") or {}
            if s["name"] != "steps" or "intervals" not in attrs or not s.get("end"):
                continue
            if s["end"] <= w.t_open or s["start"] >= w.t_close:
                continue
            for row in attrs["intervals"]:
                i = dict(zip(attrs["interval_fields"], row))
                if w.t_open < i["t_end"] - i["seconds"] / 2.0 <= w.t_close:
                    found.append(i)
    return found


def read(run, what):
    inside = intervals(run)
    steps = sum(i["steps"] for i in inside)
    if not inside or not steps:
        return None
    if what == "host_ms_per_step":  # report path + the trial function's own Python
        return 1e3 * sum(i["seconds"] - i["dispatch_s"] - i["wait_s"] for i in inside) / steps
    if what == "dispatch_ms_per_step":
        return 1e3 * sum(i["dispatch_s"] for i in inside) / steps
    if what == "wait_max_ms":
        return 1e3 * max(i["wait_s"] for i in inside)
    if what == "report_max_ms":
        return 1e3 * max(i["report_s"] for i in inside)
    raise ValueError(what)
