"""Seconds of the named children of the program's ``compile`` span (``build``,
``stage_batch``; ``jaxpr_trace``, ``lower``, ``backend_compile`` from JAX's own
timers on the trial's thread), summed over the trials begun before the window
opened: parts of set-up. None where the program records no such child."""


def read(run, spans):
    total, found = 0.0, False
    for trial in run.spans.values():
        stage = next((s for s in trial if s["name"] == "compile" and s.get("end")), None)
        if stage is None or stage["start"] >= run.window.t_open:
            continue
        for s in trial:
            if s["name"] in spans and s.get("parentId") == stage["spanId"] and s.get("end"):
                total += s["end"] - s["start"]
                found = True
    return total if found else None
