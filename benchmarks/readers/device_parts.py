"""Device time of named parts of the train step (``katib_tpu.tracing.STEP_PARTS``),
from ``trace_parts.reduce``'s ``part_seconds``: ms a whole step run, or with
``as_share`` the parts' share of the step runs' busy time in %. ``pass_`` is
``forward``, ``backward`` or ``both``. None where the trace carries no
``op_name`` or holds no whole step run."""

PASSES = {"forward": (0,), "backward": (1,), "both": (0, 1)}


def read(run, parts, pass_="both", as_share=False):
    trace = run.trace or {}
    seconds, runs = trace.get("part_seconds"), trace.get("step_runs")
    if not seconds or not runs:
        return None
    spent = sum(seconds.get(part, (0.0, 0.0))[i] for part in parts for i in PASSES[pass_])
    if as_share:
        busy = sum(f + b for f, b in seconds.values())
        return 100.0 * spent / busy if busy > 0 else None
    return 1e3 * spent / runs
