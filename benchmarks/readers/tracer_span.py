"""Mean seconds of one named span of the program's Tracer, per trial completed
in the window."""


def read(run, span):
    durations = [
        sum(s["end"] - s["start"] for s in run.spans.get(trial, []) if s["name"] == span and s.get("end"))
        for trial in run.window.trials
        if trial in run.spans
    ]
    return sum(durations) / len(durations) if durations else None
