"""Seconds of a trial's cycle that are neither suggestion, compile nor steps:
its root span minus those three, plus the gap between the previous trial's end
and this trial's start (reconcile, the run loop's wait). Mean per trial
completed in the window."""

INNER = ("suggestion", "compile", "steps")


def read(run):
    roots = {}
    for trial, spans in run.spans.items():
        root = next((s for s in spans if s["name"] == "trial" and s.get("end")), None)
        if root is not None:
            roots[trial] = (root, spans)
    order = sorted(roots, key=lambda t: roots[t][0]["start"])
    values = []
    for i, trial in enumerate(order):
        if trial not in run.window.trials or i == 0:
            continue
        root, spans = roots[trial]
        inner = sum(s["end"] - s["start"] for s in spans if s["name"] in INNER and s.get("end"))
        gap = max(0.0, root["start"] - roots[order[i - 1]][0]["end"])
        values.append(root["end"] - root["start"] - inner + gap)
    return sum(values) / len(values) if values else None
