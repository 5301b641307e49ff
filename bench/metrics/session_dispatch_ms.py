"""Host time of the session's dispatch, in ms per tick of the traced
window: the ``knn.session.dispatch`` spans (registry staging and the tick
step's call) on the trace's clock."""


def read(run):
    spans = (run.get("trace") or {}).get("program_spans") or {}
    if "session.dispatch" not in spans or not run.get("ticks"):
        return None
    return spans["session.dispatch"][0] / len(run["ticks"]) * 1e3
