"""Host time of the session's finalize, in ms per tick of the traced
window: the ``knn.session.finalize`` spans (the previous tick's scalar
readbacks and the drift decision, a drift rebuild nested inside) on the
trace's clock."""


def read(run):
    spans = (run.get("trace") or {}).get("program_spans") or {}
    if "session.finalize" not in spans or not run.get("ticks"):
        return None
    return spans["session.finalize"][0] / len(run["ticks"]) * 1e3
