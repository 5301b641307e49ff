"""Device time of the sweep's NAV walk over the count pyramid, in ms per
tick of the traced window: the self time of the ops under the tick
program's ``knn.nav`` scope, averaged over the cell's chips (profiler
trace)."""


def read(run):
    stages = (run.get("trace") or {}).get("stages")
    if stages is None or not run.get("ticks"):
        return None
    return stages.get("nav", 0.0) / len(run["ticks"]) * 1e3
