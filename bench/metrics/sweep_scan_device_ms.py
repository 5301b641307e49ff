"""Device time of the sweep's SCAN (the masked distance tile and the top-k
merge), in ms per tick of the traced window: the self time of the ops
under the tick program's ``knn.scan`` scope, averaged over the cell's
chips (profiler trace)."""


def read(run):
    stages = (run.get("trace") or {}).get("stages")
    if stages is None or not run.get("ticks"):
        return None
    return stages.get("scan", 0.0) / len(run["ticks"]) * 1e3
