"""Device time of the index refresh (the tick step's maintenance branch),
in ms per tick of the traced window: the self time of the ops under the
tick program's ``knn.reindex`` scope, averaged over the cell's chips
(profiler trace)."""


def read(run):
    stages = (run.get("trace") or {}).get("stages")
    if stages is None or not run.get("ticks"):
        return None
    return stages.get("reindex", 0.0) / len(run["ticks"]) * 1e3
