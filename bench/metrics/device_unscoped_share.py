"""Share of the device's busy time in ops of the tick program outside every
``knn.*`` stage scope, in % (profiler trace): what the per-stage device
times leave unnamed."""


def read(run):
    tr = run.get("trace") or {}
    if tr.get("unscoped_s") is None or not tr.get("busy_s"):
        return None
    return 100.0 * tr["unscoped_s"] / tr["busy_s"]
