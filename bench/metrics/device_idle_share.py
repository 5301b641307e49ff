"""Share of the traced window in which no operation ran on the device, in
%: 1 - (union of the device's op intervals) / window, averaged over the
cell's chips (profiler trace)."""


def read(run):
    tr = run.get("trace")
    if not tr or tr.get("idle_share") is None or not tr.get("events"):
        return None
    return tr["idle_share"] * 100.0
