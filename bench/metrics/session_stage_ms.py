"""Host time per tick in the session layer, in ms (host clock).

``ingest_objects`` + ``update_queries`` + ``submit()`` of each tick of the
window, without the wait for the previous tick on the device, which the
harness takes apart before ``submit()``; the mean over the window's ticks.
"""


def read(run):
    ticks = [t["stage_s"] for t in run.get("ticks", []) if "stage_s" in t]
    if not ticks:
        return None
    return sum(ticks) / len(ticks) * 1e3
