"""Host time of the window's object reports, in ms per tick (host clock).

The mean over the window's ticks of the ``update_objects`` calls each tick
made: the host dedup and padding, the transfer of the batch, and the
dispatch of the old-row gather and the scatter.  Delta-reporting cells
only.
"""


def read(run):
    ticks = [t["update_s"] for t in run.get("ticks", []) if "update_s" in t]
    if not ticks:
        return None
    return sum(ticks) / len(ticks) * 1e3
