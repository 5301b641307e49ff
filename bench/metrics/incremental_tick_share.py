"""Share of the window's ticks whose step refreshed the index by the
incremental splice, in % (``TickResult.maintenance == "incremental"``; a
program counter).  The rest skipped their refresh after a drift rebuild or
re-sorted the whole store."""


def read(run):
    ticks = [t["maintenance"] for t in run.get("ticks", [])
             if "maintenance" in t]
    if not ticks:
        return None
    return 100.0 * ticks.count("incremental") / len(ticks)
