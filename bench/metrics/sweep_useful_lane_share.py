"""Share of the sweep's gathered window slots that held a candidate, in %.

``candidates / (iterations * chunk * W)`` over the window: every trip
gathers W slots for each of a chunk's lanes, scanning or not; a program
counter.
"""


def read(run):
    ticks = [t for t in run.get("ticks", [])
             if t.get("iterations") is not None]
    slots = sum(t["iterations"] for t in ticks) * run["chunk"] * run[
        "lanes_window"]
    if not slots:
        return None
    return 100.0 * sum(t["candidates"] for t in ticks) / slots
