"""k-NN answers completed per second over the whole window (host clock).

Every row of every tick submitted in the window, over the window's length
from its start to the last tick's rows on the host.  Closed-loop cells
only.
"""


def read(run):
    if run.get("kind") != "closed" or not run.get("window_s"):
        return None
    return run["queries_answered"] / run["window_s"]
