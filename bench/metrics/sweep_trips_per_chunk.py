"""While-loop trips of the sweep per query chunk, over the window.

``KnnStats.iterations`` (summed over chunks and shards by the program) over
the number of chunks the ticks swept; a program counter.
"""


def read(run):
    ticks = [t for t in run.get("ticks", [])
             if t.get("iterations") is not None and t.get("chunks")]
    chunks = sum(t["chunks"] for t in ticks)
    if not chunks:
        return None
    return sum(t["iterations"] for t in ticks) / chunks
