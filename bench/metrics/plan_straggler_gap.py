"""Straggler gap of the query shards: max / mean of the per-shard
candidates (``TickResult.shard_candidates``), per tick, averaged over the
window's ticks; only where more than one shard ran (program counter)."""


def read(run):
    gaps = []
    for t in run.get("ticks", []):
        sc = t.get("shard_candidates")
        if sc and len(sc) > 1 and sum(sc) > 0:
            gaps.append(max(sc) / (sum(sc) / len(sc)))
    if not gaps:
        return None
    return sum(gaps) / len(gaps)
