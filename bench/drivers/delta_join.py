"""Closed loop over objects that report as deltas, one tick submitted ahead.

The delta-reporting deployment on ``KnnSession``: the world arrives once as
a snapshot, and from then on every tick the next ``reports_per_tick``
objects of one fixed cyclic order of all objects report their own fix
(``update_objects``), each at its position in that tick's frame of the
ring; the session keeps the index current from those deltas (the
configuration's ``maintenance``).  A fixed set of issuers, each one of the
objects, keeps a standing query at its last reported position
(``update_queries`` from the host mirror of the reported world) and
excludes itself.  The loop keeps one tick in flight, as ``closed_join``
does: it reports tick τ+1, waits for τ on the device, moves the queries
and submits τ+1, and reads τ's rows while τ+1 runs.

Every seed does the same work: the world, the issuers and the order in
which objects report are draws of the world's own seed, so ``--seed`` draws
only the ids the objects carry, the issuers' order and the rows checked.
A fixed count of reports keeps one padded delta length, one program.

Mix parameters (``bench/traffic/<mix>.json``): ``frames`` in the ring the
reports are read from, ``warm_ticks`` of the loop in set-up, and
``check_rows`` sampled for the brute-force comparison.
"""
from __future__ import annotations

import contextlib
import gc
import time
import types
from pathlib import Path

import numpy as np

from knnbench import feed
from knnbench.checks import Group
from knnbench.harness import load_module

closed_join = load_module(Path(__file__).with_name("closed_join.py"))

# the report order's stream of the world's seed (``feed.rng`` seeds its
# streams with the run's seed, so a stream of the world's is kept here)
ORDER_STREAM = 16


def setup(cell, seed, devices, log):
    import jax
    from repro.api import KnnSession, ServiceSpec
    from repro.core.quadtree import rebuild_zmap

    cfg, mix = cell.config, cell.traffic
    world = cfg["world"]
    n = int(world["n_objects"])
    q = int(round(cfg["query_rate"] * n))
    r = int(cfg["reports_per_tick"])
    t0 = time.perf_counter()
    ring = feed.FrameRing(feed.make_world(world), int(mix["frames"]))
    labels = feed.rng(seed, "labels").permutation(n)
    ring.frames = np.ascontiguousarray(ring.frames[:, labels])
    ids_of = np.empty(n, np.int32)
    ids_of[labels] = np.arange(n, dtype=np.int32)
    order = ids_of[np.random.default_rng(
        [int(world["seed"]), ORDER_STREAM]).permutation(n)]
    qid = closed_join.issuers(world, q, seed, labels)
    log(f"# world: {n} {world['distribution']} objects, {q} issuers, "
        f"{r} reports a tick, {ring.frames.shape[0]} frames, made in "
        f"{time.perf_counter() - t0:.2f} s")
    spec = ServiceSpec(**cfg["service"], side=float(world["side"]))
    sess = KnnSession(spec)
    st = types.SimpleNamespace(cfg=cfg, mix=mix, ring=ring, order=order,
                               qid=qid, n=n, q=q, r=r, spec=spec, sess=sess,
                               mirror=ring.frames[0].copy(), t=1, ticks=[],
                               window_s=None, seed=seed, log=log)
    # the session's buffer may alias what it is handed (on the CPU), so it
    # gets the ring's frame, which nothing writes, and the mirror is a copy
    sess.ingest_objects(ring.frames[0])
    st.handle = sess.register_queries(st.mirror[qid], qid)
    # warm-up: the first tick builds the index before its step, and so
    # runs the step a drift rebuild leaves ("skip"); then the window's own
    # loop ("incremental": the splice of one tick's reports)
    modes = [sess.submit().result().maintenance]
    nothing = lambda name: contextlib.nullcontext()  # noqa: E731
    _loop(st, nothing, ticks=int(mix["warm_ticks"]))
    # a drift rebuild, read back at the finalize inside a submit, finds
    # that tick's reports pending: it splices them in and re-derives the
    # leaf partition (``reindex_objects_delta``, ``rebuild_zmap``), and the
    # tick's step then skips its refresh.  Every run takes that route here,
    # at the same tick, so the window's rebuilds find their programs built
    _report(st)
    sess._build()
    sess.update_queries(st.handle, st.mirror[qid])
    modes.append(sess.submit().result().maintenance)
    # the window's last read can rebuild on a clean buffer (``rebuild_zmap``
    # alone)
    jax.block_until_ready(rebuild_zmap(sess._index))
    log(f"# warm-up ticks: {modes} + {mix['warm_ticks']} looped")
    return st


def _batch(st, j: int):
    """The ids and fixes of the ``j``-th report batch (``j`` from 1): the
    next ``r`` objects of the cyclic order, at their positions in frame
    ``j`` of the ring."""
    ids = np.take(st.order, np.arange((j - 1) * st.r, j * st.r) % st.n)
    return ids, st.ring[j][ids]


def _report(st) -> tuple[float, int]:
    """Send tick ``st.t``'s reports; (seconds in ``update_objects``, how
    many of the reports changed their object's position)."""
    ids, fix = _batch(st, st.t)
    moved = int(np.any(fix != st.mirror[ids], axis=1).sum())
    a = time.perf_counter()
    # arrays made for this call and never written again: a device array
    # may alias them on the CPU
    st.sess.update_objects(ids, fix)
    update_s = time.perf_counter() - a
    st.mirror[ids] = fix
    st.t += 1
    return update_s, moved


def _loop(st, span, *, ticks=None, seconds=None, keep=False):
    """Run the loop for ``ticks`` ticks or ``seconds`` seconds.

    Returns (elapsed seconds, ticks completed).  Stops submitting where
    one more tick of the mean length so far would run past ``seconds``,
    and waits for the tick in flight, so every tick submitted is counted
    with the time it took.
    """
    sess, qid = st.sess, st.qid
    t0 = time.perf_counter()
    prev = None
    done = 0

    def collect(p):
        handle, info = p
        with span("result"):
            res = handle.result()
        if keep:
            st.ticks.append(dict(info, res=res,
                                 done_s=time.perf_counter() - t0))

    while True:
        if ticks is not None and done >= ticks:
            break
        elapsed = time.perf_counter() - t0
        if seconds is not None and elapsed * (done + 1) / max(done, 1) \
                >= seconds:
            break
        with span("report"):
            update_s, moved = _report(st)
        if prev is not None:
            with span("wait_device"):
                prev[0].block_until_ready()
        with span("submit"):
            a = time.perf_counter()
            sess.update_queries(st.handle, st.mirror[qid])
            handle = sess.submit()
            stage_s = update_s + time.perf_counter() - a
        if prev is not None:
            collect(prev)
        prev = (handle, dict(t=st.t - 1, stage_s=stage_s, update_s=update_s,
                             moved=moved))
        done += 1
    if prev is not None:
        collect(prev)
    return time.perf_counter() - t0, done


def window(st, seconds, span):
    st.window_s, _ = _loop(st, span, seconds=seconds, keep=True)


def record(st) -> dict:
    chunk = st.spec.chunk
    rows = closed_join.pad_rows(st.q, st.sess.plan.pad_multiple(chunk))
    done = [0.0] + [t["done_s"] for t in st.ticks]
    ticks = []
    for t in st.ticks:
        r = t["res"]
        ticks.append(dict(
            stage_s=t["stage_s"], update_s=t["update_s"],
            iterations=r.iterations, candidates=r.candidates,
            chunks=rows // chunk,
            shard_candidates=(None if r.shard_candidates is None
                              else np.asarray(r.shard_candidates).tolist()),
            maintenance=r.maintenance,
            delta_rows=getattr(r, "delta_rows", None),
            reports=st.r, moved=t["moved"]))
    moved = sum(t["moved"] for t in ticks)
    modes = [t["maintenance"] for t in ticks]
    st.log(f"# reports: {moved} of {st.r * len(ticks)} moved their object; "
           f"steps {dict((m, modes.count(m)) for m in sorted(set(modes)))}; "
           f"rows spliced {[t['delta_rows'] for t in ticks][:4]}...")
    return dict(kind="closed", window_s=st.window_s,
                queries_answered=st.q * len(st.ticks), ticks=ticks,
                tick_s=[b - a for a, b in zip(done, done[1:])],
                chunk=chunk, lanes_window=st.spec.window)


def release(st):
    """Free the program's device state before the reference runs."""
    for t in st.ticks:
        r = t["res"]
        t["res"] = types.SimpleNamespace(
            nn_idx=None if r.nn_idx is None else np.asarray(r.nn_idx),
            nn_dist=None if r.nn_dist is None else np.asarray(r.nn_dist),
            qids=None if r.qids is None else np.asarray(r.qids),
            maintenance=r.maintenance)
    st.sess = st.handle = None
    gc.collect()


def answers(st, seed):
    """Each tick's rows as a ``Group``, against the world as reported up
    to that tick's submit, with the rows the seed draws.

    The reported world is replayed from the first snapshot, batch by
    batch, into one buffer; ``check_rows`` rows are drawn uniformly over
    every row of every tick the window ran.
    """
    q, qid = st.q, st.qid
    total = len(st.ticks) * q
    m = min(int(st.mix["check_rows"]), total)
    pick = np.sort(feed.rng(seed, "check").choice(total, m, replace=False))
    world = st.ring.frames[0].copy()
    applied = 0
    for ti, t in enumerate(st.ticks):
        while applied < t["t"]:
            applied += 1
            ids, fix = _batch(st, applied)
            world[ids] = fix
        r = t["res"]
        got = r.nn_idx is not None and np.array_equal(r.qids, qid)
        yield Group(world, world[qid], qid, r.nn_idx if got else None,
                    r.nn_dist if got else None,
                    pick[pick // q == ti] % q)


tally = closed_join.tally
