"""Closed loop: the iterated spatial join, one tick submitted ahead.

The paper's workload on ``KnnSession``: every tick the whole object world
moves and arrives as a snapshot (``ingest_objects``), and a fixed set of
issuers, each one of the objects, moves its standing query with it
(``update_queries``) and excludes itself.  Every seed replays the same
frames from the first and serves the same issuing objects, so the work is
the same; the seed draws the ids the objects carry (a permutation), the
order of the issuers and the rows checked.  The loop keeps one tick in
flight: it ingests tick τ+1's snapshot, waits for τ on the device, moves
the queries and submits τ+1, and reads τ's rows while τ+1 runs.  (The
queries move only once τ is done: on the CPU backend ``update_queries``
during τ rewrites the host buffer τ's device array aliases.)

Mix parameters (``bench/traffic/<mix>.json``): ``frames`` in the ring the
world is replayed from, ``warm_ticks`` of the loop in set-up, and
``check_rows`` sampled for the brute-force comparison.
"""
from __future__ import annotations

import contextlib
import gc
import time
import types

import numpy as np

from knnbench import feed
from knnbench.checks import Group


def setup(cell, seed, devices, log):
    import jax
    from repro.api import KnnSession, ServiceSpec
    from repro.core.quadtree import rebuild_zmap

    cfg, mix = cell.config, cell.traffic
    world = cfg["world"]
    n = int(world["n_objects"])
    q = int(round(cfg["query_rate"] * n))
    t0 = time.perf_counter()
    ring = feed.FrameRing(feed.make_world(world), int(mix["frames"]))
    labels = feed.rng(seed, "labels").permutation(n)
    ring.frames = np.ascontiguousarray(ring.frames[:, labels])
    qid = issuers(world, q, seed, labels)
    log(f"# world: {n} {world['distribution']} objects, {q} issuers, "
        f"{ring.frames.shape[0]} frames, made in "
        f"{time.perf_counter() - t0:.2f} s")
    spec = ServiceSpec(**cfg["service"], side=float(world["side"]))
    sess = KnnSession(spec)
    st = types.SimpleNamespace(cfg=cfg, mix=mix, ring=ring, qid=qid, n=n,
                               q=q, spec=spec, sess=sess, t=0, ticks=[],
                               window_s=None, seed=seed)
    # warm-up: the first tick builds the index before its step, and so
    # runs the step a drift rebuild leaves ("skip"); then the window's own
    # loop ("rebuild": a snapshot every tick) for a few ticks.  Every run
    # replays the ring from its first frame: the partition goes stale
    # between drift rebuilds, so a tick's cost depends on where the run is
    # in that cycle, and a seeded start frame would change the work
    sess.ingest_objects(ring[st.t])
    st.handle = sess.register_queries(ring[st.t][qid], qid)
    modes = [sess.submit().result().maintenance]
    st.t += 1
    _loop(st, lambda name: contextlib.nullcontext(),
          ticks=int(mix["warm_ticks"]))
    # reading a tick back applies the drift policy, which on a clean buffer
    # re-derives only the leaf partition (``rebuild_zmap``): the window's
    # last read can take that path, so its program is built here too
    jax.block_until_ready(rebuild_zmap(sess._index))
    log(f"# warm-up ticks: {modes} + {mix['warm_ticks']} looped")
    return st


def issuers(world: dict, q: int, seed: int,
            labels: np.ndarray) -> np.ndarray:
    """The ids of the ``q`` issuing objects, in the order ``seed`` draws.

    ``labels[j]`` is the generator's object that carries id ``j`` in this
    run.  The set of objects is part of the deployment, one draw from the
    world's own seed: a sweep chunk runs as long as its slowest query, so
    a fresh set for each ``--seed`` would change the amount of work, not
    only its order.
    """
    n = int(world["n_objects"])
    objects = feed.rng(world["seed"], "issuers").choice(n, q, replace=False)
    ids = np.empty(n, np.int32)
    ids[labels] = np.arange(n, dtype=np.int32)
    return np.sort(ids[objects])[feed.rng(seed, "issuers").permutation(q)]


def _loop(st, span, *, ticks=None, seconds=None, keep=False):
    """Run the loop for ``ticks`` ticks or ``seconds`` seconds.

    Returns (elapsed seconds, ticks completed).  Stops submitting where
    one more tick of the mean length so far would run past ``seconds``,
    and waits for the tick in flight, so every tick submitted is counted
    with the time it took.
    """
    sess, ring, qid = st.sess, st.ring, st.qid
    t0 = time.perf_counter()
    prev = None
    done = 0

    def collect(p):
        handle, frame, stage_s = p
        with span("result"):
            res = handle.result()
        if keep:
            st.ticks.append(dict(
                frame=frame, stage_s=stage_s, res=res,
                done_s=time.perf_counter() - t0))

    while True:
        if ticks is not None and done >= ticks:
            break
        # stop where one more tick of the mean length would overrun
        elapsed = time.perf_counter() - t0
        if seconds is not None and elapsed * (done + 1) / max(done, 1) \
                >= seconds:
            break
        frame = ring.index(st.t)
        st.t += 1
        with span("ingest"):
            a = time.perf_counter()
            sess.ingest_objects(ring.frames[frame])
            stage_s = time.perf_counter() - a
        if prev is not None:
            with span("wait_device"):
                prev[0].block_until_ready()
        with span("submit"):
            a = time.perf_counter()
            sess.update_queries(st.handle, ring.frames[frame][qid])
            handle = sess.submit()
            stage_s += time.perf_counter() - a
        if prev is not None:
            collect(prev)
        prev = (handle, frame, stage_s)
        done += 1
    if prev is not None:
        collect(prev)
    return time.perf_counter() - t0, done


def window(st, seconds, span):
    st.window_s, _ = _loop(st, span, seconds=seconds, keep=True)


def record(st) -> dict:
    chunk = st.spec.chunk
    ticks = []
    done = [0.0] + [t["done_s"] for t in st.ticks]
    for t in st.ticks:
        r = t["res"]
        rows = pad_rows(st.q, st.sess.plan.pad_multiple(chunk))
        ticks.append(dict(
            stage_s=t["stage_s"], iterations=r.iterations,
            candidates=r.candidates, chunks=rows // chunk,
            shard_candidates=(None if r.shard_candidates is None
                              else np.asarray(r.shard_candidates).tolist()),
            maintenance=r.maintenance))
    return dict(kind="closed", window_s=st.window_s,
                queries_answered=st.q * len(st.ticks), ticks=ticks,
                tick_s=[b - a for a, b in zip(done, done[1:])],
                chunk=chunk, lanes_window=st.spec.window)


def pad_rows(q: int, multiple: int) -> int:
    return max(1, -(-q // multiple)) * multiple


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
    """Each tick's rows as a ``Group``, with the rows the seed draws.

    ``check_rows`` rows are drawn uniformly over every row of every tick
    the window ran.
    """
    k, q, qid, ring = st.spec.k, st.q, st.qid, st.ring
    total = len(st.ticks) * q
    m = min(int(st.mix["check_rows"]), total)
    pick = np.sort(feed.rng(seed, "check").choice(total, m, replace=False))
    for ti, t in enumerate(st.ticks):
        r = t["res"]
        world = ring.frames[t["frame"]]
        got = r.nn_idx is not None and np.array_equal(r.qids, qid)
        yield Group(world, world[qid], qid, r.nn_idx if got else None,
                    r.nn_dist if got else None,
                    pick[pick // q == ti] % q)


def tally(st) -> tuple[int, int]:
    """(rows attempted, rows never returned) over the window."""
    q = st.q
    missing = sum(q for t in st.ticks if t["res"].nn_idx is None)
    return len(st.ticks) * q, missing
