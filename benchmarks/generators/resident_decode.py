"""Resident sessions that only decode: a replica at full occupancy whose
window holds no admission and no prefill at all (the decode pool of a
deployment that splits prefill from decode; offline long generation).

``2 x num_slots`` requests are queued before the ramp (``num_slots``
become resident, the rest wait as the reserve that refills a slot should
a session ever end). ``prompt_len`` is the ``num_slots`` evenly spaced
quantiles of its distribution, twice, each time in another seeded order;
``max_new = max_len - prompt_len``, so no session ends inside a window
unless the program is many times faster than today. The window opens at
the later of ``ramp_s`` and the moment every resident session has
delivered ``settle_tokens`` tokens, and no later than ``ramp_max_s``:
``resident_short`` counts the slots short of that at the opening and is
held to 0, so a window that still holds a prefill never reads as a rate.
``prefills_in_window`` and ``ended_in_window`` are logged.

``build`` / ``drive`` / ``account``: see open_loop.py. The plane hands
``num_slots`` and ``max_len`` in with the traffic's parameters.
"""
import time

import numpy as np

from benchmarks.generators import dist


def build(params, seed, seconds, vocab_limit):
    rng = np.random.default_rng(abs(int(seed)))
    n, cap = int(params["num_slots"]), int(params["max_len"])
    out = []
    for _ in range(2):
        plen = np.asarray(dist.stratified_ints(params["prompt_len"], n))
        rng.shuffle(plen)
        out += [{"phase": "resident",
                 "prompt": rng.integers(0, vocab_limit, size=int(p),
                                        dtype=np.int64),
                 "max_new": cap - int(p)} for p in plen]
    return out


def drive(client, specs, params, seconds, opened, closed):
    recs = [client.record(s, None) for s in specs]
    t0 = time.perf_counter()
    client.preload(recs)
    n, settle = client.num_slots, int(params["settle_tokens"])
    earliest = t0 + float(params["ramp_s"])
    latest = t0 + float(params["ramp_max_s"])

    def settled():
        return sum(1 for r in recs if len(r.stamps) >= settle)

    while time.perf_counter() < latest and (
            time.perf_counter() < earliest or settled() < n):
        time.sleep(0.05)
    short = max(0, n - settled())
    t_open = time.perf_counter()
    opened()
    time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
    t_close = time.perf_counter()
    closed()
    cut = [r for r in recs if r.req is not None and r.req.done
           and not r.finished]
    client.halt()
    return {"recs": recs, "t_open": t_open, "t_close": t_close,
            "cut_by_engine": cut, "num_slots": n,
            "resident_short": short, "ramp_took_s": t_open - t0}


def _in(t, run):
    return t is not None and run["t_open"] <= t < run["t_close"]


def account(run, params):
    """Attempted: the sessions resident in the window (those that had a
    token before it closed). Failed: refused, errored, or retired by the
    engine short of its length."""
    failed = [r for r in run["recs"] if r.error] + run["cut_by_engine"]
    resident = [r for r in run["recs"]
                if r.stamps and r.stamps[0] < run["t_close"]]
    gone = {id(r) for r in resident}
    return {"attempted": resident + [r for r in failed
                                     if id(r) not in gone],
            "failed": failed,
            "checks": [("resident_short", run["resident_short"], 0)],
            "prefills_in_window": sum(
                1 for r in run["recs"] if r.req is not None
                and _in(r.req.t_prefill_dispatched, run)),
            "ended_in_window": sum(
                1 for r in run["recs"] if r.req is not None
                and r.req.done and _in(r.req.t_done, run)),
            "ramp_took_s": run["ramp_took_s"]}
