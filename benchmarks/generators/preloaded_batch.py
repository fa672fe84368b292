"""A backlog handed over whole: every request is queued before the ramp
begins and the engine works through them; nothing is submitted while the
window is open (offline batch inference).

Parameters: ``blocks`` x ``block_requests`` requests, ``ramp_s``,
``prompt_len``, ``output_len``. Each block is the same multiset of sizes
(the evenly spaced quantiles of the two distributions) in another seeded
order, so whatever prefix of the backlog a run gets through, every seed
gives it about the same work. The backlog has to outlast ramp + window
at many times today's speed; a run that gets within ``num_slots``
requests of its end is not correct (``backlog_short``), so a dry queue
never reads as a rate.

``build`` / ``drive`` / ``account``: see open_loop.py.
"""
import time

import numpy as np

from benchmarks.generators import dist


def build(params, seed, seconds, vocab_limit):
    rng = np.random.default_rng(abs(int(seed)))
    n = int(params["block_requests"])
    out = []
    for _ in range(int(params["blocks"])):
        plen = np.asarray(dist.stratified_ints(params["prompt_len"], n))
        olen = np.asarray(dist.stratified_ints(params["output_len"], n))
        rng.shuffle(plen)
        rng.shuffle(olen)
        out += [{"phase": "backlog",
                 "prompt": rng.integers(0, vocab_limit, size=int(plen[i]),
                                        dtype=np.int64),
                 "max_new": int(olen[i])} for i in range(n)]
    return out


def drive(client, specs, params, seconds, opened, closed):
    recs = [client.record(s, None) for s in specs]
    client.preload(recs)
    t_open = time.perf_counter() + float(params["ramp_s"])
    time.sleep(max(0.0, t_open - time.perf_counter()))
    opened()
    time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
    t_close = time.perf_counter()
    closed()
    # a request the engine retired short of its length (shed, aborted,
    # timed out): read before the halt, which cuts the rest short
    cut = [r for r in recs if r.req is not None and r.req.done
           and not r.finished]
    client.halt()
    return {"recs": recs, "t_open": t_open, "t_close": t_close,
            "cut_by_engine": cut, "num_slots": client.num_slots}


def account(run, params):
    """Attempted: the requests answered inside the window, and every one
    refused or cut short by the engine up to its close. What was still
    queued or decoding at the close is neither."""
    t_open, t_close = run["t_open"], run["t_close"]
    failed = [r for r in run["recs"] if r.error] + run["cut_by_engine"]
    answered = [r for r in run["recs"]
                if r.finished and t_open <= r.stamps[-1] < t_close]
    gone = {id(r) for r in failed}
    waiting = sum(1 for r in run["recs"] if id(r) not in gone
                  and (not r.stamps or r.stamps[0] >= t_close))
    return {"attempted": answered + failed, "failed": failed,
            "checks": [("backlog_short",
                        max(0, run["num_slots"] - waiting), 0)],
            "waiting_at_close": waiting}
