"""Traffic generation: the seed permutes the work, it does not change
its amount."""
import numpy as np

from benchmarks import harness
from benchmarks.generators import dist, open_loop, preloaded_batch, token_rows

CHAT = harness.load_json(harness.HERE, "traffic", "chat_steady.json")


def test_every_seed_gets_the_same_sizes_and_arrival_gaps():
    a = open_loop.build(CHAT, 1, 20.0, 50304)
    b = open_loop.build(CHAT, 2 ** 31 + 9, 20.0, 50304)
    win = lambda reqs: [r for r in reqs if r["phase"] == "window"]  # noqa
    assert len(win(a)) == len(win(b)) == round(CHAT["rate_per_s"] * 20)
    for key in ("prompt", "max_new"):
        size = (lambda r: len(r[key])) if key == "prompt" \
            else (lambda r: r[key])
        assert sorted(map(size, win(a))) == sorted(map(size, win(b)))
    gaps = lambda reqs: np.sort(np.diff([r["due"] for r in win(reqs)]))  # noqa
    assert np.allclose(gaps(a), gaps(b))
    assert [r["due"] for r in a] == sorted(r["due"] for r in a)
    assert not np.array_equal(win(a)[0]["prompt"], win(b)[0]["prompt"])
    same = open_loop.build(CHAT, 1, 20.0, 50304)
    assert all(np.array_equal(x["prompt"], y["prompt"])
               and x["due"] == y["due"] for x, y in zip(a, same))


def test_lengths_stay_inside_the_traffic_files_limits():
    spec = CHAT["prompt_len"]
    xs = dist.stratified_ints(spec, 500)
    assert min(xs) >= spec["min"] and max(xs) <= spec["max"]
    assert abs(np.median(xs) - spec["median"]) <= 2


def test_a_backlog_is_blocks_of_one_multiset_in_seeded_order():
    params = harness.load_json(harness.HERE, "traffic", "chat_backlog.json")
    n, blocks = params["block_requests"], params["blocks"]
    a = preloaded_batch.build(params, 7, 10.0, 50304)
    b = preloaded_batch.build(params, 2 ** 31 + 8, 10.0, 50304)
    assert len(a) == len(b) == n * blocks
    plens = lambda reqs: sorted(len(r["prompt"]) for r in reqs)  # noqa
    olens = lambda reqs: sorted(r["max_new"] for r in reqs)  # noqa
    for k in range(blocks):
        blk = slice(k * n, (k + 1) * n)
        assert plens(a[blk]) == plens(b[blk]) == plens(a[:n])
        assert olens(a[blk]) == olens(b[blk]) == olens(a[:n])
    assert [len(r["prompt"]) for r in a[:n]] \
        != [len(r["prompt"]) for r in b[:n]]
    assert [len(r["prompt"]) for r in a[:n]] \
        != [len(r["prompt"]) for r in a[n:2 * n]]
    assert min(r["max_new"] for r in a) >= 8
    # it outlasts ramp + window at the decode program's memory roofline
    # (24 slots / 5 ms = 4,800 tokens/s; PERF.md) with room to spare
    tokens = sum(r["max_new"] for r in a)
    assert tokens / (params["ramp_s"] + 40.0) > 1.5 * 4800


class _Req:
    def __init__(self, done):
        self.done = done


def _rec(max_new, stamps, done=True, due=0.0, phase="window"):
    from benchmarks.planes import serve
    r = serve.Rec({"prompt": [1], "max_new": max_new, "phase": phase}, due)
    r.req, r.stamps = _Req(done), list(stamps)
    return r


def test_a_backlog_counts_what_the_engine_cut_short_and_a_dry_queue():
    done_in = _rec(2, [10.5, 11.0], phase="backlog")
    done_before = _rec(2, [8.0, 9.0], phase="backlog")
    decoding = _rec(3, [11.5], done=False, phase="backlog")
    shed = _rec(3, [], done=True, phase="backlog")       # retired empty
    queued = [_rec(2, [], done=False, phase="backlog") for _ in range(4)]
    run = {"recs": [done_in, done_before, decoding, shed] + queued,
           "t_open": 10.0, "t_close": 12.0, "cut_by_engine": [shed],
           "num_slots": 4}
    acct = preloaded_batch.account(run, {})
    assert acct["attempted"] == [done_in, shed] and acct["failed"] == [shed]
    # 4 still queued (the one the engine shed is not waiting): 4 >= 4
    assert acct["checks"] == [("backlog_short", 0, 0)]
    run["recs"] = run["recs"][:-2]
    assert preloaded_batch.account(run, {})["checks"][0][1] == 2


def test_open_loop_fails_the_unfinished_and_the_late_first_token():
    params = {"ttft_ceiling_s": 5.0}
    fine = _rec(2, [1.0, 2.0])
    late = _rec(2, [6.5, 7.0], due=1.0)
    unfinished = _rec(3, [1.0], done=False)
    ramp = _rec(2, [], done=False, phase="ramp")
    acct = open_loop.account({"recs": [fine, late, unfinished, ramp]},
                             params)
    assert acct["attempted"] == [fine, late, unfinished]
    assert acct["failed"] == [late, unfinished]


def test_token_rows_regenerate_without_the_loader():
    x, y = token_rows.batch(11, 2, 4, 16, 500)
    assert x.shape == y.shape == (4, 16)
    assert np.array_equal(x[:, 1:], y[:, :-1])
    assert np.array_equal(token_rows.row(11, 9, 16, 500)[:-1], x[1])
