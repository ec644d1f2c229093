import numpy as np

from benchmark.harness import resolve
from benchmark.runners import serve

MIX = {"rate_per_s": 8.0, "arrivals": "poisson", "shape_seed": 7,
       "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 1.0,
                      "min": 32, "max": 1792},
       "output_len": {"dist": "lognormal", "median": 96, "sigma": 0.7,
                      "min": 32, "max": 256},
       "clients_per_slot": 2, "pool": 64}


def _all(src, horizon=1e9):
    out = src.due(horizon)
    return out


def test_open_loop_reproduces_and_keeps_its_schedule_across_seeds():
    gen = resolve.load_module("traffic", "open_loop")
    a = _all(gen.Source(MIX, 5, 1000, 16, 30.0))
    b = _all(gen.Source(MIX, 5, 1000, 16, 30.0))
    c = _all(gen.Source(MIX, 2**31 + 77, 1000, 16, 30.0))
    assert len(a) == len(b) == len(c) > 200
    for x, y in zip(a, b):
        assert x["due_s"] == y["due_s"]
        assert x["max_new_tokens"] == y["max_new_tokens"]
        assert np.array_equal(x["prompt"], y["prompt"])
    # another seed: the same schedule and sizes, other token ids
    assert [(x["due_s"], len(x["prompt"]), x["max_new_tokens"]) for x in a] \
        == [(x["due_s"], len(x["prompt"]), x["max_new_tokens"]) for x in c]
    assert not np.array_equal(a[0]["prompt"], c[0]["prompt"])
    assert a[-1]["due_s"] >= 30.0
    # a longer horizon extends the schedule, it does not change it
    d = _all(gen.Source(MIX, 5, 1000, 16, 60.0))
    assert [x["due_s"] for x in d[:len(a)]] == [x["due_s"] for x in a]
    assert all(32 <= len(x["prompt"]) <= 1792
               and 32 <= x["max_new_tokens"] <= 256 for x in a)
    # the rate is the cell's: 8/s over 30 s
    assert 0.8 * 240 < len(a) < 1.25 * 240


def test_open_loop_due_only_releases_what_is_due():
    gen = resolve.load_module("traffic", "open_loop")
    s = gen.Source(MIX, 5, 1000, 16, 30.0)
    first = s.next_due_s()
    assert s.due(first - 1e-9) == []
    got = s.due(first)
    assert len(got) == 1 and got[0]["due_s"] == first
    assert s.next_due_s() > first


def test_closed_loop_keeps_clients_times_slots_in_flight():
    gen = resolve.load_module("traffic", "closed_loop")
    s = gen.Source(MIX, 9, 1000, 8, 30.0)
    start = s.due(0.0)
    assert len(start) == 16 and s.due(1.0) == [] and s.next_due_s() is None
    s.finished(2.5)
    nxt = s.due(3.0)
    assert len(nxt) == 1 and nxt[0]["due_s"] == 2.5   # due when its client was free
    t = gen.Source(MIX, 9, 1000, 8, 30.0).due(0.0)
    assert all(np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(start, t))


def test_batches_reproduce():
    gen = resolve.load_module("traffic", "batches")
    mix = {"batch": 2, "seq": 16, "pool": 3}
    a, b = gen.pool(mix, 4, 50), gen.pool(mix, 4, 50)
    assert len(a) == 3 and a[0][0].shape == (2, 16)
    assert all(np.array_equal(x[0], y[0]) and np.array_equal(x[1], y[1])
               for x, y in zip(a, b))
    assert not np.array_equal(a[0][0], gen.pool(mix, 5, 50)[0][0])


def _rec(due, submit, first, last, n, first_n, budget, final=True, **kw):
    return {"due_t": due, "submit_t": submit, "first_t": first,
            "last_t": last, "final_t": last if final else None, "n": n,
            "first_n": first_n, "budget": budget, "prompt_len": 10,
            "total_len": 10 + n, "serving": {}, "error": None, **kw}


def test_latency_is_timed_from_the_due_instant():
    recs = {
        # due at 10.0, submitted 0.3 s late, first tokens at 10.5
        0: _rec(10.0, 10.3, 10.5, 11.5, 48, 16, 48),
        # all tokens in one callback: no TPOT sample
        1: _rec(11.0, 11.0, 11.2, 11.2, 16, 16, 16),
        # due before the window: not in the sample
        2: _rec(9.0, 9.0, 9.1, 9.9, 32, 16, 32),
        # outlived the drain: failed, misses every latency
        3: _rec(12.0, 12.0, 12.4, 12.4, 16, 16, 64, final=False),
    }
    red = serve.reduce_requests(recs, [(10.5, 16), (11.5, 32), (9.5, 5),
                                       (30.0, 7)], 10.0, 20.0)
    assert len(red["window"]) == 3 and red["failed"] == 1
    assert sorted(round(x, 6) for x in red["ttft_ms"]) == [200.0, 500.0]
    assert [round(x, 6) for x in red["tpot_ms"]] == [31.25]   # 1 s / 32
    assert [round(x, 6) for x in sorted(red["late_ms"])] == [0.0, 0.0, 300.0]
    assert red["tokens_in_window"] == 48
    assert red["budgets_ok"]
    recs[1]["n"] = 15           # a finished request short of its budget
    assert not serve.reduce_requests(recs, [], 10.0, 20.0)["budgets_ok"]
