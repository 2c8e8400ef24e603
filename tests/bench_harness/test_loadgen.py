"""Traffic generation, the two loops, and the arithmetic from records to metrics."""
import math
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from benchmark.lib import loadgen
from benchmark.lib.loadgen import Record

CHAT = {
    "loop": {"kind": "open", "rate_rps": 4.0, "preroll_s": 2.0},
    "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 32, "max": 1024},
    "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.7, "min": 16, "max": 512},
}


def test_same_seed_same_schedule():
    a = loadgen.open_loop_schedule(CHAT, 10.0, seed=3)
    b = loadgen.open_loop_schedule(CHAT, 10.0, seed=3)
    assert a == b
    assert np.array_equal(loadgen.prompt_tokens(3, 5, 100, 512), loadgen.prompt_tokens(3, 5, 100, 512))


def test_other_seed_same_work_other_order():
    a = loadgen.open_loop_schedule(CHAT, 10.0, seed=3)
    b = loadgen.open_loop_schedule(CHAT, 10.0, seed=4)
    win = lambda rs: [r for r in rs if r.due >= 0]
    assert [r.due for r in a] != [r.due for r in b]
    # the window offers the same multiset of lengths whatever the seed
    assert sorted(r.prompt_len for r in win(a)) == sorted(r.prompt_len for r in win(b))
    assert sorted(r.output_len for r in win(a)) == sorted(r.output_len for r in win(b))
    assert [r.prompt_len for r in win(a)] != [r.prompt_len for r in win(b)]


def test_schedule_counts_and_bounds():
    rs = loadgen.open_loop_schedule(CHAT, 10.0, seed=0)
    pre = [r for r in rs if r.due < 0]
    win = [r for r in rs if r.due >= 0]
    assert len(win) == 40 and len(pre) == 8
    assert all(-2.0 <= r.due < 0 for r in pre) and all(0 <= r.due < 10.0 for r in win)
    assert [r.due for r in rs] == sorted(r.due for r in rs)
    assert all(32 <= r.prompt_len <= 1024 and 16 <= r.output_len <= 512 for r in rs)
    med = sorted(r.prompt_len for r in win)[len(win) // 2]
    assert 200 <= med <= 320


@pytest.mark.parametrize("spec,u,want", [
    ({"dist": "uniform", "min": 10, "max": 19}, 0.0, 10),
    ({"dist": "uniform", "min": 10, "max": 19}, 0.999, 19),
    ({"dist": "lognormal", "median": 100, "sigma": 1.0, "min": 1, "max": 10_000}, 0.5, 100),
    ({"dist": "lognormal", "median": 100, "sigma": 1.0, "min": 50, "max": 200}, 0.001, 50),
    ({"dist": "lognormal", "median": 100, "sigma": 1.0, "min": 50, "max": 200}, 0.999, 200),
    ({"dist": "fixed", "value": 7}, 0.3, 7),
])
def test_quantile(spec, u, want):
    assert loadgen.quantile(spec, u) == want


def test_bursty_arrivals_have_the_stated_variation():
    rng = np.random.default_rng(0)
    gaps = np.diff(loadgen.arrival_times(4000, 1000.0, rng, cv=3.0))
    assert 2.5 < gaps.std() / gaps.mean() < 3.5
    gaps = np.diff(loadgen.arrival_times(4000, 1000.0, np.random.default_rng(0), cv=1.0))
    assert 0.9 < gaps.std() / gaps.mean() < 1.1


def rec(index, due, submitted, done, ok=True, ttft=0.1, tbot=0.02, n_new=10, prompt=100):
    return Record(index, due, prompt, n_new, submitted=submitted, done=done, ok=ok,
                  ttft_s=ttft, tbot_s=tbot, n_new=n_new if ok else 0,
                  error="" if ok else "ValueError: refused")


def test_ttft_is_taken_from_the_due_instant():
    # due at 1.0 s, the generator got round to it at 1.3 s, first token 0.2 s after that
    r = rec(0, 1.0, 1.3, 2.0, ttft=0.2)
    assert r.ttft_from_due_s == pytest.approx(0.5)
    assert loadgen.ttft_ms([r], 10.0) == [pytest.approx(500.0)]


def test_a_failed_request_counts_as_the_window():
    records = [rec(0, 1.0, 1.0, 2.0), rec(1, 2.0, 2.0, 2.1, ok=False)]
    assert loadgen.ttft_ms(records, 10.0) == [pytest.approx(100.0), pytest.approx(10_000.0)]
    assert loadgen.tbot_ms(records, 10.0) == [pytest.approx(20.0)]


def test_measured_is_what_came_to_an_outcome_inside_the_window():
    records = [rec(0, -3.0, -3.0, -0.5),         # done before the window
               rec(1, -1.0, -1.0, 0.5),          # sent in the pre-roll, done inside: counts
               rec(2, 1.0, 1.0, 2.0),
               rec(3, 9.0, 9.0, 11.0),           # done after the window
               Record(4, 9.5, 100, 10, submitted=9.5)]  # never done
    assert [r.index for r in loadgen.measured(records, 10.0)] == [1, 2]


def test_percentile_worked_example():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert loadgen.percentile(xs, 50) == 30.0
    assert loadgen.percentile(xs, 0) == 10.0 and loadgen.percentile(xs, 100) == 50.0
    assert loadgen.percentile(xs, 95) == pytest.approx(48.0)   # 40 + 0.8 * 10
    assert loadgen.percentile([5.0], 99) == 5.0
    with pytest.raises(ValueError):
        loadgen.percentile([], 50)


def test_lateness_p99_worked_example():
    records = [rec(i, float(i), i + 0.001 * i, i + 1.0) for i in range(10)]  # 0 .. 9 ms late
    late = loadgen.late_ms(records, 20.0)
    assert late == [pytest.approx(float(i)) for i in range(10)]
    assert loadgen.percentile(late, 99) == pytest.approx(8.91)


def test_token_rate_interpolates_across_the_window_edges():
    count = lambda r: 100.0
    # completions every 2 s from -3 s on; load began at -5 s
    records = [rec(i, -5.0, -5.0, t) for i, t in enumerate([-3.0, -1.0, 1.0, 3.0, 5.0, 7.0, 9.0])]
    # the request done at 1.0 spreads over (-1, 1]: half of it is inside; the last completion
    # inside the 10 s window is at 9.0: (4.5 * 100) / 9.0
    assert loadgen.token_rate(records, 10.0, count, -5.0) == pytest.approx(50.0)
    # a steady 50 tokens/s whatever the window's phase against the completions
    assert loadgen.token_rate(records, 9.5, count, -5.0) == pytest.approx(50.0)
    assert loadgen.token_rate([rec(0, -5.0, -5.0, -1.0)], 10.0, count, -5.0) == 0.0
    assert loadgen.token_rate(records[:3] + [rec(9, 0.0, 0.0, 2.0, ok=False)], 10.0, count,
                              -5.0) == pytest.approx(50.0)


class FakeServer:
    """Completes every request ``latency`` seconds after it was submitted."""

    def __init__(self, latency=0.01, fail_every=0):
        self.latency, self.fail_every = latency, fail_every
        self.submitted, self.in_flight, self.max_in_flight = [], 0, 0
        self._lock = threading.Lock()

    def submit(self, req):
        fut = Future()
        with self._lock:
            self.submitted.append(req)
            self.in_flight += 1
            self.max_in_flight = max(self.max_in_flight, self.in_flight)
            n = len(self.submitted)

        def finish():
            with self._lock:
                self.in_flight -= 1
            if self.fail_every and n % self.fail_every == 0:
                fut.set_exception(ValueError("refused"))
            else:
                fut.set_result((0.004, 0.001, req.output_len))

        threading.Timer(self.latency, finish).start()
        return fut


def test_open_loop_submits_on_schedule_whatever_the_backlog():
    server = FakeServer(latency=0.3)   # slower than the gaps: the backlog grows, the schedule holds
    reqs = [loadgen.Request(i, 0.02 * i, 10, 5) for i in range(10)]
    loop = loadgen.OpenLoop(server.submit, lambda res: res, reqs)
    t0 = time.perf_counter() + 0.05
    loop.start(t0)
    time.sleep(0.05 + 0.02 * 10 + 0.4)
    loop.stop()
    assert len(server.submitted) == 10 and server.max_in_flight == 10
    assert all(r.ok and r.n_new == 5 for r in loop.records)
    assert all(0.0 <= r.late_s < 0.015 for r in loop.records)
    assert all(r.done >= r.submitted + 0.29 for r in loop.records)


def test_open_loop_records_failures():
    server = FakeServer(latency=0.01, fail_every=2)
    reqs = [loadgen.Request(i, 0.01 * i, 10, 5) for i in range(4)]
    loop = loadgen.OpenLoop(server.submit, lambda res: res, reqs)
    loop.start(time.perf_counter())
    time.sleep(0.2)
    loop.stop()
    assert [r.ok for r in loop.records] == [True, False, True, False]
    assert "refused" in loop.records[1].error and not math.isnan(loop.records[1].done)


def test_closed_loop_keeps_its_client_count():
    server = FakeServer(latency=0.02)
    lengths = loadgen.LengthStream({"prompt_len": {"dist": "uniform", "min": 5, "max": 50},
                                    "output_len": {"dist": "fixed", "value": 3}}, seed=1, block=8)
    loop = loadgen.ClosedLoop(server.submit, lambda res: res, lengths, clients=4)
    loop.start(time.perf_counter())
    time.sleep(0.5)
    loop.stop()
    assert server.max_in_flight == 4 and loop.max_in_flight == 4
    assert len(loop.records) >= 40          # 4 clients, 20 ms a request, 0.5 s
    done = [r for r in loop.records if r.ok]
    assert len(loop.records) - len(done) <= 4
    # each block of 8 offers the same lengths, in another order
    first, second = (sorted(r.prompt_len for r in loop.records[i:i + 8]) for i in (0, 8))
    assert first == second


def test_closed_loop_lengths_come_in_mirrored_pairs():
    spec = {"prompt_len": {"dist": "uniform", "min": 2048, "max": 7168},
            "output_len": {"dist": "uniform", "min": 16, "max": 64}}
    a = [next(s) for s in [loadgen.LengthStream(spec, seed=5)] for _ in range(96)]
    b = [next(s) for s in [loadgen.LengthStream(spec, seed=6)] for _ in range(96)]
    assert a != b and sorted(a[:48]) != sorted(a)[:48]
    for seq in (a, b):
        # every block of 48 holds the same multiset, every pair inside it the same work
        assert sorted(p for p, _ in seq[:48]) == sorted(p for p, _ in seq[48:])
        sums = {seq[i][0] + seq[i + 1][0] for i in range(0, 96, 2)}
        assert max(sums) - min(sums) <= 2 and abs(sums.pop() - (2048 + 7168)) <= 2
    assert loadgen.LengthStream(spec, seed=5).__next__() == a[0]
    with pytest.raises(ValueError):
        loadgen.LengthStream(spec, seed=0, block=7)
