"""``attempted`` and ``failed`` on a stub engine whose tick the test sets: a
slow tick changes every latency and no count."""

import threading
import time

from benchmark.generators.requests import Planned
from benchmark.modes import serve

import numpy as np


class StubRequest:
    def __init__(self, n_tokens, on_token):
        self.n_tokens, self.on_token = n_tokens, on_token
        self.output_ids, self.finish_reason = [], None
        self.done = False


class StubEngine:
    """One token for every live request per tick of ``tick_s`` seconds;
    requests named in ``poison`` end with an error instead of their tokens."""

    def __init__(self, tick_s, poison=()):
        self.tick_s, self.poison = tick_s, set(poison)
        self.live, self.lock = [], threading.Lock()
        self.stop = threading.Event()
        self.n_submitted = 0
        self.thread = threading.Thread(target=self._loop)
        self.thread.start()

    def submit(self, prompt, params, on_token=None):
        if len(prompt) == 13:
            raise ValueError("refused at submit")
        req = StubRequest(params.max_new_tokens, on_token)
        req.poisoned = self.n_submitted in self.poison
        self.n_submitted += 1
        with self.lock:
            self.live.append(req)
        return req

    def _loop(self):
        while not self.stop.is_set():
            time.sleep(self.tick_s)
            with self.lock:
                live, self.live = self.live, []
            for req in live:
                if req.poisoned and len(req.output_ids) >= 1:
                    req.finish_reason, req.done = "error", True
                    continue
                req.output_ids.append(1)
                req.on_token(req, 1, "")
                if len(req.output_ids) >= req.n_tokens:
                    req.finish_reason, req.done = "length", True
                else:
                    with self.lock:
                        self.live.append(req)

    def close(self):
        self.stop.set()
        self.thread.join()


def plan(n, gap_s, refused=()):
    return [Planned(i, i * gap_s,
                    np.zeros(13 if i in refused else 5, np.int32), 4, 0.0,
                    None, i) for i in range(n)]


def drive(tick_s, poison=(), refused=()):
    import building_llm_from_scratch_tpu.serving.request  # noqa: F401 (warm)

    engine = StubEngine(tick_s, poison)
    flights = [serve.Flight(p) for p in plan(12, 0.01, refused)]
    t_open = time.perf_counter()
    try:
        serve.open_loop(engine, flights, t_open, threading.Event())
        serve.wait_for(flights, guard_s=30.0)
    finally:
        engine.close()
    failed = [f for f in flights if f.failed]
    ttft = [f.stamps[0] - (t_open + f.planned.due_s)
            for f in flights if f.stamps]
    return len(flights), len(failed), max(ttft)


def test_a_slow_tick_changes_latency_and_not_failed():
    fast = drive(tick_s=0.002)
    slow = drive(tick_s=0.05)
    assert fast[:2] == slow[:2] == (12, 0)
    assert slow[2] > 5 * fast[2]


def test_failed_counts_errors_and_refusals_whatever_the_tick():
    for tick_s in (0.002, 0.05):
        attempted, failed, _ = drive(tick_s, poison={3}, refused={7})
        assert (attempted, failed) == (12, 2)


def test_closed_loop_counts_the_plans_first_requests_whatever_the_tick():
    sent = {}
    for tick_s in (0.002, 0.03):
        engine = StubEngine(tick_s)
        flights = [serve.Flight(p) for p in plan(400, 0.0)]
        try:
            serve.closed_loop(engine, flights, 4, 24, time.perf_counter(),
                              0.15, threading.Event())
        finally:
            engine.close()
        # the slow tick needs 0.7 s for them, so the callers ran on past the
        # window; the fast one finished them early and sent many more
        assert all(f.done and not f.failed for f in flights[:24])
        sent[tick_s] = sum(f.t_sent is not None for f in flights)
    assert sent[0.002] > 2 * sent[0.03] >= 2 * 24
