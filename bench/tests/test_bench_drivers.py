"""The closed-loop driver times each request from its submission, and the
rate counts what the window answered, checked with a fake server on a fake
clock."""
import contextlib
import types

import numpy as np

from harness import drivers


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        # a real sleep always lets time pass; keep the fake clock moving
        # where a sub-ulp wait would not advance it
        self.t += max(dt, 1e-6)


class FakeServer:
    """Answers every queued request in one pump that takes ``service`` s."""

    def __init__(self, clock, service):
        self.clock, self.service = clock, service
        self.queue = []
        self.scheduler = types.SimpleNamespace(
            oldest_arrival=lambda: min((a for _, _, a in self.queue), default=None))

    @property
    def n_queued(self):
        return len(self.queue)

    @property
    def has_ready_batch(self):
        return len(self.queue) >= 4

    def submit(self, ts, tag=None):
        self.queue.append((tag, ts, self.clock()))

    def pump(self, force=True):
        self.clock.t += self.service
        out = [types.SimpleNamespace(
            tag=tag, ok=True, heat=np.zeros((len(ts), 3)),
            stats=types.SimpleNamespace(cache_hits=0, windows_evaluated=len(ts)))
            for tag, ts, _ in self.queue]
        self.queue = []
        return out


def no_span(name):
    return contextlib.nullcontext()


def test_closed_loop_keeps_each_client_busy_until_the_window_closes():
    clock = Clock()
    server = FakeServer(clock, service=0.5)
    n = iter(range(1000))
    rec = drivers.drive_closed(server, lambda: (float(next(n)),), 4,
                               seconds=2.0, span=no_span, clock=clock,
                               sleep=clock.sleep)
    # 4 clients, one full group of 4 per 0.5 s pump: 4 rounds in 2 s
    assert len(rec.answers) == 16
    assert all(a.ok and np.isclose(a.latency, 0.5) for a in rec.answers)
    assert rec.t_close == 102.0


def windows_per_s(answers, t_close):
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "metrics", "windows_per_s.py")
    spec = importlib.util.spec_from_file_location("wps", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rec = drivers.Record(answers=answers, t_open=0.0, t_close=t_close,
                         t_drained=max(a.done for a in answers))
    return mod.read(types.SimpleNamespace(record=rec))


def test_windows_per_s_counts_from_the_first_response():
    A = drivers.Answer
    answers = [A(0, (1, 2), 0.0, done=1.0, ok=True),
               A(1, (1, 2, 3), 0.0, done=2.0, ok=True),
               A(2, (1,), 0.0, done=3.0, ok=True),
               A(3, (1, 2, 3, 4), 0.0, done=9.0, ok=True)]
    assert windows_per_s(answers, 5.0) == 4 / 2.0


def test_windows_per_s_leaves_out_the_whole_first_pump():
    # one pump stamps all of its responses with one time: the work of the
    # first pump falls before the rate opens, so none of its windows count
    A = drivers.Answer
    answers = [A(0, (1,), 0.0, done=1.0, ok=True),
               A(1, (1, 2, 3), 0.0, done=1.0, ok=True),
               A(2, (1, 2, 3, 4), 0.0, done=1.0, ok=True),
               A(3, (1, 2), 0.0, done=3.0, ok=True),
               A(4, (1, 2, 3), 0.0, done=3.0, ok=True),
               A(5, (1,), 0.0, done=5.0, ok=True)]
    assert windows_per_s(answers, 5.0) == 6 / 4.0
    # a window whose responses all came in one pump has no rate
    assert windows_per_s(answers[:3], 5.0) is None


def test_closed_loop_rate_is_the_servers_rate():
    # 4 clients, 4 windows per pump of 0.5 s: 8 windows/s, however the
    # first pump groups them
    clock = Clock()
    server = FakeServer(clock, service=0.5)
    n = iter(range(1000))
    rec = drivers.drive_closed(server, lambda: (float(next(n)),), 4,
                               seconds=3.2, span=no_span, clock=clock,
                               sleep=clock.sleep)
    assert np.isclose(windows_per_s(rec.answers, rec.t_close), 8.0)
