"""The closed-loop driver over the server's driving surface.

``clients`` callers each send their next request as soon as their last one
is answered, until the window closes; then the admitted requests are
drained. The pumping policy is the program's ``serve.loadgen`` one, copied
here so that the yardstick does not move with the program: a full group is
pumped at once, a partial one after it has lingered ``linger_s``. Host
spans (``admit``, ``pump``, ``drain``, ``sleep``) label what the host was
doing, for the trace's idle gaps.

It returns a :class:`Record`: one :class:`Answer` per admitted request and
the window's start, end and drain on the host clock.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional


@dataclasses.dataclass
class Answer:
    tag: int
    ts: tuple
    sched: float  # submission time, host clock
    done: Optional[float] = None  # response time, host clock; None = never
    ok: bool = False
    heat: object = None  # [len(ts), L] rows as returned
    cache_hits: int = 0
    windows_evaluated: int = 0
    error: Optional[str] = None

    @property
    def latency(self) -> Optional[float]:
        return None if self.done is None else self.done - self.sched


@dataclasses.dataclass
class Record:
    answers: List[Answer]
    t_open: float
    t_close: float  # the window's end (host clock)
    t_drained: float


def _handle(responses, answers: Dict[int, Answer], now: float) -> List[int]:
    done = []
    for r in responses:
        a = answers[r.tag]
        a.done = now
        a.ok = bool(r.ok)
        if r.ok:
            a.heat = r.heat
            a.cache_hits = int(r.stats.cache_hits)
            a.windows_evaluated = int(r.stats.windows_evaluated)
        else:
            a.error = f"{r.error.code}: {r.error.message}"
        done.append(r.tag)
    return done


def drive_closed(server, next_request: Callable, clients: int, *,
                 seconds: float, span: Callable, clock: Callable = time.perf_counter,
                 linger_s: float = 0.005, sleep: Callable = time.sleep) -> Record:
    """``clients`` callers, each with one request outstanding, until the
    window of ``seconds`` closes; then the admitted requests drain.
    ``span(name)`` is the context that labels what the host does
    (``jax.profiler.TraceAnnotation`` when tracing)."""
    answers: Dict[int, Answer] = {}
    t0 = clock()
    deadline = t0 + seconds
    idle = clients
    tag = 0

    def handle(responses):
        nonlocal idle
        idle += len(_handle(responses, answers, clock()))

    while True:
        if idle and clock() < deadline:
            with span("admit"):
                while idle:
                    ts = next_request()
                    answers[tag] = Answer(tag=tag, ts=ts, sched=clock())
                    server.submit(ts, tag=tag)
                    tag += 1
                    idle -= 1
        if not server.n_queued:
            if clock() >= deadline:
                break
            continue
        if server.has_ready_batch:
            with span("pump"):
                handle(server.pump(force=False))
            continue
        oldest = server.scheduler.oldest_arrival()
        lingered = oldest is not None and clock() - oldest >= linger_s
        if clock() >= deadline or lingered:
            with span("drain" if clock() >= deadline else "pump"):
                handle(server.pump(force=True))
            continue
        with span("sleep"):
            sleep(max(min(linger_s - (clock() - oldest), 0.01), 0.0))
    return Record(list(answers.values()), t0, deadline, clock())
