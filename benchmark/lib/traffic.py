"""The one load generator: reads a traffic file's parameters and drives a
``submit(rows) -> Future[(distances, ids)]`` callable from client threads.

Traffic file keys (``benchmark/traffic/<mix>.json``):

- ``loop``: ``"closed"`` (each client sends its next request when the last
  one is answered) or ``"open"`` (one sender keeps a seeded schedule,
  whether or not earlier requests have been answered);
- ``rows_per_request``: query rows in one request (1 sends a 1-D vector);
- ``clients``: closed loop, the number of client threads;
- ``rate``: open loop, requests per second offered;
- ``max_batch``, ``min_bucket``, ``max_delay_ms``: the service's batcher.

Each request records when it was due (``t_sched``), sent and answered, the
pool rows it asked for and what came back.  A request is timed from when
it was due, so a stalled sender still counts against the latency.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import jax
import numpy as np

from benchmark.lib.arrivals import fixed_poisson_arrivals

#: seconds past the window's close that a run waits for an answer
ANSWER_GRACE_S = 60.0


@dataclass
class Request:
    t_sched: float
    rows: np.ndarray  # pool indices asked for
    t_send: float = 0.0
    t_done: Optional[float] = None
    dists: Optional[np.ndarray] = None
    ids: Optional[np.ndarray] = None
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.t_done is not None and self.error is None

    def latency_s(self) -> float:
        return self.t_done - self.t_sched if self.ok else float("inf")


@dataclass
class Window:
    t0: float
    t_close: float  # no request is due after this
    t_last: float = 0.0  # last answer
    requests: List[Request] = field(default_factory=list)


def _pool_rows(rng, pool_n: int, rows: int) -> np.ndarray:
    return rng.choice(pool_n, size=rows, replace=False)


def warm_buckets(submit: Callable, pool: np.ndarray, traffic: dict) -> None:
    """Send bursts of the mix's requests that fill each of the batcher's
    buckets once (min_bucket, 2 x min_bucket, ... max_batch rows), with
    real rows, and wait for every answer."""
    m = int(traffic["rows_per_request"])
    b = int(traffic["min_bucket"])
    while b <= int(traffic["max_batch"]):
        n = max(1, b // m)
        futs = [submit(_payload(pool, np.arange(i * m, (i + 1) * m)
                                % pool.shape[0])) for i in range(n)]
        for f in futs:
            f.result(timeout=ANSWER_GRACE_S)
        b *= 2


def run(submit: Callable, pool: np.ndarray, traffic: dict, seconds: float,
        seed: int) -> Window:
    """Drive ``submit`` for ``seconds`` as ``traffic`` says; returns once
    every request sent has been answered, or ANSWER_GRACE_S after the
    close."""
    if traffic["loop"] == "closed":
        return _closed(submit, pool, traffic, seconds, seed)
    if traffic["loop"] == "open":
        return _open(submit, pool, traffic, seconds, seed)
    raise ValueError(f"unknown loop {traffic['loop']!r}")


def _payload(pool, rows):
    return pool[rows[0]] if rows.shape[0] == 1 else pool[rows]


def _closed(submit, pool, traffic, seconds, seed) -> Window:
    clients = int(traffic["clients"])
    m = int(traffic["rows_per_request"])
    per_client: List[List[Request]] = [[] for _ in range(clients)]
    t0 = time.perf_counter()
    win = Window(t0=t0, t_close=t0 + seconds)

    def client(c: int):
        rng = np.random.default_rng([seed, c])
        out = per_client[c]
        while True:
            now = time.perf_counter()
            if now >= win.t_close:
                return
            req = Request(t_sched=now, rows=_pool_rows(rng, pool.shape[0], m))
            req.t_send = now
            out.append(req)
            try:
                with jax.profiler.TraceAnnotation("bench.request"):
                    d, i = submit(_payload(pool, req.rows)).result(
                        timeout=win.t_close + ANSWER_GRACE_S - now)
                req.t_done = time.perf_counter()
                req.dists, req.ids = np.asarray(d), np.asarray(i)
            except Exception as e:  # noqa: BLE001 — a failed request is data
                req.error = repr(e)
                return

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + ANSWER_GRACE_S + 5)
    for reqs in per_client:
        win.requests.extend(reqs)
    win.t_last = max((r.t_done for r in win.requests if r.ok), default=t0)
    return win


def _open(submit, pool, traffic, seconds, seed) -> Window:
    rate = float(traffic["rate"])
    m = int(traffic["rows_per_request"])
    offsets = fixed_poisson_arrivals(rate, seconds, seed)
    rng = np.random.default_rng([seed, 1 << 20])
    reqs = [Request(t_sched=0.0, rows=(rng.integers(0, pool.shape[0], 1)
                                       if m == 1 else
                                       _pool_rows(rng, pool.shape[0], m)))
            for _ in offsets]
    done = threading.Event()
    pending = [len(reqs)]
    lock = threading.Lock()

    def finish(req: Request, fut):
        t = time.perf_counter()
        try:
            d, i = fut.result()
            req.dists, req.ids = np.asarray(d), np.asarray(i)
            req.t_done = t
        except Exception as e:  # noqa: BLE001 — a failed request is data
            req.error = repr(e)
        with lock:
            pending[0] -= 1
            if pending[0] == 0:
                done.set()

    t0 = time.perf_counter()
    win = Window(t0=t0, t_close=t0 + seconds, requests=reqs)
    for req, off in zip(reqs, offsets):
        req.t_sched = t0 + off
        wait = req.t_sched - time.perf_counter()
        if wait > 0:
            with jax.profiler.TraceAnnotation("bench.sender_sleep"):
                time.sleep(wait)
        req.t_send = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.submit"):
                fut = submit(_payload(pool, req.rows))
        except Exception as e:  # noqa: BLE001 — a refused request is data
            req.error = repr(e)
            with lock:
                pending[0] -= 1
                if pending[0] == 0:
                    done.set()
            continue
        fut.add_done_callback(lambda f, r=req: finish(r, f))
    with jax.profiler.TraceAnnotation("bench.drain"):
        done.wait(timeout=max(0.0, win.t_close + ANSWER_GRACE_S
                              - time.perf_counter()))
    win.t_last = max((r.t_done for r in reqs if r.ok), default=t0)
    return win
