"""What a driver sends and when: the request record every metric reads.

Each request keeps its scheduled time, the time it was handed to
``ServingRuntime.submit`` and the time its future resolved, all on
``time.perf_counter``.  A latency is taken from the scheduled time, so a
late generator counts against the server, and the lateness of the
generator is reported beside it.
"""
from __future__ import annotations

import threading
import time
from functools import partial

import numpy as np


DROPPED = object()     # an answered request whose answer was not kept


class Requests:
    """Append-only record of the requests of one phase (one sender
    thread appends; completion callbacks only write their own slot).

    ``keep`` marks the requests whose answers are kept for the
    comparison with the reference (all when None).  The others are let go
    as soon as they are answered, so the record does not grow the
    process's heap, and its garbage collections, as the window runs."""

    def __init__(self, capacity: int, keep=None):
        self.keep = keep
        self.due = np.full(capacity, np.nan)
        self.sent = np.full(capacity, np.nan)
        self.done = np.full(capacity, np.nan)
        self.queries: list = []       # (text, kind, target row)
        self.futures: list = []       # Future, or None when rejected
        self.floor: list = []         # writes visible when it was sent
        self.errors: list = []        # (index, repr) of refused/failed
        self._lock = threading.Lock()
        self.on_done = None           # optional callback(index)

    def __len__(self) -> int:
        return len(self.queries)

    def send(self, runtime, k: int, query, due: float, floor: int = 0) -> int:
        from repro.serving import RequestRejected

        i = len(self.queries)
        if i >= len(self.due):
            raise RuntimeError(f"request record full at {i}")
        self.queries.append(query)
        self.floor.append(floor)
        self.due[i] = due
        self.sent[i] = time.perf_counter()
        try:
            fut = runtime.submit(query[0], k)
        except RequestRejected as exc:
            self.futures.append(None)
            with self._lock:
                self.errors.append((i, repr(exc)))
            return i
        self.futures.append(fut)
        fut.add_done_callback(partial(self._finish, i))
        return i

    def _finish(self, i: int, fut) -> None:
        self.done[i] = time.perf_counter()
        if fut.exception() is not None:
            with self._lock:
                self.errors.append((i, repr(fut.exception())))
        elif self.keep is not None and not self.keep[i]:
            self.futures[i] = DROPPED
        if self.on_done is not None:
            self.on_done(i)

    def wait(self, deadline: float) -> None:
        """Wait until every sent request resolved or ``deadline``."""
        for fut in list(self.futures):
            if fut is None or fut is DROPPED:
                continue
            left = deadline - time.perf_counter()
            if left <= 0:
                return
            try:
                fut.result(timeout=left)
            except Exception:  # noqa: BLE001 -- recorded by _finish
                pass

    def in_window(self, t0: float, t1: float) -> np.ndarray:
        """Indices of requests scheduled inside [t0, t1)."""
        n = len(self)
        return np.flatnonzero((self.due[:n] >= t0) & (self.due[:n] < t1))

    def failed(self, idx: np.ndarray) -> int:
        """Refused, errored or never answered, among ``idx``."""
        bad = {i for i, _ in self.errors}
        return sum(1 for i in idx if i in bad or np.isnan(self.done[i]))


def sleep_until(t: float) -> None:
    left = t - time.perf_counter()
    if left > 0:
        time.sleep(left)


def open_loop(runtime, k: int, reqs: Requests, offsets, queries, t0: float,
              floor=lambda: 0) -> None:
    """Send ``queries[i]`` at ``t0 + offsets[i]`` whether or not earlier
    requests have been answered."""
    for off, q in zip(offsets, queries):
        due = t0 + float(off)
        sleep_until(due)
        reqs.send(runtime, k, q, due, floor())
