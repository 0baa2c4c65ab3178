"""The measured window: an open loop of independent front-ends.

One generator thread sends each request at its due time through
``Session.submit_async``, whether or not earlier ones have been answered, and
records when it actually sent it (its lateness) and when the answer came.
Requests carry no server-side deadline: the server refuses only what its
admission queue cannot hold, and a refusal counts as a failed request.  The
latency limit is the client's.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass

import numpy as np

WAIT_PAST_CLOSE_S = 60.0  # how long answers are awaited after the window closes


@dataclass
class Window:
    opened: float  # perf_counter seconds when the window opened
    seconds: float
    due: np.ndarray  # absolute perf_counter seconds
    sent: np.ndarray
    done: np.ndarray  # nan where no answer came
    answers: list  # QueryResult | Rejected | None (never came)

    @property
    def answered(self) -> np.ndarray:
        return np.array([a is not None and bool(a.ok) for a in self.answers])

    @property
    def closed(self) -> float:
        """When the last answer came (or the window's end, if later)."""
        last = np.nanmax(self.done) if np.isfinite(self.done).any() else self.opened
        return max(float(last), self.opened + self.seconds)

    @property
    def unanswered_at_close(self) -> int:
        """Requests due in the window with no answer yet when it closed."""
        return int((~(self.done <= self.opened + self.seconds)).sum())

    @property
    def trail_s(self) -> float:
        """How long the last answer came after the window's end (0 if before)."""
        return self.closed - (self.opened + self.seconds)


def run(session, reqs: list, due_s: np.ndarray, seconds: float, *,
        lead_s: float = 0.05, on_open=None) -> Window:
    """Send ``reqs[i]`` at ``due_s[i]`` after the window opens; wait for all."""
    n = len(reqs)
    sent = np.zeros(n)
    done = np.full(n, np.nan)
    answers: list = [None] * n
    futs = [None] * n
    left = threading.Semaphore(0)

    def finish(i):
        def cb(fut):
            done[i] = time.perf_counter()
            answers[i] = fut.result()
            left.release()
        return cb

    opened = time.perf_counter() + lead_s
    if on_open is not None:
        on_open(opened)
    due = opened + np.asarray(due_s, np.float64)
    for i, r in enumerate(reqs):
        wait = due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        sent[i] = time.perf_counter()
        futs[i] = session.submit_async(r)
        futs[i].add_done_callback(finish(i))
    give_up = opened + seconds + WAIT_PAST_CLOSE_S
    for _ in range(n):
        if not left.acquire(timeout=max(0.0, give_up - time.perf_counter())):
            break
    return Window(opened=opened, seconds=seconds, due=due, sent=sent,
                  done=done.copy(), answers=list(answers))
