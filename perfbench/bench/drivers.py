"""Window drivers: send the traffic, time every answer, classify it.

Each request is timed from when it was due (open loop: its scheduled
send time; closed loop: when its client sent it) to the moment its
answer was set, read by a done-callback on the request's future.  A
request the runtime refused at the door or shed by deadline is not
``failed``: it has no answer and counts, in the tails, as slower than
every answered one.  A request that raised anything else, or was never
answered, is ``failed``.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
from concurrent.futures import TimeoutError as FutureTimeout
from typing import Callable, List, Optional, Sequence, Tuple, Type

import numpy as np

from jax.profiler import TraceAnnotation


def annotate(name: str):
    """A host span in the profiler's trace (next to no cost when not
    tracing)."""
    return TraceAnnotation(f"perfbench.{name}")


@dataclasses.dataclass(eq=False)
class Record:
    index: int
    seeds: np.ndarray
    due: float = math.nan
    sent: float = math.nan        # when submit() was entered
    done: float = math.nan        # when the answer (or verdict) was set
    status: str = "pending"       # ok | refused | shed | failed | pending
    error: str = ""
    out: Optional[np.ndarray] = None
    wait_s: Optional[float] = None
    prep_s: Optional[float] = None
    future: object = None

    @property
    def latency_s(self) -> float:
        return self.done - self.due


Submit = Callable[[np.ndarray, Optional[float]], object]


class Sender:
    """Submits one request and wires its future to its record."""

    def __init__(self, submit: Submit, refused: Tuple[Type, ...],
                 shed: Tuple[Type, ...]):
        self.submit, self.refused, self.shed = submit, refused, shed

    def send(self, rec: Record, deadline: Optional[float]) -> None:
        rec.sent = time.perf_counter()
        try:
            with annotate("submit"):
                req = self.submit(rec.seeds, deadline)
        except self.refused as e:
            rec.done, rec.status, rec.error = time.perf_counter(), "refused", \
                type(e).__name__
            return
        except Exception as e:  # noqa: BLE001 - a failed request, counted
            rec.done, rec.status, rec.error = time.perf_counter(), "failed", \
                f"{type(e).__name__}: {e}"
            return
        rec.prep_s = getattr(req, "prep_s", None)
        rec.future = req.future
        # The request holds its padded operands: the record keeps only the
        # future, and the callback lets go of the request once it fires.
        held = [req]

        def stamp(_fut):
            rec.done = time.perf_counter()
            rec.wait_s = getattr(held.pop(), "wait_s", None)

        req.future.add_done_callback(stamp)

    def settle(self, rec: Record, timeout_s: float) -> None:
        """Wait for ``rec``'s answer and classify it."""
        if rec.status != "pending" or rec.future is None:
            return
        fut = rec.future
        try:
            rec.out = np.asarray(fut.result(timeout=timeout_s))
            rec.status = "ok"
        except FutureTimeout:
            rec.status, rec.error = "failed", "no answer"
        except self.refused as e:
            rec.status, rec.error = "refused", type(e).__name__
        except self.shed as e:
            rec.status, rec.error = "shed", type(e).__name__
        except Exception as e:  # noqa: BLE001 - a failed request, counted
            rec.status, rec.error = "failed", f"{type(e).__name__}: {e}"
        if math.isnan(rec.done):
            rec.done = time.perf_counter()


def _run_threads(n: int, target, name: str) -> None:
    threads = [threading.Thread(target=target, name=f"{name}-{i}",
                                daemon=True) for i in range(n)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()


def open_loop(sender: Sender, requests: Sequence[np.ndarray],
              due: Sequence[float], *, threads: int,
              deadline_s: Optional[float]) -> List[Record]:
    """Send request ``i`` at ``due[i]`` (``time.perf_counter`` seconds)
    from a pool of ``threads`` senders, so one slow submission never holds
    back the next due request."""
    recs = [Record(i, np.asarray(s), due=float(d))
            for i, (s, d) in enumerate(zip(requests, due))]
    nxt = iter(recs)
    lock = threading.Lock()

    def worker():
        while True:
            with lock:
                rec = next(nxt, None)
            if rec is None:
                return
            lag = rec.due - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
            sender.send(rec, None if deadline_s is None
                        else rec.due + deadline_s)

    _run_threads(threads, worker, "perfbench-send")
    return recs


def closed_loop(sender: Sender, requests: Sequence[np.ndarray], *,
                clients: int, until: float, deadline_s: Optional[float],
                timeout_s: float) -> List[Record]:
    """``clients`` threads, each sending the next request of the shared
    sequence when its last one is answered, until ``until``."""
    recs: List[Record] = []
    lock = threading.Lock()
    counter = iter(range(len(requests)))

    def client():
        while time.perf_counter() < until:
            with lock:
                i = next(counter, None)
                if i is None:
                    return
                rec = Record(i, np.asarray(requests[i]))
                recs.append(rec)
            rec.due = time.perf_counter()
            sender.send(rec, None if deadline_s is None
                        else rec.due + deadline_s)
            sender.settle(rec, timeout_s)

    _run_threads(clients, client, "perfbench-client")
    recs.sort(key=lambda r: r.index)
    return recs


# -- arithmetic over records ------------------------------------------------


def rank_latencies(recs: Sequence[Record]) -> List[float]:
    """Latencies in ms, ascending, every unanswered request last and
    counted as slower than every answered one (at the later of the slowest
    answer and its own time without one)."""
    answered = sorted(r.latency_s * 1e3 for r in recs if r.status == "ok")
    slowest = answered[-1] if answered else 0.0
    rest = sorted(max(slowest, (r.done - r.due) * 1e3)
                  for r in recs if r.status != "ok")
    return answered + rest


def percentile(sorted_ms: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile of an ascending list (None when empty)."""
    if not sorted_ms:
        return None
    k = max(int(math.ceil(q / 100.0 * len(sorted_ms))) - 1, 0)
    return float(sorted_ms[k])


def lateness_s(recs: Sequence[Record]) -> np.ndarray:
    """How late each request was sent against its due time."""
    return np.array([r.sent - r.due for r in recs if not math.isnan(r.sent)])
