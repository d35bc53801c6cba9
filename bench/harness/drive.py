"""The load: closed-loop clients, each recording on the host clock what
it sent, when, and what came back.

Every submit is wrapped in a ``jax.profiler`` annotation
(``bench.submit``) so that a profiled window can name the host's work
in a device idle gap.
"""
from __future__ import annotations

import threading
import time

from harness.traffic import Req, to_query

now = time.perf_counter


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Record:
    """One request: due (the client's send time), sent and answered
    times, answer or error."""

    __slots__ = ("req", "due", "sent", "done", "value", "error")

    def __init__(self, req: Req, due: float):
        self.req, self.due = req, due
        self.sent = self.done = None
        self.value = self.error = None

    def finish(self, fut) -> None:
        exc = fut.exception()
        if exc is not None:
            self.error = f"{type(exc).__name__}: {exc}"
        else:
            self.value = fut.result()
        self.done = now()


def submit(frontend, r: Req):
    if r.kind == "evolve":
        return frontend.submit_sweep(r.measure, r.t_k, r.t_l,
                                     stride=r.stride, v=r.v, scope=r.scope)
    return frontend.submit(to_query(r))


def _send(frontend, rec: Record):
    """Submit one request; its answer lands in ``rec`` (the future is
    returned, or None when the submit itself was refused)."""
    rec.sent = now()
    try:
        with _annotate("bench.submit"):
            fut = submit(frontend, rec.req)
    except Exception as exc:  # noqa: BLE001 — a refused request is a result
        rec.error, rec.done = f"{type(exc).__name__}: {exc}", now()
        return None
    fut.add_done_callback(rec.finish)
    return fut


class ClosedLoop:
    """``clients`` callers; each sends its next request when the last
    was answered, until ``t_end``."""

    def __init__(self, frontend, streams, t_end: float,
                 timeout: float = 120.0):
        self.frontend, self.t_end, self.timeout = frontend, t_end, timeout
        self.records: list[Record] = []
        self._lock = threading.Lock()
        self.threads = [threading.Thread(target=self._client, args=(s,),
                                         name=f"bench-client-{i}",
                                         daemon=True)
                        for i, s in enumerate(streams)]

    def _client(self, stream):
        for r in stream:
            t = now()
            if t >= self.t_end:
                return
            rec = Record(r, t)
            with self._lock:
                self.records.append(rec)
            fut = _send(self.frontend, rec)
            if fut is None:
                continue
            try:
                fut.result(timeout=self.timeout)
            except TimeoutError:
                return                     # unanswered: the check reports it
            except Exception:  # noqa: BLE001 — the record holds the error
                pass

    def start(self):
        for th in self.threads:
            th.start()

    def join(self, timeout=None):
        for th in self.threads:
            th.join(timeout)

