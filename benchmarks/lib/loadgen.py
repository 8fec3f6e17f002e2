"""The closed-loop load generator: `clients` threads, each with one
keep-alive connection, each sending its next request when the last one is
answered. One process, few threads' worth of Python per request, so the
generator's own cost stays small beside the server's.
"""

from __future__ import annotations

import threading
import time

from .proc import BenchFailure, Http


class Sent:
    """One request as the client saw it."""

    __slots__ = ("req", "t_send", "wall_send", "ms", "status", "got")

    def __init__(self, req, t_send, wall_send, ms, status, got):
        self.req, self.t_send, self.wall_send = req, t_send, wall_send
        self.ms, self.status, self.got = ms, status, got


def drive(host: str, port: int, index: str, clients: int, next_request,
          seconds: float | None, grace_s: float = 60.0) -> tuple:
    """Send requests from `next_request()` until `seconds` have passed (or,
    with seconds None, until it returns None). A request in flight at the
    close is waited for, up to grace_s past it, and counted with its full
    time: late is late, not wrong. Returns (list of Sent, window seconds
    from the first send to the close)."""
    out: list = []
    lock = threading.Lock()
    t_open = time.perf_counter()
    t_close = None if seconds is None else t_open + seconds

    def client() -> None:
        http = Http(host, port, timeout=(seconds or 0.0) + grace_s)
        mine: list = []
        try:
            while t_close is None or time.perf_counter() < t_close:
                req = next_request()
                if req is None:
                    break
                wall = time.time()
                t0 = time.perf_counter()
                try:
                    status, got = http.query(index, req["pql"])
                except BenchFailure as e:
                    status, got = 0, str(e)
                mine.append(Sent(req, t0, wall,
                                 (time.perf_counter() - t0) * 1e3,
                                 status, got))
        finally:
            http.close()
            with lock:
                out.extend(mine)

    threads = [threading.Thread(target=client) for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    window = (seconds if seconds is not None
              else time.perf_counter() - t_open)
    out.sort(key=lambda s: s.t_send)
    return out, window
