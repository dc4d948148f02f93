"""The load generator: one thread, the generator's own clock.

Open loop: each request is sent when it is due and timed from when it was
DUE, so a stall is paid by the requests behind it; how late the generator
itself ran is reported. Closed loop: each of `clients` sends its next
request when its last returns. Tokens are stamped in the engine's
`on_token` callback on this module's clock. The window ends on time:
what is still in flight then is attempted, not failed, and enters the
tails with what it has.
"""
from __future__ import annotations

import dataclasses
import queue
import time
from typing import Any, Callable, List, Optional

from .traffic import Request

clock = time.perf_counter


@dataclasses.dataclass
class Record:
    req: Request
    due: float = 0.0                    # absolute, generator's clock
    t_submit: Optional[float] = None
    t_first: Optional[float] = None
    t_last: Optional[float] = None
    n_tok: int = 0
    stamps: List[float] = dataclasses.field(default_factory=list)
    handle: Any = None                  # the program's request handle
    error: Optional[str] = None

    @property
    def done(self) -> bool:
        return self.n_tok >= self.req.n_out


def _send(submit: Callable, rec: Record, t_end: float,
          on_done: Optional[Callable[[Record], None]] = None) -> None:
    def on_token(_tok: int, r: Record = rec) -> None:
        now = clock()
        if now > t_end:
            return                      # the window is closed
        if r.n_tok == 0:
            r.t_first = now
        r.t_last = now
        r.n_tok += 1
        r.stamps.append(now)            # every token, inside the window
        if on_done is not None and r.n_tok == r.req.n_out:
            on_done(r)

    rec.t_submit = clock()
    try:
        rec.handle = submit(rec.req.prompt, rec.req.n_out, on_token)
    except Exception as e:      # noqa: BLE001 - refused or failed: counted
        rec.error = f"{type(e).__name__}: {e}"
        if on_done is not None:
            on_done(rec)


def run_open(submit: Callable, reqs: List[Request], seconds: float):
    """Send each request at t0 + due_s. Returns (records, t0, t_end)."""
    t0 = clock()
    t_end = t0 + seconds
    recs: List[Record] = []
    for r in reqs:
        due = t0 + r.due_s
        if due >= t_end:
            break
        wait = due - clock()
        if wait > 0:
            time.sleep(wait)
        rec = Record(r, due=due)
        recs.append(rec)
        _send(submit, rec, t_end)
    rest = t_end - clock()
    if rest > 0:
        time.sleep(rest)
    return recs, t0, t_end


def run_closed(submit: Callable, reqs: List[Request], clients: int,
               seconds: float):
    """`clients` callers, each sending its next request (the next of
    `reqs`) when its last returned. Returns (records sent, t0, t_end)."""
    returned: "queue.Queue[Record]" = queue.Queue()
    t0 = clock()
    t_end = t0 + seconds
    recs: List[Record] = []
    todo = iter(reqs)

    def send_next() -> None:
        r = next(todo, None)
        if r is None:
            raise RuntimeError(
                f"the closed loop ran out of its {len(reqs)} requests "
                f"inside the window: raise the cell's `requests_per_s_max`")
        rec = Record(r, due=clock())
        recs.append(rec)
        _send(submit, rec, t_end, returned.put)

    for _ in range(clients):
        send_next()
    while True:
        rest = t_end - clock()
        if rest <= 0:
            break
        try:
            returned.get(timeout=rest)
        except queue.Empty:
            break
        if clock() < t_end:
            send_next()
    return recs, t0, t_end


def lateness(recs: List[Record]) -> List[float]:
    """Seconds each request was sent after it was due (open loop)."""
    return [r.t_submit - r.due for r in recs if r.t_submit is not None]
