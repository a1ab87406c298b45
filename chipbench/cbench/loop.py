"""The serving loop: sends a mix to a ``ServeSession`` and times the client side.

Only the session's public API is used: ``submit``, ``step``, ``slots``,
``pending`` and each request's ``out_tokens`` / ``done``. ``step()`` returns
after its tokens reach the host, so a token's time is the end of the step
that produced it, as a client polling the session would see it; tokens
produced in one step share that time.

The loop records per request its due, sent and first-token times, each
token's time and the slot that served it, and per step its start, end,
live slots, the prompts it prefilled and the cache length each decoded
slot attended over. Metric readers take everything from these records.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Optional

from jax.profiler import TraceAnnotation

from cbench.traffic import Draw, Stream


@dataclasses.dataclass
class Req:
    draw: Draw
    handle: object            # the session's Request
    t_due: float
    t_sent: float
    t_first: Optional[float] = None
    times: list = dataclasses.field(default_factory=list)
    slot: Optional[int] = None    # the session slot that served it


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    live: int                     # step()'s return value
    prefill_lens: list            # prompts admitted in this step
    decode_lens: list             # cache rows each decoded slot attended
    traced: bool = False


class Loop:
    def __init__(self, session, mix: dict, draws: Stream,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep):
        self.session = session
        self.mix = mix
        self.draws = draws
        self.clock = clock
        self.sleep = sleep
        self.next = 0                   # index of the next draw to send
        self.live: list[Req] = []
        self.reqs: list[Req] = []
        self.steps: list[Step] = []
        self.window = (0.0, 0.0)
        self.tracing = False

    # ------------------------------------------------------------ sending
    def _send(self, draw: Draw, t_due: float, max_new: Optional[int] = None) -> None:
        with TraceAnnotation("chipbench.submit"):
            h = self.session.submit(draw.prompt,
                                    max_new_tokens=max_new or draw.max_new)
        r = Req(draw=draw, handle=h, t_due=t_due, t_sent=self.clock())
        self.reqs.append(r)
        self.live.append(r)

    def _take(self) -> Draw:
        d = self.draws[self.next]
        self.next += 1
        return d

    # ------------------------------------------------------------ stepping
    def _step(self) -> Step:
        t0 = self.clock()
        with TraceAnnotation("chipbench.step"):
            live = self.session.step()
        t1 = self.clock()
        with TraceAnnotation("chipbench.account"):
            st = Step(t0, t1, live, [], [], self.tracing)
            snapshot, self.live, done = self.live, [], 0
            for r in snapshot:
                n = len(r.handle.out_tokens)
                if n > len(r.times):
                    if not r.times:
                        st.prefill_lens.append(len(r.draw.prompt))
                        r.t_first = t1
                        r.slot = next((i for i, h in enumerate(self.session.slots)
                                       if h is r.handle), None)
                    r.times.extend([t1] * (n - len(r.times)))
                    st.decode_lens.append(len(r.draw.prompt) + n - 1)
                if r.handle.done:
                    done += 1
                else:
                    self.live.append(r)
            if self.mix["loop"] == "closed":
                for _ in range(done):
                    self._send(self._take(), t_due=t1)
            self.steps.append(st)
        return st

    def preroll(self) -> None:
        """Closed loop: fill every client with a request whose remaining
        length is staggered (client i keeps (i+1)/clients of its draw), as
        if the loop had been running, and admit them all. Set-up, not
        window."""
        n = self.mix["clients"]
        for i in range(n):
            d = self._take()
            self._send(d, t_due=self.clock(),
                       max_new=max(1, math.ceil(d.max_new * (i + 1) / n)))
        self._step()

    def run(self, seconds: float, trace: Optional[tuple[float, float, object, object]] = None):
        """Drive the mix for ``seconds``. ``trace``: (start offset, length,
        start_fn, stop_fn); the trace starts and stops at step boundaries
        and the steps between are marked ``traced``."""
        t0 = self.clock()
        t_end = t0 + seconds
        traced_span = None
        state = "before" if trace else "done"
        while True:
            now = self.clock()
            if state == "before" and now >= t0 + trace[0]:
                trace[2]()
                traced_span = TraceAnnotation("chipbench.traced")
                traced_span.__enter__()
                self.tracing, state, t_trace = True, "on", self.clock()
            elif state == "on" and now >= t_trace + trace[1]:
                traced_span.__exit__(None, None, None)
                trace[3]()
                self.tracing, state = False, "done"
            if now >= t_end and state != "on":
                break
            if self.mix["loop"] == "open":
                while t0 + self.draws[self.next].offset_s <= now:
                    d = self._take()
                    self._send(d, t_due=t0 + d.offset_s)
                if not self.live and not self.session.pending:
                    nxt = t0 + self.draws[self.next].offset_s
                    with TraceAnnotation("chipbench.wait"):
                        self.sleep(max(0.0, min(nxt, t_end) - self.clock()))
                    continue
            self._step()
        last = self.steps[-1].t1 if self.steps else t0
        self.window = (t0, max(t_end, last))
        return self.window
