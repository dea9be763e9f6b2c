"""Client side of a run: sends a plan's requests through
``AsyncEngine.submit`` and stamps every token as the client receives it.

All stamps are ``time.perf_counter()`` seconds taken in the engine's
``on_token`` callback, i.e. when the token reaches the caller.  One
driver thread sends; an open loop sends each request at its due time,
a closed loop sends a client's next request when its previous one has
delivered its last token.
"""

from __future__ import annotations

import dataclasses
import itertools
import queue
import threading
import time
from typing import List, Optional

import numpy as np

from bench.traffic import Plan, Req


@dataclasses.dataclass
class Record:
    req: Req
    max_new: int
    client: int = -1
    due: float = 0.0            # absolute; the closed loop's is its send
    sent: float = 0.0
    stamps: List[float] = dataclasses.field(default_factory=list)
    handle: object = None
    failed: bool = False

    @property
    def done(self) -> bool:
        return len(self.stamps) >= self.max_new

    @property
    def tokens(self) -> List[int]:
        return list(self.handle.tokens) if self.handle is not None else []


def _request(prompt, max_new: int):
    from repro.serving import Request
    from repro.serving.sampler import SamplingParams
    return Request(uid=0, prompt=np.asarray(prompt).tolist(),
                   sampling=SamplingParams(temperature=0.0, eos_id=None,
                                           max_new_tokens=int(max_new)))


class Driver:
    """Feeds ``plan`` to ``engine``; ``start`` returns at once, ``stop``
    ends sending.  ``records`` holds every request sent."""

    def __init__(self, engine, plan: Plan) -> None:
        self.engine = engine
        self.plan = plan
        self.records: List[Record] = []
        self._done: "queue.Queue[Record]" = queue.Queue()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.t0 = 0.0

    # -- sending -------------------------------------------------------
    def _send(self, req: Req, max_new: int, client: int, due: float):
        rec = Record(req, max_new, client=client, due=due)

        def on_token(_tok, rec=rec):
            rec.stamps.append(time.perf_counter())
            if len(rec.stamps) == rec.max_new:
                self._done.put(rec)

        rec.sent = time.perf_counter()
        if self.plan.loop == "closed":
            rec.due = rec.sent
        self.records.append(rec)
        rec.handle = self.engine.submit(_request(req.prompt, max_new),
                                        on_token=on_token)
        return rec

    def _open(self) -> None:
        for k in range(len(self.plan.due)):
            req = self.plan.request(k)
            due = self.t0 + req.due
            while not self._stop.is_set():
                wait = due - time.perf_counter()
                if wait <= 0:
                    break
                self._stop.wait(min(wait, 0.05))
            if self._stop.is_set():
                return
            self._send(req, req.max_new, -1, due)

    def _closed(self) -> None:
        reqs = map(self.plan.request, itertools.count())
        live = []
        for c, n in enumerate(self.plan.first_new):
            live.append(self._send(next(reqs), n, c, 0.0))
        while not self._stop.is_set():
            try:
                rec = self._done.get(timeout=0.2)
            except queue.Empty:
                rec = self._failed_one(live)
                if rec is None:
                    continue
            live.remove(rec)
            nxt = next(reqs)
            live.append(self._send(nxt, nxt.max_new, rec.client, 0.0))

    @staticmethod
    def _failed_one(live: List[Record]) -> Optional[Record]:
        for rec in live:
            if rec.handle is not None and rec.handle.state.value == "failed":
                rec.failed = True
                return rec
        return None

    def start(self) -> None:
        self.t0 = time.perf_counter()
        body = self._open if self.plan.loop == "open" else self._closed
        self._thread = threading.Thread(target=self._guard, args=(body,),
                                        name="bench-driver", daemon=True)
        self.error: Optional[BaseException] = None
        self._thread.start()

    def _guard(self, body) -> None:
        try:
            body()
        except BaseException as e:      # noqa: BLE001 — re-raised in stop
            self.error = e

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()
        if self.error is not None:
            raise RuntimeError("traffic driver failed") from self.error

    # -- waiting -------------------------------------------------------
    def wait_first_tokens(self, records: List[Record], timeout: float):
        """Block until every record has a first token or failed."""
        end = time.perf_counter() + timeout
        for rec in records:
            while not rec.stamps and time.perf_counter() < end:
                if rec.handle.state.value in ("failed", "cancelled"):
                    rec.failed = True
                    break
                time.sleep(0.01)
            if not rec.stamps:
                rec.failed = True


def serve_all(engine, prompts, max_new: int, timeout: float = 600.0):
    """Send every prompt at once and wait for all answers (warm-up)."""
    handles = [engine.submit(_request(p, max_new)) for p in prompts]
    return [engine.result(h, timeout=timeout) for h in handles]
