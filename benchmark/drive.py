"""Offering traffic to the node's lanes, and recording what comes back.

Two loops, chosen by the traffic mix's `loop`:

- closed: slot after slot, as fast as the node answers them. A request
  is offered only when its lane and the dispatcher's pending queue have
  room for it (read from the dispatcher's exported lane state), so the
  generator's own haste never causes a shed. The slot's first
  `lead_sets` sets go out first, and the rest once the workers have
  taken those, so that they ride a batch of their own, as a batch of the
  dispatcher's size trigger does when gossip trickles in. The next slot
  is offered once every request of the previous one has its verdict, so
  that what is offered before the window closes is answered within the
  minute the run waits for it. A request is due when it is offered.
- open: mainnet timing. Slots are `slot_s` apart and each request is due
  at its slot's start plus its offset; it is offered then, whatever the
  node is doing, and timed from when it was due.

Batchable requests go through `dispatcher.submit(sets, lane)`, which is
what the node's gossip validation calls (`verify_signature_sets(sets,
lane=...)` submits and then waits). A waiter thread per lane reads their
verdicts in submission order; the time each verdict was handed over is
taken where the dispatcher hands over a whole batch, so a batch that
finishes before an earlier one is not stamped late. Calls that are not
batchable (blocks, segments) go through
`dispatcher.verify_signature_sets(sets, batchable=False)` on a thread of
their own, as the chain's block import does.
"""

from __future__ import annotations

import queue
import threading
import time


class Record:
    """One row per request offered: what it was and what came back.
    `done` is the host time its verdict (or shed) was handed to the
    caller, set once the caller has it; `verdict` is True/False, or None
    with `err` "shed" or "raised"."""

    def __init__(self):
        self.kind, self.n_sets, self.sets = [], [], []
        self.due, self.offered, self.done = [], [], []
        self.verdict, self.err = [], []
        self.handed: dict[int, float] = {}  # row -> batch hand-over time
        self.lock = threading.Lock()

    def add(self, req, due: float) -> int:
        with self.lock:
            self.kind.append(req.kind)
            self.n_sets.append(len(req.sets))
            self.sets.append(req.sets)
            self.due.append(due)
            self.offered.append(None)
            self.done.append(None)
            self.verdict.append(None)
            self.err.append(None)
            return len(self.kind) - 1

    def finish(self, i: int, verdict, err=None) -> None:
        now = time.monotonic()
        with self.lock:
            self.verdict[i] = verdict
            self.err[i] = err
            self.done[i] = min(now, self.handed.pop(i, now))

    def hand_over(self, rows, t: float) -> None:
        """Rows whose batch handed its verdicts over at `t`."""
        with self.lock:
            for i in rows:
                if self.done[i] is None:
                    self.handed[i] = t
                else:
                    self.done[i] = min(self.done[i], t)

    def __len__(self) -> int:
        return len(self.kind)


class _BatchWatch:
    """When the lane dispatcher hands a batch's verdicts over: it answers
    every request of a batch at once, at the end of `_dispatch_batch`."""

    def __init__(self, dispatcher):
        self.driver = None
        dispatch = dispatcher._dispatch_batch

        def call(entries, *args):
            try:
                return dispatch(entries, *args)
            finally:
                if self.driver is not None:
                    self.driver._handed_over(entries, time.monotonic())
        dispatcher._dispatch_batch = call

    @classmethod
    def on(cls, dispatcher) -> "_BatchWatch":
        """The watch on `dispatcher`, installed once."""
        watch = getattr(dispatcher, "_bench_batch_watch", None)
        if watch is None:
            watch = dispatcher._bench_batch_watch = cls(dispatcher)
        return watch


class Driver:
    """Offers requests to a verifier stack and records their verdicts."""

    def __init__(self, dispatcher, pipeline, sets, shed_error):
        self.dispatcher = dispatcher
        self.pipeline = pipeline
        self.sets = sets  # SignatureSet per traffic set index
        self.shed_error = shed_error
        self.rec = Record()
        self._waiters: dict[str, queue.Queue] = {}
        self._threads: list[threading.Thread] = []
        self._calls: queue.Queue = queue.Queue()
        self._row: dict[int, int] = {}  # id of a request's first set -> row
        _BatchWatch.on(dispatcher).driver = self
        for i in range(2):  # block import's verify pool
            t = threading.Thread(target=self._call_loop, daemon=True,
                                 name=f"bench-block-{i}")
            t.start()
            self._threads.append(t)

    # -- offering -------------------------------------------------------------

    def offer(self, req, due: float) -> int:
        i = self.rec.add(req, due)
        sets = [self.sets[k] for k in req.sets]
        self.rec.offered[i] = time.monotonic()
        if not req.batchable:
            self._calls.put((i, sets))
            return i
        self._row[id(sets[0])] = i
        try:
            resolve = self.dispatcher.submit(sets, lane=req.lane)
        except self.shed_error:
            self.rec.finish(i, None, "shed")
            return i
        except Exception:  # noqa: BLE001 — a raised request is a failed one
            self.rec.finish(i, None, "raised")
            return i
        self._waiter(req.lane).put((i, resolve))
        return i

    def _handed_over(self, entries, t: float) -> None:
        rows = (self._row.get(id(e[0][0])) for e in entries if e[0])
        self.rec.hand_over([i for i in rows if i is not None], t)

    def _waiter(self, lane: str) -> queue.Queue:
        q = self._waiters.get(lane)
        if q is None:
            q = self._waiters[lane] = queue.Queue()
            t = threading.Thread(target=self._wait_loop, args=(q,),
                                 daemon=True, name=f"bench-wait-{lane}")
            t.start()
            self._threads.append(t)
        return q

    def _wait_loop(self, q: queue.Queue) -> None:
        while True:
            item = q.get()
            if item is None:
                return
            i, resolve = item
            try:
                self.rec.finish(i, bool(resolve()))
            except self.shed_error:
                self.rec.finish(i, None, "shed")
            except Exception:  # noqa: BLE001
                self.rec.finish(i, None, "raised")

    def _call_loop(self) -> None:
        while True:
            item = self._calls.get()
            if item is None:
                return
            i, sets = item
            try:
                self.rec.finish(i, bool(self.dispatcher.verify_signature_sets(
                    sets, batchable=False)))
            except self.shed_error:
                self.rec.finish(i, None, "shed")
            except Exception:  # noqa: BLE001
                self.rec.finish(i, None, "raised")

    # -- room in the queues (closed loop) -------------------------------------

    def _lanes(self) -> dict:
        return self.pipeline.lanes_snapshot()

    def wait_room(self, req) -> None:
        """Block until the dispatcher can queue `req` without shedding."""
        if not req.batchable:
            return
        n = len(req.sets)
        while True:
            s = self._lanes()
            lane = s["lanes"][req.lane]
            lane_ok = not lane["cap"] or lane["queued_sets"] + n <= lane["cap"]
            pend_ok = (not s["pending_cap"]
                       or s["pending_sets"] + n <= s["pending_cap"])
            if lane_ok and pend_ok:
                return
            time.sleep(0.002)

    def wait_taken(self, until: float) -> None:
        """Block until the workers have taken every queued set."""
        while self._lanes()["pending_sets"] and time.monotonic() < until:
            time.sleep(0.002)

    # -- loops ------------------------------------------------------------------

    def closed_slot(self, slot, close: float, lead: int = 0) -> list[int]:
        """Offer one slot as fast as the lanes take it, its first `lead`
        batchable sets apart; stops offering at `close`. Returns the rows
        offered."""
        rows, n = [], 0
        for req in slot:
            if req.batchable and n < lead <= n + len(req.sets) - 1:
                self.wait_taken(close)
            self.wait_room(req)
            now = time.monotonic()
            if now >= close:
                break
            rows.append(self.offer(req, now))
            if req.batchable:
                n += len(req.sets)
                if n == lead:
                    self.wait_taken(close)
        return rows

    def open_slots(self, slots, t0: float, slot_s: float, stop) -> None:
        """Offer `slots` at mainnet timing from `t0`; returns early once
        `stop` is set."""
        for k, slot in enumerate(slots):
            for req in slot:
                due = t0 + k * slot_s + req.offset_s
                while True:
                    if stop.is_set():
                        return
                    wait = due - time.monotonic()
                    if wait <= 0:
                        break
                    time.sleep(min(wait, 0.05))
                self.offer(req, due)

    def wait_done(self, ids, until: float) -> None:
        for i in ids:
            while self.rec.done[i] is None:
                if time.monotonic() >= until:
                    return
                time.sleep(0.005)

    def close(self) -> None:
        for q in self._waiters.values():
            q.put(None)
        for _ in range(2):
            self._calls.put(None)

    def join(self, timeout: float) -> list[str]:
        """Join the driver's threads; names of any still running."""
        end = time.monotonic() + timeout
        for t in self._threads:
            t.join(max(0.0, end - time.monotonic()))
        return [t.name for t in self._threads if t.is_alive()]
