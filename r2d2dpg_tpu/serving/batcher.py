"""Dynamic micro-batcher: coalesce concurrent act() calls into bucketed steps.

Podracer's TPU lesson (arxiv 2104.06272) applies to inference too: the chip
is efficient only at batch, so single-request policy steps waste it.  The
batcher coalesces whatever requests are in flight into ONE policy step,
padded up to a fixed bucket size so there is exactly one XLA compile per
bucket — never one per observed batch size.

Latency discipline: the first request of a batch starts a flush deadline
(``flush_ms``); the batch launches when the largest bucket fills OR the
deadline lapses, whichever is first.  An idle service adds at most one
deadline of latency to a lone request.

Admission control: the queue is bounded (``max_queue``).  ``submit`` on a
full queue fails IMMEDIATELY — the caller turns that into a ``SHED_QUEUE``
response code, not an exception, so overload degrades to fast explicit
rejections instead of unbounded queueing (the client can back off).

Ordering: at most one request per session rides in a batch — two
concurrent steps for one session would gather the same carry and race the
scatter-back.  Extras are held over (FIFO per session) for the next batch.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Deque, Dict, List, Optional, Sequence

import numpy as np

# Response codes live in utils/codes.py (shared with fleet ingest so the
# two admission layers cannot drift apart); re-exported here because they
# are part of this module's public surface.
from r2d2dpg_tpu.utils.codes import OK, SHED_QUEUE, SHED_SESSIONS, SHUTDOWN


@dataclasses.dataclass
class Request:
    """One pending act() call; doubles as its own future (event + slots)."""

    session_id: str
    obs: np.ndarray
    reset: bool
    enqueued_at: float
    _done: threading.Event = dataclasses.field(
        default_factory=threading.Event, repr=False
    )
    code: str = OK
    action: Optional[np.ndarray] = None
    params_step: int = -1
    latency_s: float = 0.0

    def finish(
        self,
        code: str,
        action: Optional[np.ndarray] = None,
        params_step: int = -1,
        *,
        clock=time.monotonic,
    ) -> None:
        self.code = code
        self.action = action
        self.params_step = params_step
        self.latency_s = clock() - self.enqueued_at
        self._done.set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)

    @property
    def done(self) -> bool:
        return self._done.is_set()


def bucket_for(n: int, bucket_sizes: Sequence[int]) -> int:
    """Smallest bucket >= n (bucket_sizes sorted ascending); n above the
    largest bucket is the caller's bug — the batcher never drains more than
    the largest bucket into one batch."""
    for b in bucket_sizes:
        if b >= n:
            return b
    raise ValueError(f"batch of {n} exceeds largest bucket {bucket_sizes[-1]}")


class MicroBatcher:
    """Bounded request queue + bucketed coalescing (host-side only).

    One consumer (the service worker thread) calls ``next_batch``; any
    number of producers call ``submit``.  The holdover deque keeps
    same-session extras strictly FIFO across batches.
    """

    def __init__(
        self,
        bucket_sizes: Sequence[int] = (1, 2, 4, 8, 16, 32),
        *,
        max_queue: int = 256,
        flush_ms: float = 5.0,
        clock=time.monotonic,
    ):
        sizes = sorted(set(int(b) for b in bucket_sizes))
        if not sizes or sizes[0] < 1:
            raise ValueError(f"bad bucket_sizes {bucket_sizes!r}")
        self.bucket_sizes = tuple(sizes)
        self.max_batch = sizes[-1]
        self.flush_s = flush_ms / 1000.0
        self.max_queue = max_queue
        self._clock = clock
        self._lock = threading.Lock()
        self._nonempty = threading.Condition(self._lock)
        self._queue: Deque[Request] = collections.deque()
        self._holdover: Deque[Request] = collections.deque()
        self._closed = False
        self.submitted = 0
        self.shed_queue_full = 0

    # -------------------------------------------------------------- producer
    def submit(self, req: Request) -> bool:
        """Enqueue; False (caller sheds) when the bounded queue is full."""
        with self._lock:
            if self._closed:
                return False
            # Holdover rides the same bound: it is queued work too.
            if len(self._queue) + len(self._holdover) >= self.max_queue:
                self.shed_queue_full += 1
                return False
            self._queue.append(req)
            self.submitted += 1
            self._nonempty.notify()
            return True

    # -------------------------------------------------------------- consumer
    def next_batch(self, poll_s: float = 0.05) -> List[Request]:
        """Block (up to ``poll_s``) for work, then coalesce one batch.

        Returns [] on timeout or close so the worker can run its
        between-batches duties (hot-reload poll, TTL sweep, health log) at
        least every ``poll_s`` even under zero traffic.
        """
        with self._nonempty:
            if not self._queue and not self._holdover:
                self._nonempty.wait(poll_s)
            if self._closed or (not self._queue and not self._holdover):
                return []
        # Flush window: give stragglers until the deadline to join, unless
        # the largest bucket is already full.
        deadline = self._clock() + self.flush_s
        while True:
            with self._lock:
                ready = len(self._holdover) + len(self._queue)
            if ready >= self.max_batch:
                break
            remaining = deadline - self._clock()
            if remaining <= 0:
                break
            time.sleep(min(remaining, 0.001))
        batch: List[Request] = []
        seen: set = set()
        kept: Deque[Request] = collections.deque()
        with self._lock:
            # Holdover first (strict per-session FIFO), then fresh queue.
            for source in (self._holdover, self._queue):
                while source and len(batch) < self.max_batch:
                    req = source.popleft()
                    if req.session_id in seen:
                        kept.append(req)
                        continue
                    seen.add(req.session_id)
                    batch.append(req)
            self._holdover = kept + self._holdover  # leftovers stay FIFO
        return batch

    def drain(self) -> List[Request]:
        """Close and return everything still queued (for SHUTDOWN replies)."""
        with self._lock:
            self._closed = True
            out = list(self._holdover) + list(self._queue)
            self._holdover.clear()
            self._queue.clear()
            self._nonempty.notify_all()
            return out

    @property
    def depth(self) -> int:
        with self._lock:
            return len(self._queue) + len(self._holdover)

    @property
    def closed(self) -> bool:
        with self._lock:
            return self._closed
