"""Instrumented, rank-ordered locking — contention as a first-class
distribution, deadlocks as assertion failures.

The JobTracker began as one process behind one RLock; every heartbeat,
completion-event poll, and status page serialized on it. The reference
never measured that (its global synchronized heartbeat monitor was a
known scaling wall nobody could see coming — SURVEY.md §3.2); here
every master lock is wrapped so wait time (how long callers queue) and
hold time (how long the winner keeps everyone else out) land in
histograms (``jt_lock_wait_seconds{lock=...}`` /
``jt_lock_hold_seconds{lock=...}``). Wait p99 climbing while hold p99
stays flat = more contenders; both climbing = the work under the lock
grew. ``tpumr simulate`` prints both p99s for a simulated fleet.

Since the lock decomposition (PR 8) the master runs on a fixed set of
lock classes with a fixed acquisition order, ascending by rank (the
``namespace*`` classes are the NameNode's — a separate process,
slotted into the one table so tooling sees every ranked lock)::

    tracker-beat(5) -> scheduler(10) -> pipeline(15) -> global(20)
        -> namespace(25) -> namespace-stripe(26) -> namespace-blocks(27)
        -> trackers(30) -> job(40)

The NameNode's three classes mirror the master's decomposition: the
``namespace`` global (25) is held only for cross-stripe structural
ops (rename/delete on shallow paths, fsck, checkpoints), the
``namespace-stripe`` stripes (26) partition the path tree so
same-rank sorted-index multi-acquisition is legal, and
``namespace-blocks`` (27) guards the block/datanode plane (locations,
heartbeats, leases) in short critical sections that never journal.

The ``pipeline`` rank (the DAG engine's state lock) sits below
``global`` because recording a stage submission and reading member-job
outcomes happen while the engine plans — but every BLOCKING part of a
stage submission (split computation, conf hooks, submit_job's history
write) runs outside it: pipeline advancement lives in the heartbeat's
deferred phase, off the fast path, and must stay there.

A thread may acquire a lock only when every lock it already holds has a
rank <= the new lock's (same-lock re-entrancy always allowed). The one
rule worth memorizing: **scheduler -> job, never the reverse** — the
scheduler pass obtains tasks under per-job locks, so a job-lock holder
calling back into the scheduler would deadlock the control plane. The
order is asserted in debug mode: violations raise ``AssertionError``
with both lock names. ``python -O`` or ``TPUMR_LOCK_ORDER_CHECK=0``
disables the check (the bookkeeping is a thread-local list append/pop
per outermost acquire — cheap, but not free).
"""

from __future__ import annotations

import os
import threading
import time
import weakref
from typing import Any

#: canonical lock ranks (ascending = legal acquisition order). The
#: numbers are spaced so a future lock class can slot between tiers.
RANK_TRACKER_BEAT = 5    # one tracker's heartbeat processing
RANK_SCHEDULER = 10      # scheduler passes (before_heartbeat / assign)
RANK_PIPELINE = 15       # DAG engine state (PipelineInProgress tables)
RANK_GLOBAL = 20         # job table, commit grants, admin swaps
RANK_NAMESPACE = 25      # the NameNode's structural/global lock (DFS
#                          control plane; its own process — co-held
#                          with no master lock today, ranked so the
#                          analyzer and /threads see it like any
#                          master class)
RANK_NAMESPACE_STRIPE = 26  # NameNode path-tree stripes (acquired in
#                          ascending stripe-index order; equal-rank
#                          multi-acquisition is legal by design)
RANK_NAMESPACE_BLOCKS = 27  # NameNode block/datanode plane (locations,
#                          heartbeats, leases, pending commands) —
#                          short sections, never journals under it
RANK_TRACKERS = 30       # tracker registry stripes
RANK_JOB = 40            # one JobInProgress's task bookkeeping

_ORDER_NAMES = "tracker-beat(5) -> scheduler(10) -> pipeline(15) " \
               "-> global(20) -> namespace(25) -> namespace-stripe(26) " \
               "-> namespace-blocks(27) -> trackers(30) -> job(40)"

#: debug-mode ordering assertion: on under ``__debug__`` (plain
#: ``python``), off under ``python -O`` or TPUMR_LOCK_ORDER_CHECK=0
ORDER_CHECK = __debug__ and os.environ.get(
    "TPUMR_LOCK_ORDER_CHECK", "1").lower() not in ("0", "false", "no")

_held = threading.local()


def _held_stack() -> "list[InstrumentedRLock]":
    s = getattr(_held, "stack", None)
    if s is None:
        s = _held.stack = []
    return s


#: every NAMED InstrumentedRLock self-registers here so live-state pages
#: (/threads) and incident bundles can enumerate the master's lock
#: classes without threading a list through every constructor. Weak so
#: per-job locks die with their JobInProgress.
_named_locks: "weakref.WeakSet[InstrumentedRLock]" = weakref.WeakSet()


def lock_table(now: "float | None" = None) -> "list[dict[str, Any]]":
    """Live holder/waiter rows for every named instrumented lock, sorted
    by (rank, name) — the "is it deadlocked right now" view. Lock-free
    read of racy-by-design fields: a row may be a few microseconds
    stale, which is exactly good enough for a human or a postmortem
    bundle (the alternative — taking each lock to report on it — would
    make the reporter a contender)."""
    if now is None:
        now = time.monotonic()
    rows = []
    for lk in list(_named_locks):
        holder = lk._holder          # racy read: grab one reference
        waiters = list(lk._waiters.values())
        rows.append({
            "name": lk.name, "rank": lk.rank,
            "holder": holder[0] if holder else None,
            "held_for_s": round(now - holder[1], 6) if holder else None,
            "waiters": sorted(w[0] for w in waiters),
            "longest_wait_s": round(
                max((now - w[1] for w in waiters), default=0.0), 6),
        })
    rows.sort(key=lambda r: (r["rank"], r["name"]))
    return rows


class InstrumentedRLock:
    """A re-entrant lock recording acquisition wait and outermost hold
    durations into histograms, optionally participating in the master's
    rank-ordered deadlock assertion.

    Drop-in for ``threading.RLock`` at the ``acquire``/``release``/
    context-manager surface. Only the OUTERMOST acquire measures wait
    (a re-entrant acquire by the owner never blocks) and only the
    outermost release records hold — nested ``with`` blocks must not
    turn one hold into N overlapping observations. Histograms may be
    bound after construction (:meth:`bind`) so the lock can exist
    before the metrics registry does.

    Named locks additionally publish LIVE state — who holds me, since
    when, who is queued — via :func:`lock_table` (/threads, incident
    bundles). The bookkeeping is deliberately lock-free: the holder
    field is one GIL-atomic tuple store per outermost acquire/release,
    and only a caller that LOST the uncontended try-acquire ever
    touches the waiter dict, so the uncontended path costs two clock
    reads and never a second lock.
    """

    def __init__(self, wait_hist: Any = None, hold_hist: Any = None,
                 *, name: str = "", rank: int = 0) -> None:
        self._lock = threading.RLock()
        self._wait = wait_hist
        self._hold = hold_hist
        self.name = name
        self.rank = int(rank)
        self._tl = threading.local()
        #: (thread name, monotonic since) of the current outermost
        #: holder, or None — racy by design, read by lock_table()
        self._holder: "tuple[str, float] | None" = None
        #: ident -> (thread name, monotonic since) of blocked acquirers
        self._waiters: "dict[int, tuple[str, float]]" = {}
        if name:
            _named_locks.add(self)

    def bind(self, wait_hist: Any, hold_hist: Any) -> "InstrumentedRLock":
        self._wait = wait_hist
        self._hold = hold_hist
        return self

    def _assert_order(self) -> None:
        stack = _held_stack()
        if not stack:
            return
        # acquisition ranks are enforced ascending, so the top of the
        # held stack is the max held rank
        top = stack[-1]
        if top.rank > self.rank:
            raise AssertionError(
                f"lock-order violation: acquiring "
                f"{self.name or 'lock'} (rank {self.rank}) while "
                f"holding {top.name or 'lock'} (rank {top.rank}); "
                f"the master's order is {_ORDER_NAMES}")

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        depth = getattr(self._tl, "depth", 0)
        if depth:
            # re-entrant: the owner never waits, the hold already runs
            ok = self._lock.acquire(blocking, timeout)
            if ok:
                self._tl.depth = depth + 1
            return ok
        if ORDER_CHECK and self.rank:
            self._assert_order()
        t0 = time.monotonic()
        # uncontended try first: only a caller that LOSES this race
        # registers in the waiter table, so the fast path never mutates
        # shared state beyond the underlying lock itself
        ok = self._lock.acquire(False)
        if not ok:
            if not blocking:
                return False
            ident = threading.get_ident()
            self._waiters[ident] = (threading.current_thread().name, t0)
            try:
                ok = self._lock.acquire(True, timeout)
            finally:
                self._waiters.pop(ident, None)
            if not ok:
                return False
        now = time.monotonic()
        if self._wait is not None:
            self._wait.observe(now - t0)
        self._tl.depth = 1
        self._tl.acquired_at = now
        self._holder = (threading.current_thread().name, now)
        if ORDER_CHECK and self.rank:
            _held_stack().append(self)
        return True

    def release(self) -> None:
        depth = getattr(self._tl, "depth", 0)
        if depth == 1:
            self._holder = None
            if self._hold is not None:
                t0 = getattr(self._tl, "acquired_at", None)
                if t0 is not None:
                    self._hold.observe(time.monotonic() - t0)
            if ORDER_CHECK and self.rank:
                stack = _held_stack()
                if stack and stack[-1] is self:
                    stack.pop()
                else:  # released out of acquisition order — still legal
                    try:
                        stack.remove(self)
                    except ValueError:
                        pass
        if depth:
            self._tl.depth = depth - 1
        self._lock.release()

    def __enter__(self) -> "InstrumentedRLock":
        self.acquire()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.release()
