"""Fetch coalescing: device→host transfers AND shuffle wire batching.

Two batchers live here because they have the same shape — many small
logical fetches carried by one exchange, so a fixed per-exchange cost is
paid once:

- :class:`DeviceFetchBatcher` coalesces concurrent tasks'
  ``jax.device_get`` calls into one ``device_get``;
- :func:`coalesce_shuffle_fetches` groups a reduce's pending map-output
  queue per SOURCE ADDRESS so the ShuffleCopier pulls many small
  segments from one tracker in one ``get_map_outputs_batch`` frame
  (the small-segment regime is exactly where per-RPC overhead
  dominates the shuffle).

Device→host batching design notes:

One ``jax.device_get`` over many tasks' pytrees is one host
synchronization (a "roundtrip" below) instead of one per task. The
LocalJobRunner gets that from its windowed prelaunch
(tpu_runner.prelaunch_device_maps); this module is the equivalent for the
DISTRIBUTED runtime, where a tracker's TPU-slot threads run tasks
concurrently and each would otherwise issue its own. Whether the
coalescing pays on a given machine is for a measurement to say; the
``fetches``/``roundtrips``/``coalesced`` counters are what it reads.

Design: rotating leader, zero added latency. The first thread to fetch
becomes leader and issues its ``device_get`` immediately — no linger
sleep. Threads arriving while a roundtrip is in flight queue up; when
the leader finishes, one of the QUEUED threads becomes the next leader
and takes the whole queue as one batched ``device_get`` — the in-flight
roundtrip itself is the coalescing window. Each leader serves exactly
one batch (which always contains its own entry), so no thread is held
hostage doing other tasks' transfers after its own is done: a lone task
is never delayed, and N concurrent tasks converge to ~2 roundtrips
instead of N.

If a batched fetch fails (one task's device computation raised), the
leader retries each entry individually so the error lands on the task
that caused it — innocent tasks in the same batch must not fail.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable


def coalesce_shuffle_fetches(
        first_map: int, addr: str,
        work: "queue.Queue[tuple[float, int]]",
        addr_of: "Callable[[int], str]",
        ready_now: "Callable[[float, int], bool]",
        max_segments: int) -> "list[int]":
    """Drain the copier's pending queue for more maps served by the
    same source as ``first_map`` — the members of one batched fetch.

    One bounded pass over the queue's current content (``qsize`` at
    entry — entries other workers push concurrently are next round's
    problem): maps that are ready (``ready_now(ready_at, m)``, i.e. no
    pending hold-off or penalty) and resolve to ``addr`` join the
    batch; everything else rotates back with its stamp intact. Always
    returns at least ``[first_map]``, so the caller degrades to a
    plain single fetch when nothing coalesces."""
    members = [first_map]
    if max_segments <= 1:
        return members
    putback: "list[tuple[float, int]]" = []
    scan = work.qsize()
    while scan > 0 and len(members) < max_segments:
        scan -= 1
        try:
            item = work.get_nowait()
        except queue.Empty:
            break
        # 2-tuple (ready, m) or the size-priority 3-tuple
        # (ready, -bytes, m): readiness first, map index last
        ready, m = item[0], item[-1]
        if ready_now(ready, m) and addr_of(m) == addr:
            members.append(m)
        else:
            putback.append(item)
    for item in putback:
        work.put(item)
    return members


class DeviceFetchBatcher:
    def __init__(self) -> None:
        self._cond = threading.Condition()
        self._pending: "list[_Slot]" = []
        self._leader_running = False
        #: observability: how many device_get roundtrips vs fetch calls
        self.roundtrips = 0
        self.fetches = 0
        self.batched = 0

    def fetch(self, tree: Any) -> Any:
        """Transfer one pytree of jax.Arrays to host, coalescing with
        concurrent callers. Returns the host pytree; re-raises the
        caller's own device error."""
        slot = _Slot(tree)
        with self._cond:
            self.fetches += 1
            self._pending.append(slot)
            while not slot.done and self._leader_running:
                self._cond.wait()
            if slot.done:
                # a previous leader's batch carried this slot
                if slot.error is not None:
                    raise slot.error
                return slot.result
            # become leader for exactly one batch — which includes this
            # slot, so leading never outlives the caller's own work
            self._leader_running = True
            batch = self._pending
            self._pending = []
            self.roundtrips += 1
            self.batched += len(batch) - 1
        try:
            self._transfer(batch)
        finally:
            with self._cond:
                self._leader_running = False
                self._cond.notify_all()
        if slot.error is not None:
            raise slot.error
        return slot.result

    def _transfer(self, batch: "list[_Slot]") -> None:
        import jax
        try:
            results = jax.device_get([s.tree for s in batch])
            for s, r in zip(batch, results):
                s.result = r
                s.fulfilled = True
        except Exception:  # noqa: BLE001 — isolate the failing entry
            for s in batch:
                try:
                    s.result = jax.device_get(s.tree)
                    s.fulfilled = True
                except Exception as e:  # noqa: BLE001
                    s.error = e
                with self._cond:
                    self.roundtrips += 1
        finally:
            for s in batch:
                if not s.fulfilled and s.error is None:
                    # a BaseException (KeyboardInterrupt, SystemExit)
                    # escaped both paths — batch-mates must see a real
                    # failure, never a silent None pytree
                    s.error = RuntimeError(
                        "batched device fetch aborted before this "
                        "entry transferred")
                s.done = True


class _Slot:
    __slots__ = ("tree", "result", "error", "done", "fulfilled")

    def __init__(self, tree: Any) -> None:
        self.tree = tree
        self.result = None
        self.error: "Exception | None" = None
        self.done = False
        self.fulfilled = False


_shared = DeviceFetchBatcher()


def shared_batcher() -> DeviceFetchBatcher:
    """The process-wide batcher (one device runtime, one queue)."""
    return _shared
