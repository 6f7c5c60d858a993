"""NodeRunner — the per-host worker daemon.

≈ ``org.apache.hadoop.mapred.TaskTracker`` (reference: src/mapred/org/
apache/hadoop/mapred/TaskTracker.java, 4636 LoC). Reproduced contracts:

- the heartbeat loop (offerService :1706-1775 / transmitHeartBeat
  :1789-1860): status with BOTH pool maxima, ``ask_for_new_task`` when
  either pool has room (:1841-1844), response-id resend protocol;
- **dual slot pools** (:331-333, :1427-1432): separate CPU and TPU map slot
  maxima; the launcher gates each task on the pool matching its
  ``run_on_tpu`` flag (TaskLauncher :2502-2628) and frees the right pool on
  completion/kill (:3401-3402);
- per-device accounting: free TPU device ids derived from running task
  statuses (availableGPUDevices, TaskTrackerStatus.java:536-550) and
  shipped in every heartbeat;
- the shuffle server role (MapOutputServlet :4050): map outputs are served
  per (job, map, partition) over the tracker's RPC port;
- task execution in-process on threads by default (the reference forks
  child JVMs via TaskRunner/JvmManager — an explicit re-design: kernels
  must share the host process to share the JAX runtime and HBM split
  cache). ``tpumr.task.isolation=process`` opts CPU map/reduce attempts
  into real child processes (process_runner.py ≈ TaskRunner/JvmManager,
  child.py ≈ Child.java) talking back over the umbilical_* RPC methods
  (≈ TaskUmbilicalProtocol), optionally launched through the native
  setuid task-controller.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import threading
import time
import traceback
from typing import Any

from tpumr.core.counters import Counters
from tpumr.io import compress
from tpumr.io.fdcache import FdCache
from tpumr.core import confkeys
from tpumr.io import ifile
from tpumr.ipc.rpc import RpcClient, RpcClientPool, RpcServer
from tpumr.mapred.api import Reporter, TaskKilledError
from tpumr.mapred.ids import TaskAttemptID, TaskID
from tpumr.mapred.jobconf import JobConf
from tpumr.mapred.jobtracker import PROTOCOL_VERSION
from tpumr.mapred.map_task import run_map_task
from tpumr.mapred.output_formats import FileOutputCommitter
from tpumr.mapred.reduce_task import run_reduce_task
from tpumr.mapred.task import Task, TaskPhase, TaskState, TaskStatus


def _resolvable(host: str) -> bool:
    import socket
    try:
        socket.getaddrinfo(host, None)
        return True
    except OSError:
        return False


class MapLocator:
    """Map-output location resolution ≈ the ReduceCopier's polling of
    TaskCompletionEvents (ReduceTask.java:659 fetch loop). ``events_fn
    (cursor) -> [event]`` is the master's incremental completion-event
    feed (called directly by the tracker, via the umbilical by isolated
    child processes). Calling ``locate(map_index)`` returns a
    :class:`_ShuffleTarget` bound to the serving tracker's shuffle RPC —
    RpcClient-shaped for one-shot ``.call``, plus ``lease``/``release``
    over the locator's shared connection pool for pipelined streams.

    The completion-event feed is APPEND-ONLY: a map output withdrawn by
    the master (lost tracker, too-many-fetch-failures re-execution)
    arrives as an OBSOLETE-status event that evicts the cached location;
    ``invalidate`` lets the ShuffleCopier drop a location it observed
    dead itself, so the next locate() round blocks until the re-run
    map's fresh completion event supplies the new address — mid-shuffle,
    without restarting the copy phase."""

    def __init__(self, events_fn: Any, secret: bytes | None,
                 poll_s: float = 0.2, timeout_s: float = 600.0,
                 scope: "str | None" = None,
                 conns_per_target: int = 2) -> None:
        self._events_fn = events_fn
        self._secret = secret
        self._poll_s = poll_s
        self._timeout_s = timeout_s
        self._scope = scope
        #: liveness seam for the hung-task reaper: invoked once per poll
        #: iteration while a caller blocks waiting for a map location
        #: (the ShuffleCopier wires the reduce Reporter's keepalive here
        #: — a reduce stalled on a not-yet-rerun map is waiting, not
        #: hung, and must not be reaped at mapred.task.timeout)
        self.on_wait: "Any | None" = None
        self._events: dict[int, dict] = {}
        #: invalidated-but-not-withdrawn locations: the feed is cursor-
        #: based (an old SUCCEEDED event is never re-sent), so a
        #: location WE dropped must stay available as a fallback until
        #: the master actually replaces or withdraws it — otherwise one
        #: reducer's asymmetric fetch fault would strand it blocking for
        #: a re-run the master never schedules
        self._stale: dict[int, dict] = {}
        self._seen = 0
        #: consecutive polls that surfaced nothing while a caller was
        #: starving — past a threshold the cursor rewinds to 0 (see
        #: __call__): a cursor minted before a master restart can sit
        #: past the resubmitted job's shorter feed, hiding recovered
        #: events; re-folding from 0 is idempotent
        self._empty_polls = 0
        # shared per-target connection pool: parallel.copies fetcher
        # threads multiplex pipelined fetches over conns_per_target
        # sockets per tracker, reused across fetches and across the
        # penalty-box recovery path — not one serialized client per
        # (addr, thread) opened anew by every fetcher
        self.pool = RpcClientPool(
            lambda host, port: RpcClient(host, port, secret=secret,
                                         scope=scope),
            conns_per_target=conns_per_target)
        # the ShuffleCopier drives locate() from parallel fetcher
        # threads. cache_lock guards the event cache/cursor; poll_lock
        # serializes the events_fn RPC OUTSIDE cache_lock, so threads
        # whose map is already cached never wait behind a network poll
        # — and the cursor can't double-advance (that silently skips
        # events forever).
        self._cache_lock = threading.Lock()
        self._poll_lock = threading.Lock()

    def _cached(self, map_index: int) -> bool:
        with self._cache_lock:
            return map_index in self._events

    def _fold(self, fresh: "list[dict]") -> None:
        """Apply one batch of completion events to the location cache.
        Caller holds ``_cache_lock``."""
        self._seen += len(fresh)
        for e in fresh:
            idx = e["map_index"]
            if e.get("status") == "OBSOLETE":
                cur = self._events.get(idx)
                if cur is not None and cur["attempt_id"] == e["attempt_id"]:
                    del self._events[idx]
                st = self._stale.get(idx)
                if st is not None and st["attempt_id"] == e["attempt_id"]:
                    # genuinely withdrawn: the fallback dies too — now
                    # we really do block for the re-run's fresh event
                    del self._stale[idx]
            else:
                self._events[idx] = e
                self._stale.pop(idx, None)

    def _entry(self, map_index: int) -> "dict | None":
        """Caller holds ``_cache_lock``."""
        e = self._events.get(map_index)
        return e if e is not None else self._stale.get(map_index)

    def attempt_of(self, map_index: int) -> str:
        """The map attempt whose output the (possibly stale) cached
        location serves — what a fetch-failure report names to the
        master."""
        with self._cache_lock:
            e = self._entry(map_index)
            return e["attempt_id"] if e is not None else ""

    def addr_of(self, map_index: int) -> str:
        with self._cache_lock:
            e = self._entry(map_index)
            return e["shuffle_addr"] if e is not None else ""

    def size_of(self, map_index: int) -> int:
        """Total map-output bytes the cached completion event advertised
        (0 when unknown) — the ShuffleCopier's largest-first fetch
        ordering key. Advisory only: a 0 never blocks a fetch."""
        with self._cache_lock:
            e = self._entry(map_index)
            return int(e.get("output_bytes", 0) or 0) if e is not None else 0

    def invalidate(self, map_index: int) -> None:
        """Demote the cached location to a fallback: the next locate()
        round polls for a fresh event first, but while the master keeps
        the output live (other reducers may fetch it fine — the fault
        could be ours) the known location keeps serving retries."""
        with self._cache_lock:
            e = self._events.pop(map_index, None)
            if e is not None:
                self._stale[map_index] = e

    def __call__(self, map_index: int) -> "_ShuffleTarget":
        return _ShuffleTarget(self.pool, self.resolve(map_index))

    def resolve(self, map_index: int) -> str:
        """Block until the map's serving address is known and return it
        ("host:port") — resolution WITHOUT binding a connection, so a
        streaming fetch resolves once per segment and a mid-fetch
        OBSOLETE fold can't flip a healthy in-flight stream."""
        # monotonic deadline: an NTP step mid-shuffle must neither fire
        # the timeout early nor stall it past the configured bound
        deadline = time.monotonic() + self._timeout_s
        while True:
            with self._cache_lock:
                # event read under the SAME lock hold that checked it: a
                # concurrent _fold of an OBSOLETE withdrawal between a
                # cached() check and a later read would KeyError
                e = self._events.get(map_index)
                if e is not None:
                    addr = e["shuffle_addr"]
                    break
            with self._poll_lock:
                if self._cached(map_index):  # another poller just fetched
                    continue
                try:
                    fresh = self._events_fn(self._seen)
                except Exception:  # noqa: BLE001 — master briefly down
                    # (restarting): a reduce mid-shuffle survives the
                    # control-plane outage by simply polling again; the
                    # deadline below bounds how long, and on_wait keeps
                    # the reaper informed that we are waiting, not hung
                    fresh = []
                with self._cache_lock:
                    self._fold(fresh)
                if fresh:
                    self._empty_polls = 0
            if self._cached(map_index):
                continue
            self._empty_polls += 1
            if self._empty_polls >= 25:
                # starving on an empty feed: the cursor may predate a
                # master restart (the recovered feed restarted at 0) —
                # rewind and re-fold everything (idempotent)
                self._empty_polls = 0
                with self._cache_lock:
                    self._seen = 0
            with self._cache_lock:
                stale = self._stale.pop(map_index, None)
                if stale is not None:
                    # nothing fresh after a poll: the invalidated
                    # location is still the best known — reinstate it
                    # (retries keep hammering it through the penalty
                    # box) until the master replaces or withdraws it
                    self._events[map_index] = stale
                    continue
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"map {map_index} output never became available")
            if self.on_wait is not None:
                self.on_wait()
            time.sleep(self._poll_s)
        return addr

    def close(self) -> None:
        self.pool.close()


class _ShuffleTarget:
    """One resolved shuffle target over the locator's shared connection
    pool. ``call`` leases a pooled connection for exactly one RPC (the
    legacy per-call sites: dense fetch, handoff probe); ``lease`` hands
    the caller an exclusive RpcClient for a pipelined call_begin/
    call_finish window, paired with ``release``. The address is fixed at
    construction — re-resolution is the LOCATOR's job, on failure."""

    __slots__ = ("pool", "addr")

    def __init__(self, pool: RpcClientPool, addr: str) -> None:
        self.pool = pool
        self.addr = addr

    @property
    def host(self) -> str:
        return self.addr.rsplit(":", 1)[0]

    @property
    def port(self) -> int:
        return int(self.addr.rsplit(":", 1)[1])

    def call(self, method: str, *params: Any) -> Any:
        cli = self.pool.acquire(self.addr)
        dead = False
        try:
            return cli.call(method, *params)
        except (ConnectionError, OSError):
            dead = True
            raise
        finally:
            self.pool.release(self.addr, cli, dead=dead)

    def lease(self) -> RpcClient:
        return self.pool.acquire(self.addr)

    def release(self, cli: RpcClient, dead: bool = False) -> None:
        self.pool.release(self.addr, cli, dead=dead)


def make_map_locator(events_fn: Any, secret: bytes | None,
                     poll_s: float = 0.2, timeout_s: float = 600.0,
                     scope: "str | None" = None,
                     conns_per_target: int = 2) -> MapLocator:
    """Factory kept for the existing call sites (tracker + child)."""
    return MapLocator(events_fn, secret, poll_s=poll_s,
                      timeout_s=timeout_s, scope=scope,
                      conns_per_target=conns_per_target)


#: PR 13's shuffle-serving fd LRU, since promoted to the shared
#: tpumr.io.fdcache engine (the datanode block read path uses the same
#: cache); the name is kept for the existing shuffle call sites.
SpillFdCache = FdCache


#: wire compression for served chunks moved to tpumr.io.compress
#: (shared with the datanode); aliases keep the shuffle call sites and
#: tests unchanged
_WIRE_MIN_BYTES = compress.WIRE_MIN_BYTES
_wire_compress = compress.wire_compress


def serve_chunk(fds: SpillFdCache, path: str, index: dict,
                partition: int, offset: int, max_bytes: int,
                max_chunk: int, wire: str = "none") -> dict:
    """One bounded chunk of one partition segment, pread off the fd
    cache. The chunk length is DETERMINISTIC — ``min(max_bytes,
    max_chunk, remaining)`` in payload space — which is what lets a
    pipelining client schedule follow-up offsets before their
    predecessors arrive. Shared by the tracker's RPC methods and the
    bench/test serving stubs."""
    off, raw_len, part_len = index["partitions"][partition]
    payload_len = part_len - 4          # minus the length prefix
    offset = max(0, int(offset))
    n = max(0, min(int(max_bytes), max_chunk, payload_len - offset))
    data = fds.pread(path, n, off + 4 + offset)
    out = {"data": data, "total": payload_len, "raw": raw_len,
           "codec": index.get("codec", "none"), "n": n}
    _wire_compress(out, wire)
    return out


def serve_batch(fds: SpillFdCache, lookup: Any, partition: int,
                map_indexes: "list[int]", max_bytes_each: int,
                max_total_bytes: int, max_chunk: int,
                wire: str = "none") -> "list[dict]":
    """Many small segments from ONE tracker in ONE response frame — the
    small-segment regime where per-call overhead dominates the copy
    phase. ``lookup(map_index) -> (path, index)`` raises to fail THAT
    entry alone: the error rides back as ``{"map_index", "error"}`` so
    one lost map triggers the fetch-failure protocol for exactly that
    map while the rest of the batch lands. The total-bytes budget stops
    the batch early (≥1 entry always served; omitted indexes are simply
    absent and the copier requeues them); an entry bigger than its
    per-entry cap arrives as a prefix the copier continues chunked."""
    out: "list[dict]" = []
    budget = max(1, int(max_total_bytes))
    for m in map_indexes:
        if budget <= 0 and out:
            break
        try:
            path, index = lookup(m)
            ent = serve_chunk(fds, path, index, partition, 0,
                              min(int(max_bytes_each), budget)
                              if out else int(max_bytes_each),
                              max_chunk, wire)
        except Exception as e:  # noqa: BLE001 — per-entry failure seam
            out.append({"map_index": m, "error": f"{type(e).__name__}: {e}"})
            continue
        ent["map_index"] = m
        budget -= len(ent["data"])
        out.append(ent)
    return out


class NodeRunner:
    def __init__(self, master_host: str, master_port: int, conf: JobConf,
                 name: str | None = None, host: str = "127.0.0.1",
                 n_tpu_devices: int | None = None,
                 bind_host: str | None = None) -> None:
        self.conf = conf
        #: locality name reported to the scheduler (may be a fake topology
        #: name ≈ MiniMRCluster hosts ctor args)
        self.host = host
        #: routable address the RPC/shuffle server binds and advertises
        self.bind_host = bind_host or ("127.0.0.1" if host and not
                                       _resolvable(host) else host)
        self.name = name or f"tracker_{host}_{id(self) & 0xffff}"
        from tpumr.security import rpc_secret
        self._rpc_secret = rpc_secret(conf)
        # control-plane partition tolerance: the master channel retries
        # transport failures with capped jittered backoff before giving
        # up (tpumr.rpc.client.*); the heartbeat loop's lost-master
        # state handles outages longer than one call's retry budget
        self.master = RpcClient(
            master_host, master_port, secret=self._rpc_secret,
            retries=confkeys.get_int(conf, "tpumr.rpc.client.retries"),
            backoff_ms=confkeys.get_int(conf, "tpumr.rpc.client.backoff.ms"))
        self.master.fi_conf = conf   # rpc.drop/delay/reset chaos seams
        remote_version = self.master.call("get_protocol_version")
        if remote_version != PROTOCOL_VERSION:
            raise RuntimeError(f"master protocol {remote_version} != "
                               f"{PROTOCOL_VERSION}")

        # rack resolved tracker-side at startup (outside any master lock —
        # the scheduler must never exec the topology script mid-heartbeat)
        from tpumr.net import resolver_from_conf
        self.rack = resolver_from_conf(conf)(self.host)

        self.max_cpu_map_slots = conf.max_cpu_map_slots
        self.max_tpu_map_slots = conf.max_tpu_map_slots
        self.max_reduce_slots = conf.max_reduce_slots
        self.n_tpu_devices = (n_tpu_devices if n_tpu_devices is not None
                              else max(1, self.max_tpu_map_slots))
        #: platform/kind/count of the devices behind the TPU slots, set
        #: by start() (None on a tracker with no TPU slots)
        self.tpu_devices: "dict | None" = None
        self.heartbeat_s = conf.get_int("tpumr.heartbeat.interval.ms", 1000) / 1000.0
        #: this tracker's own configured cadence: an out-of-band beat
        #: goes out only while the master instructs no slower one
        self._heartbeat_floor_s = self.heartbeat_s

        self.lock = threading.RLock()
        self.running: dict[str, TaskStatus] = {}      # attempt -> status
        self.running_tasks: dict[str, Task] = {}
        self._kill_requested: set[str] = set()
        self.map_outputs: dict[tuple[str, int], tuple[str, dict]] = {}
        self.job_confs: dict[str, JobConf] = {}
        # ≈ mapred.local.dir: tracker-local scratch root — when set it must
        # match the task-controller's allowed.local.dirs policy
        local_base = conf.get("mapred.local.dir")
        if local_base:
            os.makedirs(local_base, exist_ok=True)
        self.local_root = tempfile.mkdtemp(prefix=f"tpumr-{self.name}-",
                                           dir=local_base or None)
        self._response_id = 0
        self._initial_contact = True
        # heartbeat delta encoding (tpumr.heartbeat.delta, default on):
        # full status on (re)contact, change-only beats afterwards — an
        # idle tracker's beat is a near-empty dict on the wire
        from tpumr.mapred.heartbeat import HeartbeatEncoder
        self._hb_encoder = HeartbeatEncoder(
            confkeys.get_boolean(conf, "tpumr.heartbeat.delta"))
        #: the metrics piggyback rides at most this often (cumulative
        #: state — freshness is a seconds-scale concern, and building
        #: the typed snapshot every beat is pure overhead on fast-
        #: heartbeat clusters; 0 = every beat, the default, where the
        #: delta encoder still drops piggybacks that didn't change)
        self._piggyback_interval_s = conf.get_int(
            "tpumr.metrics.piggyback.interval.ms", 0) / 1000.0
        self._piggyback_last = 0.0
        #: RUNNING-status report-rate limit (delta beats only): a status
        #: whose state/phase didn't change rides the wire at most once
        #: per this interval — continuous progress movement otherwise
        #: re-ships (and the master re-folds) every running task on
        #: every beat. State transitions and terminal statuses always
        #: ship. 0 = every beat. The master's believed-running set
        #: tolerates the gaps (delta beats add/remove incrementally).
        self._status_interval_s = conf.get_int(
            "tpumr.task.status.report.interval.ms", 1000) / 1000.0
        #: aid -> (state, phase, monotonic of last ship)
        self._status_shipped: "dict[str, tuple]" = {}
        self._stop = threading.Event()
        #: set when an attempt goes terminal (_slot_freed): the heartbeat
        #: loop sleeps on it, so a freed slot is reported and refilled
        #: now and not on the next tick
        self._wake = threading.Event()
        # --- lost-master state (master restart survival) ---
        #: True while the master is unreachable at the TRANSPORT level
        #: (connect refused / reset / timeout) — in-flight tasks keep
        #: running, heartbeats retry with capped jittered backoff, and
        #: on re-contact the master ADOPTS the full status instead of
        #: answering reinit. Application-level RPC errors (the master
        #: answered, unhappily) never enter this state.
        self.master_unreachable = False
        self._master_failures = 0
        self._last_master_contact = time.monotonic()
        self._lost_master_backoff_max_s = conf.get_int(
            "tpumr.heartbeat.lostmaster.backoff.max.ms", 15_000) / 1000.0
        #: old job id -> resubmitted id, taught by a recovered master's
        #: recover_job actions: future map-output registrations under
        #: the old id are stored under the new one (existing entries
        #: are re-keyed on receipt), so NEW-id reducers can fetch
        #: outputs produced before the restart
        self._job_rebinds: dict[str, str] = {}
        #: upstream job id -> shared HandoffSource for streamed-
        #: pipeline downstream maps on this tracker (one MapLocator per
        #: upstream stage, every map task of the stage shares it)
        self._handoff_sources: dict[str, Any] = {}
        # per-pool gating ≈ TaskLauncher's numCPUFreeSlots/numGPUFreeSlots
        # wait loops (TaskTracker.java:2502-2628): even if the master ever
        # over-assigns, a task blocks until ITS pool has a slot
        self._cpu_sem = threading.Semaphore(max(1, self.max_cpu_map_slots))
        self._tpu_sem = threading.Semaphore(max(1, self.max_tpu_map_slots))
        self._red_sem = threading.Semaphore(max(1, self.max_reduce_slots))

        # shuffle server = this tracker's RPC surface (MapOutputServlet
        # role) — reactor-served by default: shuffle reads ride the
        # selector loop's bounded handler pool (saturation answered and
        # counted, rpc_pool_saturated) and pipelining fetchers keep
        # several chunk requests in flight per connection. The knob is
        # the escape hatch back to thread-per-connection.
        use_reactor = confkeys.get_boolean(conf, "tpumr.tasktracker.reactor")
        self._server = RpcServer(self, host=self.bind_host, port=0,
                                 secret=self._rpc_secret,
                                 reactor=use_reactor,
                                 fast_methods={"get_protocol_version",
                                               "umbilical_ping"}
                                 if use_reactor else None)
        # shuffle reads are idempotent byte-range reads: opt them out of
        # the replay cache so MiB-scale chunk responses never pin the
        # response stripes (and replays simply re-read)
        self._server.uncached_methods = {
            "get_map_output", "get_map_output_chunk",
            "get_map_output_dense", "get_map_outputs_batch",
        }
        #: serving-side LRU of open spill fds (os.pread per chunk — no
        #: per-chunk open/seek; invalidated on job purge/rebind)
        self._spill_fds = SpillFdCache(
            confkeys.get_int(conf, "tpumr.shuffle.fd.cache.size"))
        # task children authenticate with their JOB token, not the
        # cluster secret (≈ JobTokenSecretManager + SecureShuffleUtils):
        # scoped callers may reach only the umbilical + shuffle surface,
        # and the methods themselves pin the scope to the job argument
        self._job_tokens: dict[str, bytes] = {}
        #: scope -> monotonic retry-at (negative cache deadlines must
        #: not stretch/shrink with wall-clock steps)
        self._job_token_misses: dict[str, float] = {}
        self._miss_budget = 20.0            # token bucket for miss lookups
        self._miss_budget_ts = time.monotonic()
        self._server.token_resolver = self._job_token_or_none
        self._server.scoped_methods = {
            "get_protocol_version", "umbilical_ping", "umbilical_status",
            "umbilical_can_commit", "umbilical_events", "umbilical_done",
            "umbilical_fail", "umbilical_report_fetch_failure",
            "get_map_output", "get_map_output_chunk",
            "get_map_output_dense", "get_map_outputs_batch",
        }
        #: fetch-failure reports from this tracker's reduces (in-process
        #: or via the umbilical), forwarded to the master on the next
        #: heartbeat and dropped only once a heartbeat delivered them
        self._fetch_failures: list[dict] = []
        self._hb_thread = threading.Thread(target=self._heartbeat_loop,
                                           name=f"{self.name}-heartbeat",
                                           daemon=True)

        # instrumentation ≈ TaskTrackerInstrumentation/TaskTrackerMXBean
        from tpumr.metrics import MetricsSystem
        self.metrics = MetricsSystem(
            "tasktracker",
            period_s=confkeys.get_int(conf, "tpumr.metrics.period.ms") / 1000)
        self._mreg = self.metrics.new_registry(self.name)
        self._mreg.set_gauge("running", lambda: dict(zip(
            ("cpu_maps", "tpu_maps", "reduces"), self._counts())))
        self._mreg.set_gauge("slots", lambda: {
            "cpu": self.max_cpu_map_slots, "tpu": self.max_tpu_map_slots,
            "reduce": self.max_reduce_slots})
        # per-pool busy fractions: the device-utilization signal the
        # hybrid/job-driven scheduling work consumes (PAPERS.md), and
        # the per-tracker rows behind the master's cluster view
        self._mreg.set_gauge("slot_utilization", self._slot_utilization)
        # lost-master visibility: whether the control plane is reachable
        # from HERE, and how stale the lease is — the first thing to
        # check when a tracker looks wedged (the dashboards' twin of the
        # master-side heartbeat-age column)
        self._mreg.set_gauge("master_unreachable",
                             lambda: 1 if self.master_unreachable else 0)
        self._mreg.set_gauge(
            "master_contact_age_s",
            lambda: round(time.monotonic() - self._last_master_contact,
                          3))
        # RPC server-side latency per method — the tracker's RPC surface
        # IS the shuffle server (get_map_output_chunk) + the umbilical
        self._server.metrics = self.metrics.new_registry("rpc")
        # claim the process-wide data-plane registries (shuffle fetch,
        # TPU runner) for publication: exactly one co-located tracker
        # may publish each, or the master would double-merge increments
        from tpumr.metrics.core import claim_process_registry
        self._claimed_sources: list[str] = []
        from tpumr.mapred import shuffle_copier as _sc  # registers hists
        from tpumr.mapred import tpu_runner as _tr
        _sc.shuffle_metrics()
        _tr.runner_metrics()
        for src in ("shuffle", "tpu"):
            reg = claim_process_registry(src, self.name)
            if reg is not None:
                self.metrics.register(reg)
                self._claimed_sources.append(src)
        #: shuffle merge-engine totals across this tracker's finished
        #: attempts (uniform /metrics surface for the in-memory merges,
        #: bounded-fan-in passes, and segment placement)
        self._merge_totals: dict[str, int] = {}
        self._mreg.set_gauge("shuffle_merge",
                             lambda: dict(self._merge_totals))
        # device-cache occupancy (ops/devcache.py): how much HBM the
        # side-input cache holds here and for which tag families — the
        # observability twin of the devcache_tags heartbeat inventory
        # the master's affinity placement consumes
        from tpumr.ops.devcache import occupancy as _devcache_occupancy
        self._mreg.set_gauge("devcache_entries",
                             lambda: _devcache_occupancy()["entries"])
        self._mreg.set_gauge("devcache_bytes",
                             lambda: _devcache_occupancy()["bytes"])
        self._mreg.set_gauge(
            "devcache_family_bytes",
            lambda: dict(_devcache_occupancy()["families"]))
        from tpumr.metrics import sinks_from_conf
        for sink in sinks_from_conf(conf):
            self.metrics.add_sink(sink)
        # distributed tracing (core/tracing.py): daemon-level tracer when
        # the TRACKER conf enables it (None otherwise — the fast path);
        # jobs traced without the daemon flag get a per-job tracer built
        # from their own conf, cached until job cleanup
        from tpumr.core.tracing import Tracer
        self.tracer = Tracer.from_conf(conf, "tasktracker")
        if self.tracer is not None:
            # ring-buffer drops = spans silently lost to backpressure;
            # invisible until surfaced as a gauge (satellite of PR 15)
            self._mreg.set_gauge("trace_spans_dropped",
                                 lambda: self.tracer.dropped)
        self._job_tracers: dict[str, Tracer] = {}
        # continuous profiler (metrics/sampler.py): None unless
        # tpumr.prof.enabled — trackers share the master's knobs so one
        # conf flips the whole cluster's sampling on
        from tpumr.metrics.sampler import StackSampler
        self.sampler = StackSampler.from_conf(conf, self.metrics)
        self._http: Any = None
        self._http_port = conf.get_int("mapred.task.tracker.http.port", -1)

        # self-checks ≈ NodeHealthCheckerService + TaskMemoryManagerThread
        from tpumr.mapred.node_health import (GLOBAL_MEMORY_MANAGER,
                                              NodeHealthChecker)
        script = conf.get("mapred.healthChecker.script.path")
        self.health: NodeHealthChecker | None = None
        if script:
            self.health = NodeHealthChecker(
                script,
                interval_s=conf.get_int("mapred.healthChecker.interval.ms",
                                        10_000) / 1000)
        self._memory_manager = (
            GLOBAL_MEMORY_MANAGER
            if conf.get_int("mapred.task.limit.maxrss.mb", 0) > 0 else None)

        # per-device accelerator quarantine: N consecutive device-classed
        # failures depool a physical device (its slot vanishes from the
        # next heartbeat); a background probe re-admits it. Conf-gated:
        # threshold 0 disables.
        from tpumr.mapred.node_health import TpuDeviceHealth
        dq_threshold = conf.get_int("tpumr.tpu.device.quarantine.failures",
                                    3)
        self.device_health: TpuDeviceHealth | None = None
        if self.max_tpu_map_slots > 0 and dq_threshold > 0:
            self.device_health = TpuDeviceHealth(
                self.n_tpu_devices, threshold=dq_threshold,
                probe_interval_s=conf.get_int(
                    "tpumr.tpu.device.probe.interval.ms", 10_000) / 1000,
                probe_max_interval_s=conf.get_int(
                    "tpumr.tpu.device.probe.max.interval.ms",
                    300_000) / 1000)
        self._mreg.set_gauge(
            "tpu_devices_quarantined",
            lambda: (len(self.device_health.quarantined())
                     if self.device_health is not None else 0))

        # hung-task reaping ≈ mapred.task.timeout + TaskTracker's
        # markUnresponsiveTasks: a monotonic last-progress stamp per
        # attempt, fed by the in-process reporter's observable activity
        # and by CHANGED umbilical status pushes (an isolated child's
        # unconditional 1 Hz push must not count — a hung child keeps
        # pushing identical payloads). The reaper thread fails attempts
        # silent past the (job-conf) timeout with failure_class=timeout.
        self._last_progress: dict[str, float] = {}
        self._progress_sigs: dict[str, tuple] = {}
        self._live_reporters: dict[str, Reporter] = {}
        #: last keepalive tick count pushed by each isolated child
        self._umb_ticks: dict[str, int] = {}
        self._reaper_thread = threading.Thread(
            target=self._reaper_loop, name=f"{self.name}-task-reaper",
            daemon=True)
        self._cleanup_thread = threading.Thread(
            target=self._cleanup_loop, name=f"{self.name}-job-cleanup",
            daemon=True)

    # ------------------------------------------------------------ lifecycle

    def start(self) -> "NodeRunner":
        if self.max_tpu_map_slots > 0:
            # durable XLA compiles across worker processes — the TPU-era
            # JvmManager-reuse analog (see parallel/jaxruntime.py)
            from tpumr.parallel.jaxruntime import (accelerator_devices,
                                                   configure_persistent_cache,
                                                   describe_devices)
            configure_persistent_cache(self.conf)
            # TPU slots are real devices or the tracker does not start:
            # raises when JAX has no tpu device (and CPU was not asked
            # for), and never folds two slots onto one device
            devices = accelerator_devices()
            if self.n_tpu_devices > len(devices):
                raise RuntimeError(
                    f"{self.name}: {self.n_tpu_devices} TPU slot device(s) "
                    f"configured (mapred.tasktracker.map.tpu.tasks.maximum)"
                    f" but this process has {len(devices)} accelerator "
                    f"device(s): {[str(d) for d in devices]}")
            self.tpu_devices = describe_devices(devices, self.n_tpu_devices)
        self._server.start()
        self._hb_thread.start()
        self._reaper_thread.start()
        self._cleanup_thread.start()
        self.metrics.start()
        if self.sampler is not None:
            self.sampler.start()
        if self.health is not None:
            self.health.start()
        if self._memory_manager is not None:
            self._memory_manager.start()
        if self._http_port >= 0:
            from tpumr.http import StatusHttpServer, html_table
            srv = StatusHttpServer(self.name, port=self._http_port)
            srv.add_json("status", lambda q: self._status_dict())
            # /metrics + /json/metrics from one handler
            srv.attach_metrics(self.metrics)
            if self.sampler is not None:
                # /stacks?attempt= narrows to one in-process attempt's
                # thread (they run named task-<attempt_id>) — the live
                # complement to the post-mortem pstats block below
                self.sampler.attach_http(
                    srv, attempt_thread_prefix=lambda a: f"task-{a}")
            srv.add_json("profiles", lambda q: self.list_profiles())
            srv.add_json("profile",
                         lambda q: {"attempt": q["attempt"],
                                    "profile":
                                        self.get_profile(q["attempt"])},
                         parameterized=True)
            srv.add_json("tasklogs", lambda q: self.list_task_logs())
            srv.add_json("tasklog",
                         lambda q: {"attempt": q["attempt"],
                                    "log":
                                        self.get_task_log(q["attempt"])},
                         parameterized=True)

            from tpumr.http import RawHtml, html_escape

            def index_page(q: dict) -> str:
                st = self._status_dict()
                rows = [[RawHtml(
                            f"<a href='/task?attempt="
                            f"{html_escape(s['attempt_id'])}'>"
                            f"{html_escape(s['attempt_id'])}</a>"),
                         s["state"], s["phase"],
                         (f"tpu:{s['tpu_device_id']}" if s["run_on_tpu"]
                          else "cpu") if s["is_map"] else "reduce",
                         f"{s['progress']:.0%}"]
                        for s in st["task_statuses"]]
                profiled = self.list_profiles()
                prof_links = " · ".join(
                    f"<a href='/task?attempt={html_escape(a)}'>"
                    f"{html_escape(a)}</a>" for a in profiled)
                age = time.monotonic() - self._last_master_contact
                master_line = (
                    "<span class='bad'>master UNREACHABLE</span>"
                    if self.master_unreachable else
                    "<span class='ok'>master ok</span>")
                return (
                    f"<h1>TaskTracker {st['tracker_name']}</h1>"
                    f"<p>{master_line} · last contact {age:.1f}s ago</p>"
                    f"<p>host {st['host']} · cpu "
                    f"{st['count_cpu_map_tasks']}/{st['max_cpu_map_slots']}"
                    f" · tpu {st['count_tpu_map_tasks']}/"
                    f"{st['max_tpu_map_slots']} · reduce "
                    f"{st['count_reduce_tasks']}/{st['max_reduce_slots']}"
                    f" · devices free "
                    + "".join("●" if f else "○"
                              for f in st["available_tpu_devices"])
                    + "</p><h2>Running attempts</h2>"
                    + html_table(["attempt", "state", "phase", "backend",
                                  "progress"], rows)
                    + (f"<h2>Profiled attempts</h2><p>{prof_links}</p>"
                       if profiled else ""))

            def task_page(q: dict) -> str:
                """Per-attempt detail (≈ taskdetails.jsp + the
                TaskLogServlet links): live status when running, the
                retained child log link, and the cProfile report's
                top-N pstats lines inline instead of stranding
                profile.out in the task-local dir."""
                aid = q["attempt"]
                with self.lock:
                    st = self.running.get(aid)
                parts = [f"<h1>Attempt {html_escape(aid)}</h1>"]
                if st is not None:
                    parts.append(
                        f"<p>state <b>{html_escape(st.state)}</b> · phase "
                        f"{html_escape(st.phase)} · progress "
                        f"{st.progress:.0%}"
                        + (f" · diagnostics "
                           f"{html_escape(st.diagnostics)}"
                           if st.diagnostics else "") + "</p>")
                else:
                    parts.append("<p class='dim'>not currently running "
                                 "on this tracker</p>")
                if st is not None and st.counters:
                    # shuffle merge-engine placement for this attempt:
                    # in-memory merges, bounded passes, segment homes
                    from tpumr.core.counters import TaskCounter
                    fw = st.counters.get(TaskCounter.FRAMEWORK_GROUP) or {}
                    rows = [[html_escape(k.lower()), int(fw[k])]
                            for k in self._MERGE_COUNTER_KEYS if k in fw]
                    if rows:
                        parts.append("<h2>Shuffle / merge</h2>"
                                     + html_table(["counter", "value"],
                                                  rows))
                if st is not None and self.sampler is not None:
                    # live view while the attempt runs; the pstats block
                    # below only exists after it finishes
                    parts.append(
                        f"<p>live: <a href='/stacks?attempt="
                        f"{html_escape(aid)}'>sampled stacks</a> · "
                        f"<a href='/flame?attempt={html_escape(aid)}'>"
                        f"flame graph</a> (last 30s)</p>")
                from tpumr.mapred.profiler import profile_top_lines
                try:
                    text = self.get_profile(aid)
                except KeyError:
                    parts.append("<p class='dim'>no profile for this "
                                 "attempt (enable mapred.task.profile "
                                 "and the task-id range keys)</p>")
                else:
                    top = profile_top_lines(text)
                    parts.append(
                        "<h2>Profile (top of pstats report)</h2><pre>"
                        + html_escape("\n".join(top)) + "</pre>"
                        f"<p><a href='/json/profile?attempt="
                        f"{html_escape(aid)}'>full profile.out</a></p>")
                try:
                    self._open_userlog(aid, "child.log").close()
                except KeyError:
                    pass
                else:
                    parts.append(
                        f"<p><a href='/json/tasklog?attempt="
                        f"{html_escape(aid)}'>retained child log</a></p>")
                return "".join(parts)

            srv.add_page("index", index_page)
            srv.add_page("task", task_page, parameterized=True)
            self._http = srv.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        if self.sampler is not None:
            self.sampler.stop()
        self.metrics.stop()
        from tpumr.metrics.core import release_process_registry
        for src in self._claimed_sources:
            release_process_registry(src, self.name)
        if self.tracer is not None:
            self.tracer.flush()
        with self.lock:
            tracers = list(self._job_tracers.values())
        for t in tracers:
            t.flush()
        if self.health is not None:
            self.health.stop()
        if self.device_health is not None:
            self.device_health.stop()
        if self._http is not None:
            self._http.stop()
        self._server.stop()
        shutil.rmtree(self.local_root, ignore_errors=True)

    @property
    def shuffle_port(self) -> int:
        return self._server.port

    @property
    def shuffle_addr(self) -> str:
        """Where this tracker serves map outputs, as its status and the
        completion events of its maps give it."""
        return f"{self.bind_host}:{self.shuffle_port}"

    # ------------------------------------------------------------ status

    def _slot_utilization(self) -> dict:
        """Busy fraction per slot pool (0.0 when the pool is absent —
        a present-but-zero series beats a missing one)."""
        with self.lock:
            cpu, tpu, red = self._counts()
        return {
            "cpu": cpu / self.max_cpu_map_slots
            if self.max_cpu_map_slots else 0.0,
            "tpu": tpu / self.max_tpu_map_slots
            if self.max_tpu_map_slots else 0.0,
            "reduce": red / self.max_reduce_slots
            if self.max_reduce_slots else 0.0,
        }

    def _counts(self) -> tuple[int, int, int]:
        cpu = tpu = red = 0
        for aid, st in self.running.items():
            if st.state != TaskState.RUNNING:
                continue
            if st.is_map:
                if st.run_on_tpu:
                    tpu += 1
                else:
                    cpu += 1
            else:
                red += 1
        return cpu, tpu, red

    def _available_tpu_devices(self) -> list[bool]:
        """free[i] derived from running task statuses each heartbeat
        (≈ TaskTrackerStatus.availableGPUDevices, :536-550), minus any
        quarantined devices — the scheduler derives assignable device
        ids from this list, so a sick device vanishes here first."""
        free = [True] * self.n_tpu_devices
        for st in self.running.values():
            if (st.state == TaskState.RUNNING and st.run_on_tpu
                    and 0 <= st.tpu_device_id < self.n_tpu_devices):
                free[st.tpu_device_id] = False
        if self.device_health is not None:
            for d in self.device_health.quarantined():
                if 0 <= d < self.n_tpu_devices:
                    free[d] = False
        return free

    @staticmethod
    def _fetch_batcher_stats() -> dict:
        """Device→host transfer coalescing effectiveness (fetch_batcher):
        logical fetches vs ``device_get`` calls actually issued."""
        from tpumr.mapred.fetch_batcher import shared_batcher
        b = shared_batcher()
        return {"fetches": b.fetches, "roundtrips": b.roundtrips,
                "coalesced": b.batched}

    def _devcache_tags(self) -> "list[str]":
        """Bounded, SORTED list of device-cache tags resident here —
        the heartbeat inventory behind the master's affinity placement.
        Sorted so an unchanged inventory is byte-identical across beats
        and the heartbeat delta encoder elides it; bounded
        (tpumr.devcache.heartbeat.tags, 0 disables) so a tag-heavy
        workload can't bloat every beat."""
        limit = confkeys.get_int(self.conf, "tpumr.devcache.heartbeat.tags")
        if limit <= 0:
            return []
        from tpumr.ops.devcache import inventory
        return sorted(inventory(max_tags=limit))

    def _status_dict(self) -> dict:
        with self.lock:
            cpu, tpu, red = self._counts()
            statuses = [st.to_dict() for st in self.running.values()]
            # memory accounting for the capacity scheduler's matching
            # (≈ CapacityTaskScheduler memory checks): total offered minus
            # the declared demand of everything running; -1 = unlimited
            total_mb = self.conf.get_int("mapred.tasktracker.memory.mb", -1)
            if total_mb >= 0:
                used = sum(t.memory_mb for aid, t in self.running_tasks.items()
                           if self.running.get(aid) is not None
                           and self.running[aid].state == TaskState.RUNNING)
                avail_mb = max(0, total_mb - used)
            else:
                avail_mb = -1
            # device quarantine shrinks the ADVERTISED TPU slot pool on
            # the next heartbeat (the acceptance contract: a sick device
            # is observably depooled, and restored when the probe clears)
            quarantined = (self.device_health.quarantined()
                           if self.device_health is not None else [])
            tpu_slots = max(0, self.max_tpu_map_slots - len(quarantined))
            return {
                "available_memory_mb": avail_mb,
                "fetch_failures": list(self._fetch_failures),
                "tracker_name": self.name,
                "host": self.host,
                "shuffle_addr": self.shuffle_addr,
                "shuffle_port": self.shuffle_port,
                "max_cpu_map_slots": self.max_cpu_map_slots,
                "max_tpu_map_slots": tpu_slots,
                "quarantined_tpu_devices": quarantined,
                "max_reduce_slots": self.max_reduce_slots,
                "count_cpu_map_tasks": cpu,
                "count_tpu_map_tasks": tpu,
                "count_reduce_tasks": red,
                "available_tpu_devices": self._available_tpu_devices(),
                "device_fetch": self._fetch_batcher_stats(),
                # bounded devcache inventory (tag names only — byte
                # counts stay in the local gauges): the master's
                # affinity placement signal. A baseline heartbeat key,
                # so steady-state beats delta-encode it away for free.
                "devcache_tags": self._devcache_tags(),
                "task_statuses": statuses,
                "rack": self.rack,
                "healthy": (self.health.healthy
                            if self.health is not None else True),
                "health_report": (self.health.report
                                  if self.health is not None else ""),
            }

    # ------------------------------------------------------------ heartbeat

    #: least spacing of out-of-band beats: attempts that finish together
    #: share one beat, and a stream of instant tasks cannot spin the loop
    _OOB_MIN_GAP_S = 0.05

    def _slot_freed(self) -> None:
        """An attempt of this tracker has just gone terminal: a slot is
        free and a completion waits to be told, so wake the heartbeat
        loop (≈ TaskTracker.notifyTTAboutTaskCompletion). Called AFTER
        the terminal state is set — the loop looks for it — and, where
        the attempt held a slot semaphore, after its release, so the
        task the early beat brings back finds the slot open."""
        self._wake.set()

    def _completion_waiting(self) -> bool:
        with self.lock:
            return any(st.state in TaskState.TERMINAL
                       for st in self.running.values())

    def _heartbeat_loop(self) -> None:
        import random as _random
        oob = False
        while not self._stop.is_set():
            # cleared BEFORE the beat snapshots the statuses: an attempt
            # that finishes while the RPC is in flight (reported
            # RUNNING) sets it again and gets a beat of its own
            self._wake.clear()
            wait_s = self.heartbeat_s
            try:
                if self.tracer is None:
                    self._heartbeat_once(oob=oob)
                else:
                    # daemon-scoped trace (trace id = the tracker, not a
                    # job): heartbeat latency is where master contention
                    # shows up first. The span's context rides the
                    # status dict so the master records its phase
                    # breakdown (fold/assign/deferred_io) as sub-spans
                    # of THIS span — one swimlane shows where a slow
                    # beat's time went, master-side included.
                    with self.tracer.span("heartbeat",
                                          f"daemon-{self.name}") as hb:
                        self._heartbeat_once(hb_span=hb, oob=oob)
            except (ConnectionError, OSError):
                # LOST MASTER: transport-level failure (crashed,
                # restarting, partitioned). In-flight tasks keep
                # running; retry with capped jittered exponential
                # backoff so a restarting master isn't stampeded by the
                # whole fleet at once. NOT a fault of this tracker and
                # NOT an application error — nothing is killed.
                self._master_failures += 1
                self.master_unreachable = True
                self._mreg.incr("master_unreachable_beats")
                backoff = min(self._lost_master_backoff_max_s,
                              self.heartbeat_s
                              * (2 ** min(self._master_failures, 6)))
                wait_s = max(self.heartbeat_s,
                             backoff * _random.uniform(0.5, 1.0))
            except Exception:
                # application-level RPC error: the master is ALIVE and
                # answered (a raise inside the handler, an auth refusal)
                # — keep the normal cadence, no lost-master backoff
                pass
            oob = self._await_next_beat(wait_s)

    def _await_next_beat(self, wait_s: float) -> bool:
        """Sleep until the next beat is due; True when that beat is an
        out-of-band one (≈ mapreduce.tasktracker.outofband.heartbeat,
        with no switch: it depends only on what the loop observes). The
        timer runs from the beat just sent, whichever kind it was, so an
        idle tracker still beats every ``wait_s``. A wake-up becomes an
        early beat only while (a) the master instructs no slower cadence
        than this tracker's own (a master that stretches it — adaptive
        rate, brownout — is shedding load and is obeyed), (b) the master
        is reachable (a wake must not defeat the lost-master backoff),
        and a completion is in fact waiting; (c) early beats keep
        ``_OOB_MIN_GAP_S`` from the beat before. So a tracker sends no
        more early beats than it finishes tasks."""
        sent = time.monotonic()
        while True:
            left = sent + wait_s - time.monotonic()
            if left <= 0 or not self._wake.wait(left) \
                    or self._stop.is_set():
                return False
            # clear, THEN look: a completion between the two sets it again
            self._wake.clear()
            if not self.master_unreachable \
                    and self.heartbeat_s <= self._heartbeat_floor_s \
                    and self._completion_waiting():
                self._stop.wait(max(0.0, sent + self._OOB_MIN_GAP_S
                                    - time.monotonic()))
                return True

    def _metrics_piggyback(self) -> dict:
        """The compact metrics snapshot that rides every heartbeat:
        cumulative counters + cumulative sparse histogram state + numeric
        gauges, per source. Cumulative (not delta) on purpose — replayed
        heartbeats merge idempotently master-side (metrics/cluster.py).
        The tracker's own per-instance source name is normalized to
        ``tasktracker`` so cluster metric names don't embed instance
        names."""
        out: dict[str, dict] = {}
        for src, t in self.metrics.typed_snapshot().items():
            name = "tasktracker" if src == self.name else src
            counters = {k: v for k, v in (t.get("counters") or {}).items()
                        if isinstance(v, (int, float))}
            gauges = {k: v for k, v in (t.get("gauges") or {}).items()
                      if isinstance(v, (int, float, dict))}
            hists = t.get("histograms") or {}
            if counters or gauges or hists:
                out[name] = {"counters": counters, "gauges": gauges,
                             "histograms": hists}
        return out

    def _suppress_statuses(self, statuses: "list[dict]") -> "list[dict]":
        """The RUNNING-status rate limit: drop statuses whose
        (state, phase) is unchanged and whose last ship is fresher than
        the report interval. Terminal statuses always pass (losing one
        would lose the completion)."""
        if not self._status_interval_s:
            return statuses
        now = time.monotonic()
        out = []
        for sd in statuses:
            if sd["state"] != TaskState.RUNNING:
                out.append(sd)
                continue
            aid = sd["attempt_id"]
            key = (sd["state"], sd.get("phase"))
            prev = self._status_shipped.get(aid)
            if prev is not None and prev[:2] == key \
                    and now - prev[2] < self._status_interval_s:
                continue
            self._status_shipped[aid] = (*key, now)
            out.append(sd)
        return out

    def _heartbeat_once(self, hb_span: Any = None,
                        oob: bool = False) -> None:
        full = self._status_dict()
        # the completions this beat tells: dropped once it is delivered
        sent_terminal = {sd["attempt_id"] for sd in full["task_statuses"]
                         if sd["state"] in TaskState.TERMINAL}
        now = time.monotonic()
        metrics = None
        if now - self._piggyback_last >= self._piggyback_interval_s:
            try:
                metrics = self._metrics_piggyback()
            except Exception:  # noqa: BLE001 — metering must not break
                metrics = None  # the heartbeat lease
        # wire encoding: full on (re)contact, change-only delta after —
        # the encoder also omits an UNCHANGED metrics piggyback (it is
        # cumulative, so the master's last fold still holds). Delta
        # beats additionally rate-limit unchanged RUNNING statuses; a
        # FULL beat bypasses that (it resets the master's believed set)
        wire = full
        if self._hb_encoder.will_delta():
            wire = dict(full, task_statuses=self._suppress_statuses(
                full["task_statuses"]))
        status = self._hb_encoder.encode(wire, metrics)
        if hb_span is not None:
            # the master pops this and parents its heartbeat phase
            # sub-spans to it (never stored in the tracker registry)
            status["trace"] = hb_span.context
        cpu, tpu, red = (full["count_cpu_map_tasks"],
                         full["count_tpu_map_tasks"],
                         full["count_reduce_tasks"])
        # ask if ANY pool has room (TaskTracker.java:1841-1844)
        ask = (cpu < self.max_cpu_map_slots or tpu < self.max_tpu_map_slots
               or red < self.max_reduce_slots)
        try:
            resp = self.master.call("heartbeat", status,
                                    self._initial_contact,
                                    ask, self._response_id)
        except Exception:
            # delivery UNKNOWN (the master may have applied the beat and
            # lost the response): the next beat must re-ship the full
            # status — a delta against a baseline newer than ours could
            # mask a changed-then-reverted key forever
            self._hb_encoder.reset()
            raise
        self._hb_encoder.delivered()
        if oob:
            self._mreg.incr("heartbeats_out_of_band")
            if hb_span is not None:
                hb_span.set(oob=True, freed=len(sent_terminal))
        # re-contact: the lost-master state clears the moment a beat
        # lands (the master that answered has adopted our full status)
        self.master_unreachable = False
        self._master_failures = 0
        self._last_master_contact = time.monotonic()
        if metrics is not None:
            self._piggyback_last = now
        self._initial_contact = False
        self._response_id = resp["response_id"]
        # adaptive cadence: the master instructs the next interval
        # (scaled to fleet size, ≈ HeartbeatResponse.getHeartbeat-
        # Interval); the loop's _stop.wait reads heartbeat_s fresh
        # every beat, so the new cadence takes effect immediately
        nxt = resp.get("next_interval_ms")
        if isinstance(nxt, (int, float)) and nxt > 0:
            self.heartbeat_s = nxt / 1000.0
        if any(a.get("type") == "resend_full"
               for a in resp["actions"]):
            # the master did NOT fold this beat (no baseline — it wants
            # the full status first): keep every status and report for
            # the re-send, or a terminal completion delivered into the
            # early return would be dropped unseen and its task re-run
            for action in resp["actions"]:
                self._apply_action(action)
            return
        with self.lock:
            # the heartbeat DELIVERED these fetch-failure reports (they
            # were snapshotted into `full` first — a failed RPC keeps
            # them queued for the retry); entries appended since the
            # snapshot stay for the next beat
            sent_ff = len(full.get("fetch_failures", []))
            if sent_ff:
                del self._fetch_failures[:sent_ff]
            # Drop only statuses whose SENT snapshot was terminal — a task
            # that finished while the RPC was in flight was reported as
            # RUNNING, so it must survive until the next heartbeat or the
            # master never learns it completed.
            for aid in sent_terminal:
                self.running.pop(aid, None)
                self._status_shipped.pop(aid, None)
                self.running_tasks.pop(aid, None)
                # reaper bookkeeping dies with the attempt
                self._last_progress.pop(aid, None)
                self._progress_sigs.pop(aid, None)
                self._live_reporters.pop(aid, None)
                self._umb_ticks.pop(aid, None)
        for action in resp["actions"]:
            self._apply_action(action)

    def _cleanup_loop(self) -> None:
        """The sweep of finished jobs, every 20 heartbeat intervals on a
        thread of its own: it asks the master about every known job and
        removes their scratch trees, 0.6 to 2 s beside busy map threads,
        and the beat's thread has to stay free to tell a completion the
        moment it happens."""
        while not self._stop.wait(20 * self.heartbeat_s):
            try:
                self._cleanup_finished_jobs()
            except Exception:  # noqa: BLE001 — retried next sweep
                pass

    def _cleanup_finished_jobs(self) -> None:
        """Drop map outputs + cached confs of terminal jobs (≈ the
        KillJobAction-driven purge of job-local dirs). Streamed-handoff
        entries (``handoff:<job>`` keys) are NOT governed by their
        job's terminal state — a finished upstream stage keeps serving
        its live pipeline — so they consult the master's purge oracle
        (pipeline terminal?) instead."""
        from tpumr.pipeline.handoff import SERVE_PREFIX
        with self.lock:
            # include resolver-populated token entries for jobs this
            # tracker never ran (shuffle-source role) so they stop
            # authenticating once the master reports the job terminal
            all_ids = ({j for j, _ in self.map_outputs}
                       | set(self.job_confs) | set(self._job_tokens))
        job_ids = {j for j in all_ids
                   if not j.startswith(SERVE_PREFIX)}
        for key in all_ids - job_ids:
            job_id = key[len(SERVE_PREFIX):]
            try:
                if not self.master.call("handoff_purgeable", job_id):
                    continue
            except Exception:  # noqa: BLE001 — master briefly down:
                continue       # keep serving, retry next sweep
            with self.lock:
                self.map_outputs = {k: v for k, v in
                                    self.map_outputs.items()
                                    if k[0] != key}
            self._spill_fds.invalidate(
                os.path.join(self.local_root, "handoff", job_id))
            shutil.rmtree(os.path.join(self.local_root, "handoff",
                                       job_id), ignore_errors=True)
            with self.lock:
                self._handoff_sources.pop(job_id, None)
        for job_id in job_ids:
            try:
                st = self.master.call("get_job_status", job_id)
            except Exception as e:  # noqa: BLE001
                from tpumr.ipc.rpc import RpcError
                if isinstance(e, RpcError) and "KeyError" in str(e):
                    # the master does not know this job at all (restart
                    # with recovery off, or past its alias horizon) —
                    # purgeable, or the outputs leak forever
                    st = {"state": "KILLED"}
                else:
                    continue
            if st["state"] in ("SUCCEEDED", "FAILED", "KILLED"):
                with self.lock:
                    self.map_outputs = {k: v for k, v in
                                        self.map_outputs.items()
                                        if k[0] != job_id}
                    jc = self.job_confs.pop(job_id, None)
                    self._job_tokens.pop(job_id, None)
                    jt = self._job_tracers.pop(job_id, None)
                    self._job_rebinds = {
                        k: v for k, v in self._job_rebinds.items()
                        if job_id not in (k, v)}
                if jt is not None:
                    jt.flush()   # stragglers of the finished traced job
                if jc is not None:
                    from tpumr.mapred import filecache
                    filecache.release_job(
                        jc, os.path.join(self.local_root, "cache"), job_id)
                self._spill_fds.invalidate(
                    os.path.join(self.local_root, job_id))
                shutil.rmtree(os.path.join(self.local_root, job_id),
                              ignore_errors=True)
        self._purge_old_userlogs()

    def _purge_old_userlogs(self) -> None:
        """Retained logs (profiles) age out after
        ``mapred.userlog.retain.hours`` (reference default 24) — they
        outlive job cleanup on purpose, but not forever."""
        logs = os.path.join(self.local_root, "userlogs")
        if not os.path.isdir(logs):
            return
        retain_s = self.conf.get_float("mapred.userlog.retain.hours",
                                       24.0) * 3600
        now = time.time()
        with self.lock:
            # a LIVE attempt's child.log lives in this tree; its job dir
            # must never age out mid-run (appends don't bump dir mtime)
            live_jobs = {str(TaskAttemptID.parse(aid).task.job)
                         for aid in self.running}
        for job_id in os.listdir(logs):
            if job_id in live_jobs:
                continue
            d = os.path.join(logs, job_id)
            try:
                # file mtimes are wall clock; so must the cutoff be
                if now - os.path.getmtime(d) > retain_s:  # tpulint: disable=clock-arith
                    shutil.rmtree(d, ignore_errors=True)
            except OSError:
                pass

    def _apply_action(self, action: dict) -> None:
        kind = action.get("type")
        if kind == "launch":
            task = Task.from_dict(action["task"])
            self._launch(action["job_id"], task)
        elif kind == "kill_task":
            with self.lock:
                self._kill_requested.add(action["attempt_id"])
        elif kind == "reinit":
            # ≈ ReinitTrackerAction: drop local state, re-register —
            # with a FULL status (the master that reset us has no
            # baseline to apply deltas to)
            with self.lock:
                self.running.clear()
                self.running_tasks.clear()
                self._initial_contact = True
                self._response_id = 0
                self._hb_encoder.reset()
                self._status_shipped.clear()
        elif kind == "resend_full":
            # the master lost our baseline (restart / eviction): the
            # next beat ships the FULL status and the master ADOPTS it.
            # Unlike reinit, nothing local is dropped — in-flight tasks
            # survive the master's restart.
            with self.lock:
                self._hb_encoder.reset()
                self._status_shipped.clear()
        elif kind == "recover_job":
            # a restarted master resubmitted an interrupted job under a
            # new id: re-key this tracker's served map outputs (and
            # translate future registrations) so reducers launched
            # under the NEW id can fetch outputs produced under the old
            old, new = str(action["old"]), str(action["new"])
            with self.lock:
                self._job_rebinds[old] = new
                for key in [k for k in self.map_outputs if k[0] == old]:
                    self.map_outputs[(new, key[1])] = \
                        self.map_outputs.pop(key)
        elif kind == "disallowed":
            # ≈ DisallowedTaskTrackerException: this host was excluded
            # (mapred.hosts/.exclude + mradmin -refreshNodes). The
            # reference's TaskTracker shuts down; ours stops
            # heartbeating and kills its local work — an operator must
            # re-admit the host before restarting the daemon.
            import logging
            logging.getLogger(__name__).warning(
                "master disallowed this tracker (host excluded) — "
                "shutting down")
            with self.lock:
                for aid in list(self.running_tasks):
                    self._kill_requested.add(aid)
            self._stop.set()
            self._wake.set()

    # ------------------------------------------------------------ execution

    def _job_token(self, job_id: str) -> bytes:
        """This job's token, fetched from the master (cluster-secret
        channel) on first use and cached for the job's lifetime."""
        with self.lock:
            tok = self._job_tokens.get(job_id)
        if tok is None:
            tok = bytes(self.master.call("get_job_token", job_id) or b"")
            with self.lock:
                while len(self._job_tokens) >= 1024:
                    # hard cap (same policy as _job_token_misses): an
                    # evicted live job just re-resolves via the master
                    self._job_tokens.pop(next(iter(self._job_tokens)))
                self._job_tokens[job_id] = tok
        return tok

    def _job_token_or_none(self, scope: str) -> "bytes | None":
        """Token resolver for the RPC server: serve scoped callers of any
        job this tracker knows (it may be the shuffle SOURCE for a job
        whose reduce child runs elsewhere — resolve via the master on
        cache miss rather than rejecting). Unresolved scopes are
        negatively cached AND master lookups for unknown scopes are
        globally rate-limited, so a flood of unique bogus scopes (each a
        guaranteed cache miss) cannot amplify into unbounded
        tracker→master RPC traffic or memory growth."""
        now = time.monotonic()
        with self.lock:
            if self._job_token_misses.get(scope, 0) > now:
                return None
            if scope not in self._job_tokens:
                # token-bucket on miss lookups: ~4/s sustained, burst 20
                self._miss_budget = min(
                    20.0, self._miss_budget
                    + (now - self._miss_budget_ts) * 4.0)
                self._miss_budget_ts = now
                if self._miss_budget < 1.0:
                    return None
                self._miss_budget -= 1.0
        try:
            return self._job_token(scope) or None
        except Exception:  # noqa: BLE001 — unknown job / master down
            with self.lock:
                while len(self._job_token_misses) >= 1024:
                    # hard cap: evict oldest entries (insertion order)
                    self._job_token_misses.pop(
                        next(iter(self._job_token_misses)))
                self._job_token_misses[scope] = now + 30.0
            return None

    @staticmethod
    def _check_scope(job_id: str) -> None:
        """Token-scoped callers may only touch THEIR job (≈ the
        SecureShuffleUtils verification on MapOutputServlet)."""
        from tpumr.ipc.rpc import current_rpc_scope
        scope = current_rpc_scope()
        if scope is not None and scope != job_id:
            raise PermissionError(
                f"job token for {scope} cannot access job {job_id}")

    def _job_conf(self, job_id: str) -> JobConf:
        with self.lock:
            jc = self.job_confs.get(job_id)
        if jc is None:
            conf_dict = self.master.call("get_job_conf", job_id)
            jc = JobConf()
            for k, v in conf_dict.items():
                jc.set(k, v)
            # tracker-local cache root for DistributedCache localization
            jc.set("tpumr.cache.dir", os.path.join(self.local_root, "cache"))
            # shuffle spill dir (ShuffleCopier disk segments) — inside the
            # job scratch tree so job cleanup rmtree's any stragglers
            jc.set("tpumr.task.local.dir",
                   os.path.join(self.local_root, job_id, "shuffle"))
            jc.set("tpumr.job.id", job_id)
            # retained logs tree (≈ userlogs): per-attempt profiles land
            # here, OUTSIDE the job scratch dir that cleanup rmtree's
            jc.set("tpumr.task.userlogs.dir",
                   os.path.join(self.local_root, "userlogs", job_id))
            # pipeline streamed handoff: the tee spills land OUTSIDE the
            # job scratch tree — they must outlive this job's cleanup
            # (downstream stages fetch them after the job is terminal)
            # and are purged only once the owning pipeline is over.
            # Thread-isolated tasks only: a PROCESS child's registration
            # payload never reaches the tracker, so its tee would be
            # write-only waste — those stages serve via DFS fallback
            if jc.get_boolean("tpumr.pipeline.stream.handoff", False) \
                    and jc.get("tpumr.task.isolation",
                               "thread") != "process":
                jc.set("tpumr.pipeline.handoff.dir",
                       os.path.join(self.local_root, "handoff", job_id))
            # downstream streamed stage: stash the in-process stream-
            # source factory (MapLocator over the master's handoff feed
            # + this tracker's rpc credentials). Thread-isolated tasks
            # only — a process child's conf serializes to a file, and
            # its maps fall back to the committed DFS artifact instead.
            if jc.get("tpumr.pipeline.handoff.upstream") and \
                    jc.get("tpumr.task.isolation", "thread") != "process":
                jc.set("tpumr.pipeline.handoff.source",
                       self._handoff_source)
            # trace sink fallback: a client may enable tracing without
            # naming a dir (those are daemon-side keys) — without this,
            # the tracker's and child's spans would be silently dropped
            from tpumr.core.tracing import trace_dir_from_conf
            if trace_dir_from_conf(jc) is None:
                d = trace_dir_from_conf(self.conf)
                if d:
                    jc.set("tpumr.trace.dir", d)
            with self.lock:
                self.job_confs[job_id] = jc
        return jc

    def _launch(self, job_id: str, task: Task) -> None:
        aid = str(task.attempt_id)
        status = TaskStatus(attempt_id=task.attempt_id, is_map=task.is_map,
                            state=TaskState.RUNNING,
                            phase=TaskPhase.MAP if task.is_map
                            else TaskPhase.SHUFFLE,
                            run_on_tpu=task.run_on_tpu,
                            tpu_device_id=task.tpu_device_id)
        with self.lock:
            self.running[aid] = status
            self.running_tasks[aid] = task
            self._last_progress[aid] = time.monotonic()
        if not task.is_map:
            self._mreg.incr("reduces_launched")
        else:
            self._mreg.incr("tpu_maps_launched" if task.run_on_tpu
                            else "cpu_maps_launched")
        t = threading.Thread(target=self._run_task,
                             args=(job_id, task, status),
                             name=f"task-{aid}", daemon=True)
        t.start()

    def _trace_tracer(self, job_id: str, task: Task):
        """The tracer for a TRACED task (``task.trace`` stamped by the
        master), or None: the daemon's own when the tracker conf enables
        tracing, else a per-job tracer built from the job conf (cached
        until job cleanup). Never raises — a master outage during the
        conf fetch just runs the task untraced."""
        if task.trace is None:
            return None
        if self.tracer is not None:
            if self.tracer.trace_dir is None:
                # tracker conf enabled tracing but named no sink — the
                # job conf (dir-fallback-patched in _job_conf) supplies
                # it, exactly like the master patches its own at submit
                try:
                    from tpumr.core.tracing import trace_dir_from_conf
                    self.tracer.trace_dir = trace_dir_from_conf(
                        self._job_conf(job_id))
                except Exception:  # noqa: BLE001 — master briefly down
                    pass
            return self.tracer
        with self.lock:
            t = self._job_tracers.get(job_id)
        if t is not None:
            return t
        try:
            conf = self._job_conf(job_id)
        except Exception:  # noqa: BLE001
            return None
        from tpumr.core.tracing import Tracer
        t = Tracer.from_conf(conf, "tasktracker")
        if t is None:
            return None
        with self.lock:
            t = self._job_tracers.setdefault(job_id, t)
        return t

    def _run_task(self, job_id: str, task: Task, status: TaskStatus) -> None:
        aid = str(task.attempt_id)

        def killed() -> bool:
            with self.lock:
                return aid in self._kill_requested

        def on_progress(f: float) -> None:
            # in-process fraction reports land directly on the heartbeat
            # status (isolated children ship theirs over the umbilical) —
            # the master's per-TIP rate model is fed either way. Monotone
            # max: a late report must never roll back the settle's 1.0.
            status.progress = max(status.progress, min(1.0, float(f)))

        # cooperative cancellation: record loops poll this so a preemption
        # or speculative-race kill frees the slot mid-task, not at natural
        # completion (hard process kills arrive with the subprocess
        # executor; threads cannot be interrupted)
        reporter = Reporter(abort_check=killed, on_progress=on_progress)
        with self.lock:
            # the reaper samples this live reporter's counters/status for
            # progress liveness — zero hot-path cost (hoisted Counter
            # objects bypass Reporter.incr_counter, so a push-style hook
            # could never see the per-record activity anyway)
            self._live_reporters[aid] = reporter
        sem = (self._red_sem if not task.is_map
               else self._tpu_sem if task.run_on_tpu else self._cpu_sem)
        tracer = self._trace_tracer(job_id, task)
        wait_t0 = time.monotonic()
        sem.acquire()
        try:
            if tracer is None:
                self._run_task_inner(job_id, task, status, reporter)
                return
            self._run_task_traced(tracer, job_id, task, status, reporter,
                                  time.monotonic() - wait_t0)
        finally:
            sem.release()  # ≈ addFreeSlots on done/kill (:3401-3402)
            # the status is terminal, the launch span ended and the slot
            # is open: NOW tell the master, so that what it sends back
            # does not queue behind the attempt that just ended
            self._slot_freed()

    def _run_task_traced(self, tracer: Any, job_id: str, task: Task,
                         status: TaskStatus, reporter: Reporter,
                         slot_wait_s: float) -> None:
        """Traced execution: a tracker-role ``task:launch`` span parented
        to the master's scheduling span, and (in-process only — isolated
        children open their own) a task-role ``task:run`` span installed
        as the thread's ambient context so spill/merge/shuffle/TPU spans
        nest under it."""
        from tpumr.core import tracing
        aid = str(task.attempt_id)
        backend = ("tpu" if task.run_on_tpu else "cpu") if task.is_map \
            else "cpu"
        # ``slots``: the size of the pool this task's semaphore guards,
        # so a reader can turn launch seconds into slot occupancy
        launch = tracer.start_span(
            "task:launch", task.trace["trace_id"], parent=task.trace,
            backend=backend, attempt_id=aid, tracker=self.name,
            is_map=task.is_map, slot_wait_s=round(slot_wait_s, 6),
            slots=max(1, self.max_reduce_slots if not task.is_map
                      else self.max_tpu_map_slots if task.run_on_tpu
                      else self.max_cpu_map_slots))
        if task.run_on_tpu and task.tpu_device_id >= 0:
            launch.set(device_id=task.tpu_device_id)
        try:
            isolated = False
            try:
                isolated = self._isolate_in_process(
                    self._job_conf(job_id), task)
            except Exception:  # noqa: BLE001 — inner settles the failure
                pass
            # re-parent downstream spans (isolated child's task:run, the
            # master-facing chain stays schedule → launch → run)
            task.trace = launch.context
            if isolated:
                self._run_task_inner(job_id, task, status, reporter)
                return
            run = tracer.start_span("task:run", launch.trace_id,
                                    parent=launch, role="task",
                                    backend=backend, attempt_id=aid)
            try:
                with tracing.activate(tracer, run):
                    self._run_task_inner(job_id, task, status, reporter)
            finally:
                tracer.finish(run.set(state=status.state))
        finally:
            tracer.finish(launch.set(state=status.state))
            tracer.flush()

    def _isolate_in_process(self, conf: JobConf, task: Task) -> bool:
        """Process isolation gate (≈ which tasks get a child JVM): opt-in
        via ``tpumr.task.isolation=process`` (job conf first, tracker conf
        fallback). TPU tasks and device-shuffle gang reduces always stay
        in-process — they must share the tracker's JAX runtime, device
        mesh, and HBM split cache."""
        mode = conf.get("tpumr.task.isolation",
                        self.conf.get("tpumr.task.isolation", "thread"))
        if mode != "process" or task.run_on_tpu:
            return False
        if not task.is_map:
            from tpumr.mapred.device_shuffle import is_device_shuffle
            if is_device_shuffle(conf):
                return False
        return True

    def _abort_if_settled(self, status: TaskStatus) -> None:
        """A reaped (terminally settled) in-process attempt must never
        reach the commit gate or register map outputs: the master
        already counted it FAILED and re-queued the task, and a zombie
        can_commit call would CAPTURE the commit grant for a dead
        attempt — every re-run then loses the grant race and the task
        livelocks KILLED forever. Checked at the side-effect boundaries
        (output registration, commit)."""
        with self.lock:
            if status.state in TaskState.TERMINAL:
                raise TaskKilledError(
                    "attempt settled terminally while still running "
                    "(reaped for progress silence)")

    def _run_task_inner(self, job_id: str, task: Task, status: TaskStatus,
                        reporter: Reporter) -> None:
        aid = str(task.attempt_id)
        try:
            conf = self._job_conf(job_id)
            if self._isolate_in_process(conf, task):
                from tpumr.mapred.process_runner import run_task_in_process
                run_task_in_process(self, job_id, task, status, conf)
                return
            from tpumr.mapred.profiler import maybe_profile, profile_dir
            committed = True
            local_dir = os.path.join(self.local_root, job_id, aid)
            prof_dir = profile_dir(conf, aid, local_dir)
            if task.is_map:
                out = maybe_profile(
                    conf, task, prof_dir,
                    lambda: run_map_task(conf, task, local_dir, reporter,
                                         status=status))
                self._abort_if_settled(status)
                with self.lock:
                    if out[0]:
                        # stamp the producing attempt on the served index
                        # (fi serve seams target attempt generations; a
                        # re-run registers OVER the lost attempt's entry)
                        idx = dict(out[1])
                        idx["attempt"] = aid
                        idx["attempt_no"] = task.attempt_id.attempt
                        # total output size rides the success status into
                        # the completion event — the reduces' fetch-
                        # ordering key (size-aware shuffle)
                        status.output_bytes = sum(
                            int(p[2]) for p in idx.get("partitions", ()))
                        # a job recovered under a new id registers its
                        # stragglers' outputs under the NEW key
                        self.map_outputs[
                            (self._job_rebinds.get(job_id, job_id),
                             task.partition)] = (out[0], idx)
                # commit covers direct-output maps AND map-side named
                # outputs (lib.MultipleOutputs) in jobs with reducers;
                # needs_commit makes it a no-op when no files exist
                committed = self._commit(conf, task)
            else:
                status.phase = TaskPhase.SHUFFLE
                handoff_out = None
                from tpumr.mapred.device_shuffle import is_device_shuffle
                if is_device_shuffle(conf):
                    # gang task: exchange + sort on this host's mesh
                    from tpumr.mapred.device_shuffle import run_device_reduce
                    run_device_reduce(
                        conf, task,
                        self._remote_dense_fetch_factory(job_id, task,
                                                         reporter),
                        reporter)
                else:
                    fetch = self._remote_fetch_factory(job_id, task)
                    handoff_out = maybe_profile(
                        conf, task, prof_dir,
                        lambda: run_reduce_task(conf, task, fetch,
                                                reporter))
                status.phase = TaskPhase.REDUCE
                self._abort_if_settled(status)
                committed = self._commit(conf, task)
                if handoff_out and not committed:
                    # the tee of a commit-race loser must not linger on
                    # disk (nothing would ever register or purge it)
                    try:
                        os.unlink(handoff_out["path"])
                    except OSError:
                        pass
                elif committed and handoff_out:
                    # streamed stage handoff: ONLY the commit winner
                    # registers (a speculative loser's tee must never
                    # serve) — downstream pipeline maps fetch this
                    # through the same get_map_output endpoints, keyed
                    # off the job id proper so job cleanup can't
                    # collide with the pipeline-scoped lifetime
                    from tpumr.pipeline.handoff import serve_key
                    idx = dict(handoff_out["index"])
                    idx["attempt"] = aid
                    idx["attempt_no"] = task.attempt_id.attempt
                    with self.lock:
                        self.map_outputs[
                            (serve_key(self._job_rebinds.get(job_id,
                                                             job_id)),
                             task.partition)] = (handoff_out["path"],
                                                 idx)
                    self._mreg.incr("handoff_outputs_registered")
            with self.lock:
                killed = aid in self._kill_requested
                # the reaper may have terminally settled this attempt
                # (FAILED/timeout) while the thread finished anyway — a
                # late settle must not resurrect it
                if status.state in TaskState.TERMINAL:
                    return
                status.counters = reporter.counters.to_dict()
                self._note_merge_counters(status.counters)
                status.progress = 1.0
                status.finish_time = time.time()
                if not committed:
                    status.diagnostics = "commit denied: another attempt won"
                    status.state = TaskState.KILLED
                else:
                    status.state = (TaskState.KILLED if killed
                                    else TaskState.SUCCEEDED)
            if status.state == TaskState.SUCCEEDED:
                self._note_device_result(task, None)
        except TaskKilledError:
            with self.lock:
                if status.state in TaskState.TERMINAL:
                    return  # reaped: FAILED/timeout already settled
                status.diagnostics = "attempt killed while running " \
                                     "(preempted or superseded)"
                status.finish_time = time.time()
                status.state = TaskState.KILLED  # requeue, no attempt budget
        except Exception as e:  # noqa: BLE001 — task failure is data
            from tpumr.mapred.task import classify_exception
            with self.lock:
                if status.state in TaskState.TERMINAL:
                    return
                status.diagnostics = f"{type(e).__name__}: {e}\n" + \
                    traceback.format_exc(limit=8)
                status.finish_time = time.time()
                # the demotion/quarantine signal: tagged at the failure
                # site (tpu_runner) or classified generically here
                status.failure_class = classify_exception(e)
                status.state = TaskState.FAILED
            self._note_device_result(task, status.failure_class)

    def _note_device_result(self, task: Task,
                            failure_class: "str | None") -> None:
        """Feed the per-device quarantine: device-classed failures of TPU
        attempts count against their physical device; a success (or any
        non-device failure) breaks the consecutive streak."""
        if (self.device_health is None or not task.is_map
                or not task.run_on_tpu or task.tpu_device_id < 0):
            return
        from tpumr.mapred.task import FailureClass
        dev = task.tpu_device_id % max(1, self.n_tpu_devices)
        if failure_class == FailureClass.DEVICE:
            if self.device_health.record_failure(dev):
                self._mreg.incr("tpu_device_quarantines")
        else:
            self.device_health.record_success(dev)

    # ------------------------------------------------------------ reaper
    # ≈ mapred.task.timeout + TaskTracker.markUnresponsiveTasks: fail
    # attempts that stopped reporting progress. Liveness is OBSERVED, not
    # pushed: the reaper samples each running attempt's progress
    # signature (phase, progress, status line, total counter ticks —
    # from the live in-process Reporter when there is one, else from the
    # umbilical-pushed status) and bumps last_progress on change. An
    # isolated child's unconditional 1 Hz status push therefore does NOT
    # count unless its payload changed, and neither does its kill-poll
    # ping — a hung child is reaped despite both threads staying alive.

    def _progress_signature(self, aid: str, st: TaskStatus,
                            reporter: "Reporter | None") -> tuple:
        if reporter is not None:
            total = sum(c.value for g in reporter.counters for c in g)
            note = reporter.status
            ticks = reporter.ticks
        else:
            total, note, ticks = 0, "", 0
        pushed = sum(v for g in (st.counters or {}).values()
                     for v in g.values()) if st.counters else 0
        with self.lock:
            umb_ticks = self._umb_ticks.get(aid, 0)
        return (st.phase, round(st.progress, 6), note, total, ticks,
                pushed, umb_ticks)

    def _task_timeout_s(self, aid: str) -> float:
        """This attempt's progress timeout (job conf wins over tracker
        conf, tracker conf over the Hadoop default; ≤0 disables —
        mapred.task.timeout contract)."""
        tracker_ms = confkeys.get_int(self.conf, "mapred.task.timeout")
        try:
            job_id = str(TaskAttemptID.parse(aid).task.job)
        except (ValueError, IndexError):
            return tracker_ms / 1000
        with self.lock:
            jc = self.job_confs.get(job_id)
        if jc is None:
            return tracker_ms / 1000
        return jc.get_int("mapred.task.timeout", tracker_ms) / 1000

    def _reaper_wait_s(self) -> float:
        """Poll granularity: a quarter of the SMALLEST live timeout
        (tracker conf and every cached job conf — a job may override
        mapred.task.timeout far below the tracker's), bounded [0.1, 5]s,
        so a tight per-job timeout is enforced near its configured
        value, not at a fixed 5 s grid."""
        smallest = confkeys.get_int(self.conf, "mapred.task.timeout")
        with self.lock:
            confs = list(self.job_confs.values())
        for jc in confs:
            t = jc.get_int("mapred.task.timeout", smallest)
            if 0 < t < smallest or smallest <= 0 < t:
                smallest = t
        if smallest <= 0:
            return 5.0   # reaping disabled everywhere; idle slowly
        return max(0.1, min(5.0, smallest / 1000 / 4.0))

    def _reaper_loop(self) -> None:
        while not self._stop.wait(self._reaper_wait_s()):
            try:
                self._reap_hung_tasks()
            except Exception:  # noqa: BLE001 — the reaper must outlive
                pass           # any one attempt's weirdness

    def _reap_hung_tasks(self) -> "list[str]":
        now = time.monotonic()
        with self.lock:
            snapshot = [(aid, st, self._live_reporters.get(aid))
                        for aid, st in self.running.items()
                        if st.state == TaskState.RUNNING]
        reaped = []
        for aid, st, reporter in snapshot:
            try:
                sig = self._progress_signature(aid, st, reporter)
            except RuntimeError:
                # a counter table grew mid-iteration (live Counters are
                # read lock-free) — a mutating table IS task activity
                with self.lock:
                    self._last_progress[aid] = now
                continue
            with self.lock:
                if self._progress_sigs.get(aid) != sig:
                    self._progress_sigs[aid] = sig
                    self._last_progress[aid] = now
                    continue
                last = self._last_progress.setdefault(aid, now)
            timeout_s = self._task_timeout_s(aid)
            if timeout_s <= 0 or now - last <= timeout_s:
                continue
            if self._reap_one(aid, now - last, timeout_s):
                reaped.append(aid)
        return reaped

    def _reap_one(self, aid: str, silent_s: float,
                  timeout_s: float) -> bool:
        """Terminally fail one silent attempt. The kill mechanics differ
        by isolation: the babysitter SIGKILLs an isolated child's whole
        session via _kill_tree the moment the kill request lands;
        in-process runners see the cooperative cancel flag at their next
        batch/record boundary (a thread cannot be interrupted — the
        settle guards keep a late finisher from resurrecting the
        attempt)."""
        with self.lock:
            st = self.running.get(aid)
            if st is None or st.state in TaskState.TERMINAL:
                return False
            self._kill_requested.add(aid)   # SIGKILL / cooperative cancel
            st.diagnostics = (
                f"Task {aid} failed to report status for "
                f"{silent_s:.0f} seconds (mapred.task.timeout="
                f"{int(timeout_s * 1000)} ms). Killing!")
            from tpumr.mapred.task import FailureClass
            st.failure_class = FailureClass.TIMEOUT
            st.finish_time = time.time()
            st.state = TaskState.FAILED
            task = self.running_tasks.get(aid)
        # a hung in-process thread may never reach _run_task's release
        self._slot_freed()
        self._mreg.incr("tasks_reaped_timeout")
        if task is not None and task.trace is not None:
            try:
                job_id = str(TaskAttemptID.parse(aid).task.job)
                tracer = self._trace_tracer(job_id, task)
                if tracer is not None:
                    tracer.instant("task:reaped", task.trace["trace_id"],
                                   parent=task.trace, attempt_id=aid,
                                   silent_s=round(silent_s, 3))
            except Exception:  # noqa: BLE001 — observability best-effort
                pass
        return True

    #: framework counters rolled up into the /metrics shuffle_merge gauge
    _MERGE_COUNTER_KEYS = ("SHUFFLE_INMEM_MERGES",
                           "SHUFFLE_INMEM_MERGE_SEGMENTS",
                           "MERGE_PASSES", "MERGE_PASS_SEGMENTS",
                           "REDUCE_SHUFFLE_SEGMENTS_MEM",
                           "REDUCE_SHUFFLE_SEGMENTS_DISK")

    def _note_merge_counters(self, counters: "dict | None") -> None:
        """Fold one finished attempt's merge-engine counters into the
        tracker-wide totals behind the ``shuffle_merge`` metrics gauge."""
        if not counters:
            return
        from tpumr.core.counters import TaskCounter
        group = counters.get(TaskCounter.FRAMEWORK_GROUP) or {}
        with self.lock:   # RLock — safe from the umbilical path too
            for key in self._MERGE_COUNTER_KEYS:
                v = int(group.get(key, 0))
                if v:
                    k = key.lower()
                    self._merge_totals[k] = self._merge_totals.get(k, 0) + v

    def _commit(self, conf: JobConf, task: Task) -> bool:
        """Output promotion gated by the master (≈ COMMIT_PENDING →
        CommitTaskAction). Returns False when the grant went to another
        attempt — the caller must report this attempt KILLED, not SUCCEEDED
        (its output was discarded)."""
        from tpumr.core import tracing
        committer = FileOutputCommitter(conf)
        aid = str(task.attempt_id)
        if not committer.needs_commit(aid):
            return True
        with tracing.span("task:commit", attempt_id=aid) as s:
            if self.master.call("can_commit", str(task.task_id), aid):
                committer.commit_task(aid)
                return True
            if s is not None:
                s.set(denied=True)
            committer.abort_task(aid)
            return False

    # ------------------------------------------------------------ profiles
    # ≈ TaskLog.LogName.PROFILE served by TaskLogServlet: per-attempt
    # cProfile reports written by profiler.maybe_profile

    def _list_userlog_attempts(self, filename: str) -> "list[str]":
        """Attempts whose retained userlogs dir holds ``filename``."""
        logs = os.path.join(self.local_root, "userlogs")
        out = []
        if not os.path.isdir(logs):
            return out
        for job_id in sorted(os.listdir(logs)):
            job_dir = os.path.join(logs, job_id)
            if not os.path.isdir(job_dir):
                continue
            for aid in sorted(os.listdir(job_dir)):
                if os.path.exists(os.path.join(job_dir, aid, filename)):
                    out.append(aid)
        return out

    def _open_userlog(self, attempt_id: str, filename: str):
        """Open one attempt's retained file for reading, O(1) and
        symlink-proof. The id is round-tripped through the parser (which
        fully constrains the path — no traversal bytes survive it), and
        the file is opened O_NOFOLLOW: the attempt dir is chowned to the
        task user in setuid mode (_prepare_sandbox_for_user), so a job
        could swap child.log for a symlink and have the root-running
        tracker serve any file on the box (the native controller opens
        its logfile O_NOFOLLOW for the same reason)."""
        import re
        try:
            parsed = TaskAttemptID.parse(attempt_id)
        except (ValueError, IndexError):
            raise KeyError(f"bad attempt id {attempt_id!r}") from None
        if (str(parsed) != attempt_id
                or not re.fullmatch(r"[A-Za-z0-9-]+",
                                    parsed.task.job.cluster)):
            # the cluster segment is free-form text that survives the
            # parse/str roundtrip — without this check "../x" would too
            raise KeyError(f"bad attempt id {attempt_id!r}")
        path = os.path.join(self.local_root, "userlogs",
                            str(parsed.task.job), attempt_id, filename)
        try:
            fd = os.open(path, os.O_RDONLY | os.O_NOFOLLOW)
        except OSError as e:
            raise KeyError(
                f"no {filename} for attempt {attempt_id}: {e}") from None
        return os.fdopen(fd, "rb")

    def list_profiles(self) -> "list[str]":
        from tpumr.mapred.profiler import PROFILE_FILE
        return self._list_userlog_attempts(PROFILE_FILE)

    def get_profile(self, attempt_id: str) -> str:
        from tpumr.mapred.profiler import PROFILE_FILE
        with self._open_userlog(attempt_id, PROFILE_FILE) as f:
            return f.read().decode("utf-8", "replace")

    def list_task_logs(self) -> "list[str]":
        """Attempts with a retained child log (≈ the userlogs listing)."""
        return self._list_userlog_attempts("child.log")

    def get_task_log(self, attempt_id: str,
                     max_bytes: int = 1 << 20) -> str:
        """One attempt's retained stdout/stderr tail (≈ TaskLogServlet;
        tail-bounded like TaskLogsTruncater)."""
        with self._open_userlog(attempt_id, "child.log") as f:
            size = os.fstat(f.fileno()).st_size
            if size > max_bytes:
                f.seek(size - max_bytes)
            return f.read().decode("utf-8", "replace")

    # ------------------------------------------------------------ umbilical
    # child-process task protocol ≈ TaskUmbilicalProtocol (reference:
    # mapred/TaskUmbilicalProtocol.java:65) on the tracker's existing
    # authenticated RPC surface. The child NEVER talks to the master —
    # commit grants and completion events are proxied, like the reference
    # TaskTracker proxies commit/shuffle coordination for its children.

    def umbilical_ping(self, attempt_id: str) -> bool:
        """Kill-poll: True = the tracker wants this attempt gone."""
        self._check_scope(str(TaskAttemptID.parse(attempt_id).task.job))
        with self.lock:
            return attempt_id in self._kill_requested

    def umbilical_status(self, attempt_id: str, d: dict) -> bool:
        """Periodic progress/counter push (≈ statusUpdate). The reaper
        watches the fields written here: a push whose observable payload
        never changes keeps the attempt walking toward
        ``mapred.task.timeout``."""
        self._check_scope(str(TaskAttemptID.parse(attempt_id).task.job))
        with self.lock:
            st = self.running.get(attempt_id)
            if st is None or st.state in TaskState.TERMINAL:
                return False
            st.phase = d.get("phase", st.phase)
            st.progress = float(d.get("progress", st.progress))
            if d.get("counters"):
                st.counters = d["counters"]
            if "ticks" in d:
                self._umb_ticks[attempt_id] = int(d["ticks"])
            return True

    def umbilical_can_commit(self, task_id: str, attempt_id: str) -> bool:
        """Commit-grant proxy (≈ commitPending → JobTracker.canCommit)."""
        attempt = TaskAttemptID.parse(attempt_id)
        if str(TaskID.parse(task_id)) != str(attempt.task):
            # task_id must be the ATTEMPT's OWN task: the master's
            # can_commit setdefaults the grant to the first claimant, so
            # any laxer binding lets an attempt seed a sibling (or
            # foreign) task's grant with an attempt that never fails —
            # permanently denying that task's real attempts
            raise PermissionError(
                f"task {task_id} does not belong to attempt {attempt_id}")
        self._check_scope(str(attempt.task.job))
        return bool(self.master.call("can_commit", task_id, attempt_id))

    def umbilical_events(self, job_id: str, cursor: int) -> list:
        """Map-completion-event proxy for isolated reduce children."""
        self._check_scope(job_id)
        return self.master.call("get_map_completion_events", job_id, cursor)

    def umbilical_done(self, attempt_id: str, final: dict, job_id: str,
                       partition: int, out_path: str, index: dict) -> None:
        """Terminal report (≈ done): settle status, register map output."""
        if str(TaskAttemptID.parse(attempt_id).task.job) != job_id:
            # scope pins to job_id below — the attempt must actually BE
            # that job's, or a scoped caller could settle another job's
            # attempt by mislabeling the job argument
            raise PermissionError(
                f"attempt {attempt_id} does not belong to job {job_id}")
        self._check_scope(job_id)
        with self.lock:
            st = self.running.get(attempt_id)
            if st is not None and st.state not in TaskState.TERMINAL:
                st.counters = final.get("counters", {})
                self._note_merge_counters(st.counters)
                st.progress = float(final.get("progress", 1.0))
                st.phase = final.get("phase", st.phase)
                st.diagnostics = final.get("diagnostics", "")
                st.finish_time = time.time()
                st.state = final.get("state", TaskState.SUCCEEDED)
                # the child still has to exit (its babysitter holds the
                # slot ~0.1 s more); the master can hear of it already
                self._slot_freed()
                if out_path and index:
                    # size-aware shuffle: isolated children report their
                    # output size exactly like in-process attempts do
                    st.output_bytes = sum(
                        int(p[2]) for p in index.get("partitions", ()))
            if out_path:
                # confine served paths to this tracker's scratch tree — the
                # shuffle server must never be steerable at arbitrary files
                real = os.path.realpath(out_path)
                root = os.path.realpath(self.local_root) + os.sep
                if real.startswith(root):
                    idx = dict(index)
                    idx["attempt"] = attempt_id
                    idx["attempt_no"] = TaskAttemptID.parse(
                        attempt_id).attempt
                    self.map_outputs[
                        (self._job_rebinds.get(job_id, job_id),
                         partition)] = (real, idx)

    def umbilical_fail(self, attempt_id: str, state: str,
                       diagnostics: str, failure_class: str = "") -> None:
        """Failure/kill report (≈ fsError/fatalError). ``failure_class``
        carries the child-side classification (task.FailureClass) into
        the heartbeat so the master's demotion/quarantine logic sees
        isolated attempts exactly like in-process ones."""
        self._check_scope(str(TaskAttemptID.parse(attempt_id).task.job))
        with self.lock:
            st = self.running.get(attempt_id)
            if st is not None and st.state not in TaskState.TERMINAL:
                st.diagnostics = diagnostics
                st.finish_time = time.time()
                st.failure_class = str(failure_class or "")
                st.state = (state if state in TaskState.TERMINAL
                            else TaskState.FAILED)
                self._slot_freed()

    # ------------------------------------------------- fetch failures

    def report_fetch_failure(self, reduce_attempt: str,
                             map_attempt: str) -> None:
        """A reduce on this tracker could not fetch ``map_attempt``'s
        output (≈ ReduceTask's fetch-failure notification up the
        umbilical): queue the report for the next heartbeat — the master
        counts distinct reducers per map attempt and re-executes the map
        at ``mapred.max.fetch.failures.per.map``. The reduce stays alive
        (stalled-but-progressing) while that happens."""
        if not map_attempt:
            return   # location never resolved — nothing to indict
        with self.lock:
            self._fetch_failures.append({"reduce_attempt": reduce_attempt,
                                         "map_attempt": map_attempt})
        self._mreg.incr("fetch_failures_reported")

    def umbilical_report_fetch_failure(self, reduce_attempt: str,
                                       map_attempt: str) -> None:
        """Child-process seam for :meth:`report_fetch_failure`. BOTH
        attempts must belong to the caller's job: a job-token child may
        only ever indict its own job's map outputs (the master
        additionally verifies the reducer is a real, running attempt)."""
        reduce_job = str(TaskAttemptID.parse(reduce_attempt).task.job)
        if map_attempt and \
                str(TaskAttemptID.parse(map_attempt).task.job) != reduce_job:
            raise PermissionError(
                f"map attempt {map_attempt} does not belong to "
                f"{reduce_attempt}'s job")
        self._check_scope(reduce_job)
        self.report_fetch_failure(reduce_attempt, map_attempt)

    # ------------------------------------------------------------ shuffle

    def _maybe_fail_serve(self, job_id: str, map_index: int,
                          index: dict) -> None:
        """Deterministic chaos seam on the serving side of the shuffle
        (the map-output-unfetchable failure mode: disk loss, corrupt
        spill, wedged-but-heartbeating tracker). Qualified points let a
        test target one map's output or one attempt GENERATION — e.g.
        ``tpumr.fi.shuffle.serve.a0.probability=1`` makes every map's
        FIRST attempt unfetchable while its re-run serves fine."""
        from tpumr.utils.fi import maybe_fail
        with self.lock:
            conf = self.job_confs.get(job_id)
        conf = conf if conf is not None else self.conf
        maybe_fail("shuffle.serve", conf)
        maybe_fail(f"shuffle.serve.m{map_index}", conf)
        attempt_no = index.get("attempt_no")
        if attempt_no is not None:
            maybe_fail(f"shuffle.serve.a{attempt_no}", conf)

    def _map_output_entry(self, job_id: str,
                          map_index: int) -> "tuple | None":
        """Served-output lookup that follows the recover_job rebinding
        in BOTH directions: entries are re-keyed to the NEW job id when
        the master teaches the rebinding, but reducers ADOPTED across
        the restart keep fetching with the OLD id — both must hit.
        Streamed-handoff keys (``handoff:<job>``) follow the SAME
        rebinding on their embedded job id: downstream pipeline splits
        name the pre-restart upstream id forever, while re-run reduces
        register under the recovered one."""
        from tpumr.pipeline.handoff import SERVE_PREFIX
        rebind = job_id
        if job_id.startswith(SERVE_PREFIX):
            inner = self._job_rebinds.get(job_id[len(SERVE_PREFIX):])
            if inner is not None:
                rebind = SERVE_PREFIX + inner
        with self.lock:
            ent = self.map_outputs.get((job_id, map_index))
            if ent is None and rebind != job_id:
                ent = self.map_outputs.get((rebind, map_index))
            if ent is None:
                new = self._job_rebinds.get(job_id)
                if new is not None:
                    ent = self.map_outputs.get((new, map_index))
        return ent

    def get_map_output(self, job_id: str, map_index: int,
                       partition: int) -> dict:
        """Serve one partition segment (≈ MapOutputServlet,
        TaskTracker.java:4050): raw length-prefixed (possibly compressed)
        bytes straight off the spill file + the codec name."""
        self._check_scope(job_id)
        ent = self._map_output_entry(job_id, map_index)
        if ent is None:
            raise KeyError(f"no map output for {job_id} map {map_index}")
        path, index = ent
        self._maybe_fail_serve(job_id, map_index, index)
        if index.get("dense"):
            raise ValueError(f"map output for {job_id} map {map_index} is "
                             "dense (device-shuffled job) — fetch with "
                             "get_map_output_dense")
        with open(path, "rb") as f:
            data = ifile.partition_bytes(f, index, partition)
        return {"data": data, "codec": index.get("codec", "none")}

    #: server-side cap on one chunk response — bounds tracker memory per
    #: request no matter what the client asks for (the chunked-transfer
    #: half of Missing #6: whole segments never ride one RPC response)
    MAX_CHUNK_BYTES = 4 << 20

    def get_map_output_chunk(self, job_id: str, map_index: int,
                             partition: int, offset: int,
                             max_bytes: int, wire: str = "none") -> dict:
        """Serve one bounded range of a partition segment's compressed
        payload (the streaming re-design of MapOutputServlet,
        TaskTracker.java:4050 — the reference streams via servlet chunked
        output; here each RPC response is one bounded chunk). ``offset``
        is payload-relative; ``total`` is the payload length so the copier
        knows when it has everything; ``raw`` is the decompressed size the
        ShuffleRamManager budgets on. ``wire`` (optional, 6th param so
        old 5-arg callers are untouched) names a codec the CLIENT can
        decode: chunks of uncompressed spills come back wire-compressed
        (response field ``wire``) when it shrinks them, with ``n`` the
        payload-space length covered so offsets stay payload-relative."""
        self._check_scope(job_id)
        path, index = self._chunk_entry(job_id, map_index)
        return serve_chunk(self._spill_fds, path, index, partition,
                           offset, max_bytes, self.MAX_CHUNK_BYTES, wire)

    def get_map_outputs_batch(self, job_id: str, partition: int,
                              map_indexes: "list[int]",
                              max_bytes_each: int = 1 << 20,
                              max_total_bytes: int = 8 << 20,
                              wire: str = "none") -> "list[dict]":
        """Batched multi-segment fetch: many (small) map outputs of one
        partition in ONE response frame — see :func:`serve_batch` for
        the per-entry failure / budget-omission / prefix-continuation
        contract. The per-entry fault seam fires INSIDE the batch, so a
        chaos-killed map fails its own entry while siblings land."""
        self._check_scope(job_id)

        def lookup(m: int) -> tuple:
            return self._chunk_entry(job_id, m)

        return serve_batch(
            self._spill_fds, lookup, partition, list(map_indexes),
            min(int(max_bytes_each), self.MAX_CHUNK_BYTES),
            min(int(max_total_bytes), 8 * self.MAX_CHUNK_BYTES),
            self.MAX_CHUNK_BYTES, wire)

    def _chunk_entry(self, job_id: str, map_index: int) -> tuple:
        """(path, index) of one chunk-servable output, with the lookup
        failure + chaos seam + dense guard shared by the chunk and
        batch endpoints."""
        ent = self._map_output_entry(job_id, map_index)
        if ent is None:
            raise KeyError(f"no map output for {job_id} map {map_index}")
        path, index = ent
        self._maybe_fail_serve(job_id, map_index, index)
        if index.get("dense"):
            raise ValueError(f"map output for {job_id} map {map_index} is "
                             "dense (device-shuffled job) — fetch with "
                             "get_map_output_dense")
        return path, index

    def get_map_output_dense(self, job_id: str, map_index: int) -> dict:
        """Serve a device-shuffled job's whole dense map output (same
        MapOutputServlet role; the exchange itself happens on the mesh).
        Ships the self-describing file verbatim — no parse/reserialize."""
        self._check_scope(job_id)
        return {"data": self._dense_output_bytes(job_id, map_index)}

    def _dense_output_bytes(self, job_id: str, map_index: int) -> bytes:
        """The dense file this tracker serves for one map, whole; what a
        lookup or a read that fails raises is the same for the RPC and
        for the gang reduce's own read."""
        ent = self._map_output_entry(job_id, map_index)
        if ent is None:
            raise KeyError(f"no map output for {job_id} map {map_index}")
        path, index = ent
        if not index.get("dense"):
            raise ValueError(f"map output for {job_id} map {map_index} is "
                             "not dense — fetch with get_map_output")
        with open(path, "rb") as f:
            return f.read()

    def _map_locator(self, job_id: str):
        """Resolve a map's serving tracker from the master's completion
        events (shared by the IFile and dense fetch paths): returns
        ``locate(map_index) -> RpcClient`` to the source tracker."""
        return make_map_locator(
            lambda cursor: self.master.call("get_map_completion_events",
                                            job_id, cursor),
            self._rpc_secret,
            poll_s=self.conf.get_int("tpumr.shuffle.poll.ms", 200) / 1000.0,
            timeout_s=self.conf.get_int("tpumr.shuffle.timeout.ms",
                                        600_000) / 1000.0,
            conns_per_target=confkeys.get_int(
                self.conf, "tpumr.shuffle.conns.per.target"))

    def _handoff_source(self, upstream_job: str):
        """Shared per-upstream-stage stream source for downstream
        pipeline maps (the `tpumr.pipeline.handoff.source` conf seam):
        the PR-1 MapLocator over the master's HANDOFF completion-event
        feed, authenticated with this tracker's credentials. Cached —
        every map of the downstream stage on this tracker folds one
        cursor instead of N."""
        with self.lock:
            src = self._handoff_sources.get(upstream_job)
        if src is not None:
            return src
        from tpumr.pipeline.handoff import make_handoff_source
        src = make_handoff_source(
            upstream_job,
            lambda cursor: self.master.call(
                "get_handoff_completion_events", upstream_job, cursor),
            self._rpc_secret,
            poll_s=self.conf.get_int("tpumr.shuffle.poll.ms",
                                     200) / 1000.0)
        with self.lock:
            return self._handoff_sources.setdefault(upstream_job, src)

    def _remote_fetch_factory(self, job_id: str, task: Task):
        """Chunked shuffle source ≈ ReduceCopier.MapOutputCopier: resolves
        map locations from completion events; run_reduce_task drives it
        with the parallel RAM-budgeted ShuffleCopier."""
        from tpumr.mapred.shuffle_copier import RemoteChunkSource
        src = RemoteChunkSource(self._job_conf(job_id), job_id,
                                self._map_locator(job_id))
        reduce_attempt = str(task.attempt_id)
        src.on_fetch_failure = (
            lambda map_index, map_attempt:
            self.report_fetch_failure(reduce_attempt, map_attempt))
        return src

    def _remote_dense_fetch_factory(self, job_id: str, task: Task,
                                    reporter: Any):
        """Dense fetch for device-shuffled jobs: each map's whole
        fixed-width output. A map that THIS tracker serves (the address
        in its completion event is this tracker's own) is read from its
        file; any other is pulled from the tracker that serves it (same
        serving seam, array payload)."""
        from tpumr.core import tracing
        from tpumr.core.counters import BackendCounter
        from tpumr.mapred.device_shuffle import parse_dense_bytes

        locate = self._map_locator(job_id)

        def fetch(map_index: int):
            # apart, so that waiting for a map to finish (the 200 ms poll
            # of the master's completion events) is not read as copying
            with tracing.span("dshuffle:locate", map_index=map_index):
                source = locate(map_index)
            # by address, not by process: a mini cluster runs several
            # trackers in one
            local = source.addr == self.shuffle_addr
            with tracing.span("dshuffle:fetch", map_index=map_index,
                              local=local) as sp:
                if local:
                    data = self._dense_output_bytes(job_id, map_index)
                else:
                    data = source.call("get_map_output_dense", job_id,
                                       map_index)["data"]
                # by 0 too: the counter is in every gang reduce's rollup
                reporter.incr_counter(BackendCounter.GROUP,
                                      BackendCounter.TPU_SHUFFLE_LOCAL_MAPS,
                                      int(local))
                if sp is not None:
                    sp.set(bytes=len(data))
                return parse_dense_bytes(data)

        return fetch
