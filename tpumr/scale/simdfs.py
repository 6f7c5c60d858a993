"""Simulated DFS clients: real DFSClient traffic, synthetic workload.

``SimTracker``'s storage twin: where the scale lab's ``SimFleet`` beats
a real JobTracker with the real heartbeat protocol, ``SimDFSFleet``
drives a real NameNode + DataNodes with real ``DFSClient`` instances —
every namespace op is a genuine RPC through the instrumented
``NameNode._op`` seam, every block read moves real bytes off a real
DataNode (and into its SpaceSaving hot-block sketch). Nothing is
mocked, so what a rung measures is the actual serving stack.

The workload is the mix a MapReduce cluster's storage layer sees:

- **reads dominate** and are SKEWED — with probability ``hot_read_p``
  a client reads the designated hot file (everyone's job config /
  shared side input), otherwise a uniform draw over the working set.
  The skew is what makes ``/hotblocks`` testable: the hot file's
  block must surface as the cluster-wide top entry.
- **metadata ops** (exists / get_status / list_status) — the
  lightweight chatter of job setup and polling.
- **writes** roll small per-client files (task output commit), with
  renames and deletes bounding each client's namespace footprint —
  so create/complete/rename/delete all show op latency under load.

``SimDFSFleet`` schedules N clients from a bounded worker pool on a
fixed-rate heap (same skeleton as ``SimFleet``): each client has a due
time every ``interval_s``; the due-vs-actual gap is the client-side
scheduling lag, and per-op round trips are the client-side latency
view a rung's row sets beside the NameNode's own ``nn_op_seconds``
attribution.
"""

from __future__ import annotations

import heapq
import random
import threading
import time
from typing import Any

from tpumr.dfs.client import DFSClient
from tpumr.metrics.core import MetricsRegistry
from tpumr.metrics.histogram import Histogram

#: op mix (weights, normalized at draw time): reads dominate, metadata
#: chatter second, a steady trickle of write/rename/delete churn
DEFAULT_MIX = (("read", 0.66), ("stat", 0.18), ("write", 0.10),
               ("rename", 0.03), ("delete", 0.03))

#: the seeded working-set payload is ``bytes(range(256))`` repeated, so
#: byte ``i`` of every file is ``i % 256`` — a read of any prefix is
#: verifiable without shipping the expectation around
_PAYLOAD_TEMPLATE = bytes(range(256))


class CorruptReadError(IOError):
    """A verified read returned bytes that differ from the seeded
    payload — the checksum/bad-block-report defense FAILED and rot
    reached an application. The one counter that must stay at zero
    under ``block_corrupt`` chaos."""


def seed_files(nn_host: str, nn_port: int, conf: Any = None,
               n_files: int = 8, file_bytes: int = 1 << 18,
               root: str = "/bench/data") -> "list[str]":
    """Create the shared read working set (``f_0`` is the hot file).
    Returns the paths. Idempotent: existing files are reused so a
    ramp's later rungs don't re-write the set."""
    cli = DFSClient(nn_host, nn_port, conf)
    try:
        cli.mkdirs(root)
        paths = []
        payload = bytes(range(256)) * (max(1, file_bytes) // 256 + 1)
        for i in range(n_files):
            path = f"{root}/f_{i}"
            if not cli.exists(path):
                # replication=2 on a 3-DN rung leaves the hot-block
                # policy headroom to prove itself: the hot file's
                # replica count visibly climbs 2 -> 3 under skew
                with cli.create(path, replication=2) as out:
                    out.write(payload[:file_bytes])
            paths.append(path)
        return paths
    finally:
        close_client(cli)


def close_client(cli: DFSClient) -> None:
    """Drop the client's sockets (renewer, NN conn, DN pool) so a
    ramp's retired rungs don't leak fds into the next."""
    try:
        cli.close()
    except Exception:  # noqa: BLE001
        pass


class SimDFSClient:
    """One synthetic client: a real ``DFSClient`` plus a seeded op
    generator. ``step()`` performs exactly one operation drawn from
    the mix and returns ``(op, bytes_read)``."""

    def __init__(self, name: str, nn_host: str, nn_port: int,
                 conf: Any = None, *,
                 files: "list[str] | None" = None,
                 hot_read_p: float = 0.5,
                 read_bytes: int = 1 << 16,
                 mix: "tuple | None" = None,
                 home: str = "/user",
                 verify: bool = False,
                 rng: "random.Random | None" = None) -> None:
        self.name = name
        self.cli = DFSClient(nn_host, nn_port, conf)
        self.files = list(files or [])
        self.hot_read_p = float(hot_read_p)
        self.read_bytes = int(read_bytes)
        # verify=True checks every working-set read against the seeded
        # seed_files payload (byte i == i % 256) and raises
        # CorruptReadError on mismatch — the block_corrupt invariant
        self.verify = bool(verify)
        self._expected = (_PAYLOAD_TEMPLATE
                          * (self.read_bytes // 256 + 1))[
                              :self.read_bytes] if verify else b""
        self.mix = tuple(mix or DEFAULT_MIX)
        self._weights = [w for _op, w in self.mix]
        self._rng = rng or random.Random(hash(name) & 0xFFFFFFFF)
        # /user/<name>/... gives every client its own depth-2 stripe
        # prefix, so write/rename/delete churn spreads across the
        # namenode's striped locks instead of serializing on one
        self.home = f"{home}/{name}"
        # the directory the listing op sweeps: the working set's own
        # parent (NOT a hardcoded root — the scenario lab seeds under a
        # different tree than a ``simulate -dfs`` rung)
        self._data_root = (self.files[0].rsplit("/", 1)[0] or "/") \
            if self.files else "/"
        self._made_home = False
        self._seq = 0
        self._mine: "list[str]" = []   # my rolled files, oldest first
        self.ops = 0
        self.stopped = False

    def step(self) -> "tuple[str, int]":
        op = self._rng.choices([o for o, _w in self.mix],
                               weights=self._weights)[0]
        n = getattr(self, f"_op_{op}")()
        self.ops += 1
        return op, n

    # ------------------------------------------------------------ ops

    def _op_read(self) -> int:
        if not self.files:
            return 0
        # the skew: hot file with probability hot_read_p, else uniform
        if self._rng.random() < self.hot_read_p:
            path = self.files[0]
        else:
            path = self._rng.choice(self.files)
        with self.cli.open(path) as f:
            data = f.read(self.read_bytes)
        if self.verify and data != self._expected[:len(data)]:
            raise CorruptReadError(
                f"{self.name}: {path} returned {len(data)} bytes that "
                f"do not match the seeded payload")
        return len(data)

    def _op_stat(self) -> int:
        which = self._rng.randrange(3)
        if which == 0:
            self.cli.exists(self.files[0] if self.files else "/")
        elif which == 1 and self.files:
            self.cli.get_status(self._rng.choice(self.files))
        else:
            self.cli.list_status(self._data_root)
        return 0

    def _op_write(self) -> int:
        if not self._made_home:
            self.cli.mkdirs(self.home)
            self._made_home = True
        self._seq += 1
        path = f"{self.home}/w_{self._seq}.dat"
        with self.cli.create(path) as out:
            out.write(b"x" * 4096)
        self._mine.append(path)
        # bound the per-client namespace footprint (and generate
        # steady delete traffic): at most 8 rolled files live
        if len(self._mine) > 8:
            self.cli.delete(self._mine.pop(0))
        return 0

    def _op_rename(self) -> int:
        if not self._mine:
            return self._op_write()
        src = self._mine.pop(self._rng.randrange(len(self._mine)))
        dst = src + ".r"
        if self.cli.rename(src, dst):
            self._mine.append(dst)
        return 0

    def _op_delete(self) -> int:
        if not self._mine:
            return self._op_stat()
        self.cli.delete(self._mine.pop(0))
        return 0

    def close(self) -> None:
        self.stopped = True
        close_client(self.cli)


class SimDFSFleet:
    """N ``SimDFSClient``s on a fixed-rate op schedule, driven by a
    bounded worker pool (the ``SimFleet`` skeleton: due-time heap,
    staggered start, skip-ahead when saturated)."""

    def __init__(self, nn_host: str, nn_port: int, n_clients: int,
                 conf: Any = None, *, interval_s: float = 0.05,
                 workers: "int | None" = None, seed: int = 0,
                 name_prefix: str = "sdfs",
                 **client_kwargs: Any) -> None:
        self.nn_host, self.nn_port = nn_host, int(nn_port)
        self.conf = conf
        self.n = int(n_clients)
        self.interval_s = float(interval_s)
        self.workers = workers or min(32, max(4, self.n // 2))
        self._prefix = name_prefix
        self._seed = seed
        self._client_kwargs = client_kwargs
        self.clients: "list[SimDFSClient]" = []
        self._heap: "list[tuple[float, int]]" = []
        self._cv = threading.Condition()
        self._stop = threading.Event()
        self._threads: "list[threading.Thread]" = []
        # the client-side view, independent of the NameNode's own
        # nn_op_seconds attribution: read round trips (the end-to-end
        # NN-locate + DN-fetch path), metadata/write round trips, and
        # schedule lag (how far behind the intended op rate we run)
        self.registry = MetricsRegistry("simdfs")
        self._read_rtt = self.registry.histogram("dfs_read_rtt_seconds")
        self._meta_rtt = self.registry.histogram("dfs_meta_rtt_seconds")
        self._lag = self.registry.histogram("op_lag_seconds")
        self.bytes_read = 0
        self.op_counts: "dict[str, int]" = {}

    def start(self) -> "SimDFSFleet":
        rng = random.Random(self._seed)
        for i in range(self.n):
            self.clients.append(SimDFSClient(
                f"{self._prefix}_{i:04d}", self.nn_host, self.nn_port,
                self.conf, rng=random.Random(rng.randrange(1 << 30)),
                **self._client_kwargs))
        now = time.monotonic()
        # stagger first ops across one interval: fleet start must not
        # land as a synchronized herd unless saturation makes it one
        self._heap = [(now + (i * self.interval_s) / max(1, self.n), i)
                      for i in range(self.n)]
        heapq.heapify(self._heap)
        for w in range(self.workers):
            t = threading.Thread(target=self._worker,
                                 name=f"{self._prefix}-fleet-{w}",
                                 daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def _worker(self) -> None:
        while not self._stop.is_set():
            with self._cv:
                while not self._stop.is_set():
                    now = time.monotonic()
                    if self._heap and self._heap[0][0] <= now:
                        due, idx = heapq.heappop(self._heap)
                        break
                    wait = (self._heap[0][0] - now) if self._heap \
                        else 0.05
                    self._cv.wait(min(max(wait, 0.0), 0.05))
                else:
                    return
            self._lag.observe(max(0.0, time.monotonic() - due))
            client = self.clients[idx]
            if client.stopped:
                continue
            t0 = time.monotonic()
            try:
                op, nbytes = client.step()
            except CorruptReadError:
                self.registry.incr("dfs_corrupt_reads")
                op, nbytes = "corrupt_read", 0
            except Exception as e:  # noqa: BLE001 — NN/DN down or overloaded
                if "safe mode" in str(e).lower():
                    # a freshly restarted NameNode refusing ops until
                    # block reports land: an availability event, not a
                    # data error — budgeted separately (the SLO is
                    # time-to-safemode-exit, judged by the scenario)
                    self.registry.incr("dfs_safemode_refusals")
                    op, nbytes = "safemode", 0
                else:
                    self.registry.incr("dfs_errors")
                    op, nbytes = "error", 0
            else:
                rtt = time.monotonic() - t0
                (self._read_rtt if op == "read"
                 else self._meta_rtt).observe(rtt)
            with self._cv:
                self.bytes_read += nbytes
                self.op_counts[op] = self.op_counts.get(op, 0) + 1
                if not client.stopped and not self._stop.is_set():
                    # fixed-rate against the intended cadence; when a
                    # full interval behind, skip ahead (the lag was
                    # recorded — queueing missed ops would spiral)
                    nxt = due + self.interval_s
                    now = time.monotonic()
                    if nxt <= now:
                        nxt = now + self.interval_s
                    heapq.heappush(self._heap, (nxt, idx))
                self._cv.notify()

    def stop(self) -> None:
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=5.0)
        for c in self.clients:
            c.close()

    # ------------------------------------------------------------ read side

    def stats(self) -> dict:
        """Client-side summary for one measurement window's rung."""
        snap = self.registry.snapshot()
        with self._cv:
            counts = dict(self.op_counts)
            bytes_read = self.bytes_read
        return {
            "ops": sum(c.ops for c in self.clients),
            "op_counts": counts,
            "bytes_read": bytes_read,
            "errors": snap.get("dfs_errors", 0),
            "corrupt_reads": snap.get("dfs_corrupt_reads", 0),
            "safemode_refusals": snap.get("dfs_safemode_refusals", 0),
            "read_rtt": snap.get("dfs_read_rtt_seconds",
                                 Histogram("x").snapshot()),
            "meta_rtt": snap.get("dfs_meta_rtt_seconds",
                                 Histogram("x").snapshot()),
            "lag": snap.get("op_lag_seconds",
                            Histogram("x").snapshot()),
        }


# ---------------------------------------------------------------- harness


def _p(h: "dict | None", q: str) -> float:
    return float((h or {}).get(q, 0.0))


def run_dfs_step(n_clients: int, *, conf: Any = None,
                 interval_s: float = 0.05, measure_s: float = 6.0,
                 num_datanodes: int = 3, n_files: int = 8,
                 file_bytes: int = 1 << 18, hot_read_p: float = 0.5,
                 read_bytes: int = 1 << 16, seed: int = 0,
                 prom_out: "str | None" = None,
                 hot_top_n: int = 8) -> dict:
    """One DFS saturation rung: a FRESH in-process MiniDFSCluster, a
    fleet of ``n_clients`` real DFSClients on a fixed op cadence for
    ``measure_s``, then one joined snapshot of both sides — the
    NameNode's own op/lock/editlog attribution and the fleet's
    client-side round trips. ``tpumr simulate -dfs`` runs one rung.

    ``prom_out`` additionally scrapes the NameNode's live
    ``/metrics/prom`` at the end of the window and writes the body
    there (the CI artifact proving the exposition renders under load).
    """
    from tpumr.dfs.mini_cluster import MiniDFSCluster
    from tpumr.mapred.jobconf import JobConf

    conf = conf or JobConf()
    # the scrape/hotblocks surface rides the rung on an ephemeral port
    conf.set_if_unset("tdfs.http.port", 0)
    t0 = time.monotonic()
    with MiniDFSCluster(num_datanodes, conf=conf) as cluster:
        files = seed_files(cluster.nn_host, cluster.nn_port, conf,
                           n_files=n_files, file_bytes=file_bytes)
        nn = cluster.namenode
        fleet = SimDFSFleet(cluster.nn_host, cluster.nn_port, n_clients,
                            conf, interval_s=interval_s, seed=seed,
                            files=files, hot_read_p=hot_read_p,
                            read_bytes=read_bytes).start()
        try:
            time.sleep(measure_s)
        finally:
            fleet.stop()
        # let the last datanode heartbeats land so the cluster
        # hot-block table holds every sketch slice
        from tpumr.core import confkeys
        time.sleep(2 * confkeys.get_float(
            conf, "tdfs.datanode.heartbeat.s") + 0.1)
        wall = time.monotonic() - t0
        fl = fleet.stats()
        snap = nn.metrics.snapshot()
        reg = snap.get("namenode", {})
        merged = Histogram("nn_op_seconds")
        for h in nn._op_hists.values():
            merged.merge_typed(h.typed())
        ops_merged = merged.snapshot()
        hot_top = nn.ns.get_hot_blocks(hot_top_n)
        hot_total = nn.ns.hot_blocks.total_reads()
        row = {
            "clients": n_clients,
            "interval_s": interval_s,
            "wall_s": round(wall, 3),
            "ops": fl["ops"],
            "op_counts": fl["op_counts"],
            "errors": int(fl["errors"]),
            "completed": int(fl["errors"]) == 0,
            # the NameNode's own attribution (nn_op_seconds merged
            # across every op family, plus the per-op p99 map)
            "nn_op_count": int(_p(ops_merged, "count")),
            "nn_op_p50_s": round(_p(ops_merged, "p50"), 6),
            "nn_op_p99_s": round(_p(ops_merged, "p99"), 6),
            "nn_op_p99_by_op": {
                op: round(_p(h.snapshot(), "p99"), 6)
                for op, h in sorted(nn._op_hists.items())},
            # the striped namenode reports three lock families
            # (namespace = structural/global, namespace-stripe,
            # namespace-blocks); the headline wait/hold p99 is the
            # worst family — the one gating op latency at this rung
            "lock_wait_p99_s": round(max(
                (_p(h, "p99") for k, h in reg.items()
                 if k.startswith("nn_lock_wait_seconds|")),
                default=0.0), 6),
            "lock_hold_p99_s": round(max(
                (_p(h, "p99") for k, h in reg.items()
                 if k.startswith("nn_lock_hold_seconds|")),
                default=0.0), 6),
            "lock_wait_p99_by_lock": {
                k.split("lock=", 1)[1]: round(_p(h, "p99"), 6)
                for k, h in sorted(reg.items())
                if k.startswith("nn_lock_wait_seconds|")},
            "editlog_sync_p99_s": round(_p(reg.get(
                "nn_editlog_sync_seconds"), "p99"), 6),
            # fsyncs absorbed per group commit: mean ops covered by
            # one sync (1.0 = no batching; >1 = the editlog is
            # coalescing concurrent mutations into shared fsyncs)
            "editlog_group_ops_mean": round(_p(reg.get(
                "nn_editlog_group_ops"), "mean"), 3),
            # data-plane throughput + tails, both sides
            "read_mb_s": round(fl["bytes_read"] / wall / 1e6, 3),
            "read_rtt_p50_s": round(_p(fl["read_rtt"], "p50"), 6),
            "read_rtt_p99_s": round(_p(fl["read_rtt"], "p99"), 6),
            "meta_rtt_p99_s": round(_p(fl["meta_rtt"], "p99"), 6),
            "lag_p99_s": round(_p(fl["lag"], "p99"), 6),
            "dn_read_p99_s": round(max(
                (_p(dn.metrics.snapshot().get("datanode", {})
                    .get("dn_read_seconds"), "p99")
                 for dn in cluster.datanodes), default=0.0), 6),
            # hot-block skew: share of all sketched reads landing on
            # the cluster-wide top block (the /hotblocks headline)
            "hot_total_reads": hot_total,
            "hot_top": [{"block": r["block"], "path": r.get("path", ""),
                         "reads": r["reads"],
                         "replicas": r.get("replicas", 0),
                         "boost": r.get("boost", 0)}
                        for r in hot_top[:3]],
            "hot_top1_share": round(
                hot_top[0]["reads"] / hot_total, 4)
                if hot_top and hot_total else 0.0,
            # the auto-replication receipt: the top block's live
            # replica count and the boost the policy assigned it
            "hot_top1_replicas": int(hot_top[0].get("replicas", 0))
                if hot_top else 0,
            "hot_top1_boost": int(hot_top[0].get("boost", 0))
                if hot_top else 0,
        }
        # lock wait p99 as a share of op p99: ~1.0 means the namespace
        # lock IS the op latency (the saturation signature the
        # fine-grained-locking roadmap item would have to move)
        p99 = row["nn_op_p99_s"]
        row["lock_wait_share"] = round(
            row["lock_wait_p99_s"] / p99, 3) if p99 > 0 else 0.0
        if prom_out and nn.http_url:
            from urllib.request import urlopen
            with urlopen(f"{nn.http_url}/metrics/prom",
                         timeout=10) as resp:
                body = resp.read()
            with open(prom_out, "wb") as f:
                f.write(body)
        return row
