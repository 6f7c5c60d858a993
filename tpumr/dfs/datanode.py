"""DataNode — checksummed block storage + pipelined transfer.

≈ ``org.apache.hadoop.hdfs.server.datanode.{DataNode,DataXceiver,
FSDataset,BlockReceiver,BlockSender}`` (reference: DataNode.java 2133 LoC).
Contracts reproduced:

- blocks live as ``blk_<id>`` files with a sidecar ``.meta`` of per-chunk
  CRC32s (≈ the checksum meta file); reads verify and raise on corruption
  (ChecksumException), which also triggers client replica failover;
- write pipeline: the client streams a block to the FIRST target in
  bounded chunks (open/write_chunk/commit), each node forwards
  downstream then appends, acks propagate back up the chain
  (DN→DN→DN chained pipeline of BlockReceiver; ≈ DataTransferProtocol
  WRITE_BLOCK). Reads stream the same way (read_block_chunk ≈
  BlockSender) with chunk-aligned checksum verification — whole blocks
  never ride one RPC payload in either direction;
- heartbeat loop: register → initial block report → periodic heartbeats
  that carry back NameNode commands (replicate/delete ≈
  DNA_TRANSFER/DNA_INVALIDATE), full block reports on request/interval.
"""

from __future__ import annotations

import json
import os
import threading
import time
import zlib
from typing import Any

from tpumr.io import compress
from tpumr.io.fdcache import FdCache
from tpumr.ipc.rpc import RpcClient, RpcServer

CHUNK = 64 * 1024


class ChecksumError(IOError):
    pass


class BlockStore:
    """On-disk block files + chunk checksums (≈ FSDataset).

    The read path is served from a pinned-LRU fd cache (tpumr.io.fdcache,
    the shuffle server's engine) plus an in-memory meta cache: a block
    streamed out as N chunks used to cost N×(open block + open/parse
    .meta) — now chunk 2..N is one ``pread`` and a dict hit. Every
    mutation (write/finalize/abort/delete) invalidates both caches:
    ``os.replace`` swaps the inode under the path, and a cached fd would
    otherwise keep serving the OLD block's bytes forever."""

    def __init__(self, data_dir: str, fd_capacity: int = 64) -> None:
        self.dir = data_dir
        os.makedirs(data_dir, exist_ok=True)
        self._fds = FdCache(capacity=fd_capacity)
        #: block_id -> parsed .meta ({"len", "sums"}); bounded by the
        #: same capacity as the fd cache (metas are ~the hot set)
        self._meta: "dict[int, dict]" = {}
        self._meta_mu = threading.Lock()
        self._meta_cap = max(16, int(fd_capacity) * 4)

    def _path(self, block_id: int) -> str:
        return os.path.join(self.dir, f"blk_{block_id}")

    def _invalidate(self, block_id: int) -> None:
        """Drop cached fd + meta for one block (call on ANY mutation)."""
        self._fds.invalidate(self._path(block_id))
        with self._meta_mu:
            self._meta.pop(block_id, None)

    def _load_meta(self, block_id: int) -> dict:
        with self._meta_mu:
            meta = self._meta.get(block_id)
        if meta is not None:
            return meta
        with open(self._path(block_id) + ".meta") as f:
            meta = json.load(f)
        with self._meta_mu:
            while len(self._meta) >= self._meta_cap:
                self._meta.pop(next(iter(self._meta)))
            self._meta[block_id] = meta
        return meta

    def write(self, block_id: int, data: bytes) -> None:
        sums = [zlib.crc32(data[i:i + CHUNK])
                for i in range(0, max(len(data), 1), CHUNK)]
        tmp = self._path(block_id) + ".tmp"
        with open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        with open(tmp + ".meta", "w") as f:
            json.dump({"len": len(data), "sums": sums}, f)
        os.replace(tmp + ".meta", self._path(block_id) + ".meta")
        os.replace(tmp, self._path(block_id))
        self._invalidate(block_id)

    def read(self, block_id: int, offset: int = 0,
             length: int = -1) -> bytes:
        path = self._path(block_id)
        if not os.path.exists(path):
            raise FileNotFoundError(f"block {block_id} not stored here")
        with open(path, "rb") as f:
            data = f.read()
        with open(path + ".meta") as f:
            meta = json.load(f)
        sums = [zlib.crc32(data[i:i + CHUNK])
                for i in range(0, max(len(data), 1), CHUNK)]
        if meta["len"] != len(data) or meta["sums"] != sums:
            raise ChecksumError(f"block {block_id} fails checksum")
        if length < 0:
            length = len(data) - offset
        return data[offset:offset + length]

    def read_range(self, block_id: int, offset: int,
                   length: int) -> "tuple[bytes, int]":
        """Range read verifying ONLY the covering checksum chunks (the
        reference's chunk-aligned verification in BlockSender): a
        streaming reader never re-reads or re-hashes the whole block
        per chunk. Served via the fd/meta caches — a multi-chunk stream
        pays one open + one meta parse total, then a ``pread`` per
        chunk (stateless, so the reactor's pool threads serve many
        clients off the same fd concurrently). Returns
        (data, block_length)."""
        path = self._path(block_id)
        try:
            meta = self._load_meta(block_id)
        except FileNotFoundError:
            raise FileNotFoundError(
                f"block {block_id} not stored here") from None
        total = meta["len"]
        offset = max(0, offset)
        length = max(0, min(length, total - offset))
        if length == 0:
            return b"", total
        c0 = offset // CHUNK
        c1 = (offset + length - 1) // CHUNK
        try:
            covering = self._fds.pread(
                path, (c1 - c0 + 1) * CHUNK, c0 * CHUNK)
        except FileNotFoundError:
            # meta cached but block deleted under us: drop stale meta
            self._invalidate(block_id)
            raise FileNotFoundError(
                f"block {block_id} not stored here") from None
        sums = [zlib.crc32(covering[i:i + CHUNK])
                for i in range(0, len(covering), CHUNK)]
        if sums != meta["sums"][c0:c1 + 1]:
            raise ChecksumError(f"block {block_id} fails checksum "
                                f"(chunks {c0}..{c1})")
        lo = offset - c0 * CHUNK
        return covering[lo:lo + length], total

    # ------------------------------------------------ streaming receive

    def open_stream(self, block_id: int) -> str:
        """Begin a streamed block write: appends go to the .tmp file,
        finalize_stream checksums + atomically installs it."""
        tmp = self._path(block_id) + ".tmp"
        open(tmp, "wb").close()
        return tmp

    def append_stream(self, block_id: int, data: bytes) -> None:
        with open(self._path(block_id) + ".tmp", "ab") as f:
            f.write(data)

    def finalize_stream(self, block_id: int) -> int:
        """Compute chunk CRCs from the streamed file (one bounded-memory
        re-read), fsync, install block + meta. Returns the length."""
        tmp = self._path(block_id) + ".tmp"
        sums = []
        total = 0
        with open(tmp, "rb") as f:
            while True:
                piece = f.read(CHUNK)
                if not piece and total > 0:
                    break
                sums.append(zlib.crc32(piece))
                total += len(piece)
                if len(piece) < CHUNK:
                    break
        with open(tmp, "ab") as f:
            f.flush()
            os.fsync(f.fileno())
        with open(tmp + ".meta", "w") as f:
            json.dump({"len": total, "sums": sums}, f)
        os.replace(tmp + ".meta", self._path(block_id) + ".meta")
        os.replace(tmp, self._path(block_id))
        self._invalidate(block_id)
        return total

    def abort_stream(self, block_id: int) -> None:
        for suffix in (".tmp", ".tmp.meta"):
            try:
                os.remove(self._path(block_id) + suffix)
            except FileNotFoundError:
                pass

    def delete(self, block_id: int) -> None:
        self._invalidate(block_id)
        for suffix in ("", ".meta"):
            try:
                os.remove(self._path(block_id) + suffix)
            except FileNotFoundError:
                pass

    def corrupt_replica(self, block_id: int) -> bool:
        """Flip one byte mid-file in the ON-DISK replica (chaos/test
        hook — the ``block_corrupt`` scenario's bit-rot model). The
        sidecar .meta is left intact, so the next read or scanner pass
        fails CRC verification exactly like real disk rot. Caches are
        invalidated so the flip is visible immediately, not after the
        cached fd ages out. Returns False when the block isn't here."""
        path = self._path(block_id)
        try:
            size = os.path.getsize(path)
        except OSError:
            return False
        off = size // 2
        fd = os.open(path, os.O_RDWR)
        try:
            old = os.pread(fd, 1, off)
            if not old:
                return False
            os.pwrite(fd, bytes([old[0] ^ 0xFF]), off)
            os.fsync(fd)
        finally:
            os.close(fd)
        self._invalidate(block_id)
        return True

    def blocks(self) -> list[tuple[int, int]]:
        out = []
        for name in os.listdir(self.dir):
            if name.startswith("blk_") and not name.endswith(".meta") \
                    and not name.endswith(".tmp"):
                bid = int(name[4:])
                out.append((bid, os.path.getsize(os.path.join(self.dir,
                                                              name))))
        return out

    def used(self) -> int:
        return sum(size for _, size in self.blocks())


class DataNode:
    def __init__(self, nn_host: str, nn_port: int, data_dir: str,
                 conf: Any, host: str = "127.0.0.1", port: int = 0) -> None:
        self.conf = conf
        self.store = BlockStore(
            data_dir,
            fd_capacity=int(conf.get("tdfs.datanode.fdcache.capacity",
                                     64)))
        from tpumr.security import rpc_secret
        self._secret = rpc_secret(conf)
        self.nn = RpcClient(nn_host, nn_port, secret=self._secret)
        self.capacity = int(conf.get("tdfs.datanode.capacity",
                                     1 << 40))
        self.heartbeat_s = float(conf.get("tdfs.datanode.heartbeat.s", 1.0))
        # block read/write path metrics — byte + latency distributions
        # and a live concurrent-reader gauge
        from tpumr.metrics import MetricsSystem
        from tpumr.metrics.histogram import BYTES
        self.metrics = MetricsSystem("datanode")
        self._mreg = self.metrics.new_registry("datanode")
        self._read_bytes = self._mreg.histogram("dn_read_bytes",
                                                bounds=BYTES)
        self._read_seconds = self._mreg.histogram("dn_read_seconds")
        self._write_bytes = self._mreg.histogram("dn_write_bytes",
                                                 bounds=BYTES)
        self._write_seconds = self._mreg.histogram("dn_write_seconds")
        self._readers = 0
        self._mreg.set_gauge("dn_readers", lambda: self._readers)
        # bounded per-block read-frequency sketch (SpaceSaving), its
        # top slice piggybacked on every heartbeat for the NameNode's
        # cluster-wide hot-block table
        from tpumr.dfs.hotblocks import SpaceSaving
        self._hot = SpaceSaving(
            k=int(conf.get("tpumr.dn.hotblocks.k", 64)))
        self._hot_top = int(conf.get("tpumr.dn.hotblocks.top", 16))
        self._hot_lock = threading.Lock()
        # per-heartbeat exponential decay so the sketch follows the
        # CURRENT read mix (the NN cool-down depends on hot shares
        # actually falling); factor chosen so counts halve every
        # halflife.s seconds of heartbeats; 0 disables
        halflife = float(conf.get("tpumr.dn.hotblocks.halflife.s", 60.0))
        self._hot_decay = (0.5 ** (self.heartbeat_s / halflife)
                           if halflife > 0 else 1.0)
        self._server = RpcServer(self, host=host, port=port, secret=self._secret)
        # block reads are read-only + idempotent: exempt them from the
        # server's dedup/replay cache so re-sent reads never pin whole
        # chunk payloads in the reply cache (same idiom as the shuffle
        # server's get_map_output)
        self._server.uncached_methods = {"read_block", "read_block_chunk",
                                         "block_checksum"}
        self._server.metrics = self.metrics.new_registry("rpc")
        # Personal-credential callers (user keys, delegation tokens)
        # reach block data ONLY with a NameNode-minted per-block access
        # stamp (≈ the reference's BlockToken split): the frame is
        # authenticated statelessly, the GATE below demands the stamp.
        # Cluster-secret daemons (NN commands, peer replication) bypass.
        self._server.token_stateless = True
        self._server.request_gate = self._gate_block_access
        self._stop = threading.Event()
        self._hb = threading.Thread(target=self._heartbeat_loop,
                                    name="dn-heartbeat", daemon=True)
        self._peer_clients: dict[str, RpcClient] = {}
        self._lock = threading.Lock()
        #: in-flight streamed uploads: block_id -> {downstream, ts}
        self._uploads: dict[int, dict] = {}
        #: periodic CRC verification of every stored block ≈
        #: DataBlockScanner (reference default: one full pass per 3
        #: weeks; here per-period sweep, 0 disables)
        self.scan_period_s = float(conf.get("tdfs.datanode.scan.period.s",
                                            6 * 3600))
        self._scanner = threading.Thread(target=self._scan_loop,
                                         name="dn-block-scanner",
                                         daemon=True)
        self._http: Any = None
        self._http_port = int(conf.get("tpumr.dn.http.port", -1))
        self.sampler: Any = None
        #: fleet slot (the ``d<n>`` of the targeted ``dn.crash.d<n>``
        #: chaos seam) — -1 when not run under a mini cluster/scenario
        self.fi_index = -1
        #: monotonic deadline while "partitioned away" (``dn.partition``
        #: seam): heartbeats are skipped until then — the process stays
        #: alive and KEEPS SERVING reads, the NN is left to expire it
        #: and fold the rejoin through the re-register + block report
        self._partition_until = 0.0
        self.killed = False

    # ------------------------------------------------------------ lifecycle

    def start(self) -> "DataNode":
        self._server.start()
        self._register()
        self._hb.start()
        if self.scan_period_s > 0:
            self._scanner.start()
        if self._http_port >= 0:
            self._http = self._build_http(self._http_port).start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self.sampler is not None:
            self.sampler.stop()
        if self._http is not None:
            self._http.stop()
        self._server.stop()

    def kill(self) -> None:
        """Hard-kill (≈ SIGKILL): the RPC server drops mid-request —
        in-flight reads and pipeline writes fail on the wire, nothing
        deregisters, and the NameNode is left to expire the node and
        re-replicate. The storage dir survives, so a later DataNode on
        the same dir rejoins with its old replicas via block report."""
        self.killed = True
        self._stop.set()
        self._server.stop()

    @property
    def http_url(self) -> "str | None":
        return self._http.url if self._http is not None else None

    def _build_http(self, port: int):
        """Uniform daemon status surface (/metrics, /metrics/prom,
        /stacks //flame under tpumr.prof.enabled) — the same scraper
        config that covers the mapred daemons and the NN now covers
        datanodes too; today the datanode served no status page at all."""
        from tpumr.http import StatusHttpServer
        srv = StatusHttpServer("datanode", port=port)
        srv.attach_metrics(self.metrics)
        from tpumr.metrics.sampler import StackSampler
        self.sampler = StackSampler.from_conf(self.conf, self.metrics)
        if self.sampler is not None:
            self.sampler.start()
            self.sampler.attach_http(srv)

        def hotblocks(q: dict) -> dict:
            with self._hot_lock:
                return self._hot.to_wire(int(q.get("n", self._hot_top)))

        srv.add_raw("hotblocks", hotblocks)

        def summary(q: dict) -> dict:
            blocks = self.store.blocks()
            return {"addr": self.addr, "blocks": len(blocks),
                    "used": sum(s for _, s in blocks),
                    "capacity": self.capacity,
                    "readers": self._readers}

        srv.add_json("datanode", summary)
        return srv

    @property
    def addr(self) -> str:
        host, port = self._server.address
        return f"{host}:{port}"

    def _register(self) -> None:
        self.nn.call("register_datanode", self.addr, self.capacity)
        invalid = self.nn.call("block_report", self.addr,
                               [list(b) for b in self.store.blocks()])
        # the report's return is the NN-driven invalidation channel
        # (orphans of files deleted while we were down, replicas the NN
        # dropped): act on it, or the stale replicas — and any cached
        # fds onto them — live here forever (delete() invalidates the
        # fd/meta caches, closing the fd-cache staleness hole)
        for bid in invalid or []:
            try:
                self.store.delete(int(bid))
            except (TypeError, ValueError, OSError):
                continue

    def _peer(self, addr: str) -> RpcClient:
        with self._lock:
            cli = self._peer_clients.get(addr)
            if cli is None:
                host, port = addr.rsplit(":", 1)
                cli = self._peer_clients[addr] = RpcClient(host, int(port), secret=self._secret)
            return cli

    # ------------------------------------------------------------ heartbeat

    def hot_wire(self) -> dict:
        """The read-frequency slice piggybacked on each heartbeat: the
        sketch's top entries + stream total, bounded by
        tpumr.dn.hotblocks.top regardless of how hot the node runs."""
        with self._hot_lock:
            return self._hot.to_wire(self._hot_top)

    def _heartbeat_loop(self) -> None:
        from tpumr.utils.fi import fires
        while not self._stop.wait(self.heartbeat_s):
            if fires(f"dn.crash.d{self.fi_index}", self.conf) \
                    or fires("dn.crash", self.conf):
                # BEHAVIORAL churn seam: hard-kill mid-beat — in-flight
                # reads/pipeline writes die on the wire, nothing
                # deregisters; NN expiry + re-replication (and client
                # replica failover) are the quarry's predator
                self.kill()
                return
            if fires("dn.partition", self.conf):
                # heartbeat silence WITHOUT process death: reads keep
                # being served while the NN expires us; the rejoin goes
                # through dn_heartbeat's "register" → block report
                self._partition_until = time.monotonic() + float(
                    self.conf.get("tpumr.fi.dn.partition.ms", 3000)) \
                    / 1000.0
            if self._hot_decay < 1.0:
                with self._hot_lock:
                    self._hot.decay(self._hot_decay)
            if time.monotonic() < self._partition_until:
                continue
            try:
                cmds = self.nn.call("dn_heartbeat", self.addr,
                                    self.store.used(), self.capacity,
                                    len(self.store.blocks()),
                                    self.hot_wire())
                for cmd in cmds:
                    self._apply_command(cmd)
            except Exception:  # noqa: BLE001 — NN briefly unreachable
                pass
            # purge streamed uploads abandoned by dead clients (their
            # temp files would otherwise live forever)
            cutoff = time.monotonic() - float(
                self.conf.get("tdfs.upload.stale.s", 600))
            with self._lock:
                stale = [bid for bid, up in self._uploads.items()
                         if up["ts"] < cutoff]
            for bid in stale:
                try:
                    self.abort_block_stream(bid)
                except Exception:  # noqa: BLE001
                    pass

    # ------------------------------------------------------------ scanner

    def scan_once(self) -> "list[int]":
        """One verification sweep over every stored block; corrupt ones
        are reported to the NameNode (which drops the replica — unless it
        is the last — and re-replicates from a good copy). Returns the
        corrupt block ids found."""
        bad = []
        for bid, _size in self.store.blocks():
            if self._stop.is_set():
                break
            try:
                self.store.read(bid)  # full read = CRC verification
            except ChecksumError:
                bad.append(bid)
                try:
                    self.nn.call("report_bad_block", bid, self.addr)
                except Exception:  # noqa: BLE001 — retried next sweep
                    pass
            except FileNotFoundError:
                continue  # deleted mid-scan
        return bad

    def _scan_loop(self) -> None:
        while not self._stop.wait(self.scan_period_s):
            try:
                self.scan_once()
            except Exception:  # noqa: BLE001 — scanner must survive
                pass

    def _apply_command(self, cmd: dict) -> None:
        kind = cmd.get("type")
        if kind == "delete":
            self.store.delete(cmd["block_id"])
        elif kind == "replicate":
            bid = cmd["block_id"]
            try:
                data = self.store.read(bid)
            except (FileNotFoundError, ChecksumError):
                return
            for target in cmd["targets"]:
                try:
                    self._peer(target).call("write_block", bid, data, [])
                except Exception:  # noqa: BLE001
                    continue
        elif kind == "register":
            self._register()

    # ------------------------------------------------------------ access gate

    #: method -> required access mode; every entry takes block_id first
    _GATED = {"read_block": "r", "read_block_chunk": "r",
              "block_checksum": "r", "write_block": "w",
              "open_block_stream": "w", "write_block_chunk": "w",
              "commit_block_stream": "w", "abort_block_stream": "w"}

    def _gate_block_access(self, req: dict, verified_user, job_scoped):
        """Pre-dispatch enforcement (rpc request_gate): personal-scoped
        callers must present a live NameNode stamp bound to (user,
        block, mode). Raw block ids are guessable integers — without
        this, a canceled token could read/corrupt arbitrary blocks until
        its max lifetime."""
        if verified_user is None:
            return                      # cluster-secret daemon caller
        from tpumr.ipc.rpc import RpcAuthError
        method = str(req.get("method", ""))
        mode = self._GATED.get(method)
        if mode is None:
            if method in ("get_protocol_version",):
                return
            raise RpcAuthError(
                f"method {method!r} is not available to "
                "personal-credential callers")
        params = req.get("params") or []
        from tpumr.security.tokens import check_block_access
        if not params or not check_block_access(
                self._secret, req.get("access"), verified_user,
                params[0], mode):
            raise RpcAuthError(
                "block access denied: missing/expired/mismatched "
                "NameNode access stamp")

    # ------------------------------------------------------------ transfer RPC

    def _maybe_rot(self, block_id: int) -> None:
        """``dn.read.corrupt[.b<id>]`` chaos seam: model bit-rot by
        flipping a byte in the on-disk replica just before serving it —
        the UNMODIFIED read path must then fail CRC verification, the
        client fails over and reports the bad block, and the NN drops
        this replica and re-replicates. Readers never see the rot."""
        from tpumr.utils.fi import fires
        if fires(f"dn.read.corrupt.b{block_id}", self.conf) \
                or fires("dn.read.corrupt", self.conf):
            self.store.corrupt_replica(block_id)

    def _note_read(self, block_id: int, n: int, t0: float) -> None:
        self._read_bytes.observe(n)
        self._read_seconds.observe(time.monotonic() - t0)
        with self._hot_lock:
            self._hot.offer(str(block_id))

    def write_block(self, block_id: int, data: bytes,
                    downstream: list[str]) -> None:
        """Pipelined write: forward downstream FIRST, then store locally —
        an ack only returns once the whole chain stored the block
        (≈ BlockReceiver's chained pipeline with downstream acks)."""
        if downstream:
            self._peer(downstream[0]).call("write_block", block_id, data,
                                           downstream[1:])
        t0 = time.monotonic()
        self.store.write(block_id, data)
        self._write_bytes.observe(len(data))
        self._write_seconds.observe(time.monotonic() - t0)
        self.nn.call("block_received", self.addr, block_id, len(data))

    def read_block(self, block_id: int, offset: int = 0,
                   length: int = -1) -> bytes:
        self._maybe_rot(block_id)
        t0 = time.monotonic()
        self._readers += 1
        try:
            data = self.store.read(block_id, offset, length)
        finally:
            self._readers -= 1
        self._note_read(block_id, len(data), t0)
        return data

    #: server-side cap per streamed-transfer RPC — bounds datanode
    #: memory per request regardless of client asks (the streaming
    #: re-design of DataTransferProtocol's op READ_BLOCK: payloads move
    #: as bounded chunks, never whole blocks per response)
    MAX_CHUNK_BYTES = 4 << 20

    def read_block_chunk(self, block_id: int, offset: int,
                         max_bytes: int, wire: str = "none") -> dict:
        """One bounded chunk of a block + its total length; checksums
        verified for the covering CRC chunks only. ``wire`` is a codec
        the CLIENT offers (tdfs.read.wire.codec) — when it pays, the
        payload ships compressed with ``wire`` set in the response and
        the client decodes; sizes/offsets stay payload-relative. Old
        clients omit the param and always get raw bytes."""
        self._maybe_rot(block_id)
        n = max(0, min(int(max_bytes), self.MAX_CHUNK_BYTES))
        t0 = time.monotonic()
        self._readers += 1
        try:
            data, total = self.store.read_range(block_id, int(offset), n)
        finally:
            self._readers -= 1
        self._note_read(block_id, len(data), t0)
        out = {"data": data, "total": total}
        compress.wire_compress(out, compress.wire_codec_or_none(wire))
        return out

    # streamed pipelined write ≈ DataTransferProtocol op WRITE_BLOCK:
    # chunks relay downstream FIRST (same ordering as write_block), each
    # ack returns once the whole chain appended; commit finalizes the
    # chain from the tail up so a successful return means every replica
    # is installed. Session state is (block_id, downstream) — one
    # concurrent upload per block per node, like the reference's
    # single-writer block lease.

    def open_block_stream(self, block_id: int,
                          downstream: "list[str]") -> None:
        if downstream:
            self._peer(downstream[0]).call("open_block_stream", block_id,
                                           downstream[1:])
        with self._lock:
            self._uploads[block_id] = {"downstream": list(downstream),
                                       "ts": time.monotonic()}
        self.store.open_stream(block_id)

    def write_block_chunk(self, block_id: int, data: bytes) -> None:
        with self._lock:
            up = self._uploads.get(block_id)
        if up is None:
            raise KeyError(f"no open stream for block {block_id}")
        if up["downstream"]:
            self._peer(up["downstream"][0]).call("write_block_chunk",
                                                 block_id, data)
        self.store.append_stream(block_id, data)
        up["ts"] = time.monotonic()

    def commit_block_stream(self, block_id: int) -> None:
        with self._lock:
            up = self._uploads.pop(block_id, None)
        if up is None:
            raise KeyError(f"no open stream for block {block_id}")
        if up["downstream"]:
            self._peer(up["downstream"][0]).call("commit_block_stream",
                                                 block_id)
        t0 = time.monotonic()
        size = self.store.finalize_stream(block_id)
        self._write_bytes.observe(size)
        self._write_seconds.observe(time.monotonic() - t0)
        self.nn.call("block_received", self.addr, block_id, size)

    def abort_block_stream(self, block_id: int) -> None:
        with self._lock:
            up = self._uploads.pop(block_id, None)
        if up and up["downstream"]:
            try:
                self._peer(up["downstream"][0]).call("abort_block_stream",
                                                     block_id)
            except Exception:  # noqa: BLE001 — best-effort chain abort
                pass
        self.store.abort_stream(block_id)

    def block_checksum(self, block_id: int) -> int:
        return zlib.crc32(self.store.read(block_id))
