"""NameNode — namespace + block management master.

≈ ``org.apache.hadoop.hdfs.server.namenode.{NameNode,FSNamesystem}``
(reference: FSNamesystem.java, 5907 LoC; NameNode.java RPC front). Contracts
reproduced:

- flat namespace of files/dirs; files are ordered block lists; every
  mutation journals to the edit log BEFORE applying (editlog.py);
- single-writer leases: create() grants the lease, concurrent creates fail
  (AlreadyBeingCreatedException semantics); expired leases are recovered by
  finalizing the file with its reported blocks (LeaseManager);
- block locations are NOT persisted — rebuilt from DataNode block reports
  (BlocksMap + processReport semantics);
- safemode on startup until a threshold fraction of known blocks have a
  reported replica (``dfs.safemode.threshold.pct``, FSNamesystem.SafeModeInfo);
- heartbeat-lease liveness for DataNodes; a dead DataNode's replicas go
  under-replicated and the replication monitor schedules re-replication on
  surviving nodes (heartbeatCheck + ReplicationMonitor → DNA_TRANSFER /
  DNA_INVALIDATE commands piggybacked on heartbeats);
- write-path placement excludes client-reported bad nodes (abandonBlock +
  excludedNodes on addBlock).
"""

from __future__ import annotations

import contextlib
import logging
import random
import threading
import time
from typing import Any

from tpumr.dfs.editlog import FSEditLog, FSImage
from tpumr.dfs.hotblocks import HotBlockTable
from tpumr.dfs.nslock import NamespaceLocks
from tpumr.ipc.rpc import RpcServer

#: ≈ ClientProtocol.versionID (hdfs/protocol/ClientProtocol.java)
PROTOCOL_VERSION = 61


class SafeModeError(RuntimeError):
    pass


class LeaseError(RuntimeError):
    pass


class QuotaExceededError(RuntimeError):
    pass


def _now() -> float:
    return time.time()


class FSNamesystem:
    """Namespace + block map + leases. All public mutators journal first."""

    def __init__(self, name_dir: str, conf: Any) -> None:
        self.conf = conf
        self.name_dir = name_dir
        # striped locking (nslock.py): path ops take only their
        # subtree's stripe, datanode/block ops take only the blocks
        # lock, and the global ``namespace`` lock is reserved for
        # cross-stripe structural work — wait/hold land in
        # nn_lock_*_seconds{lock=namespace|namespace-stripe|
        # namespace-blocks}; histograms bind later (bind_metrics)
        self.locks = NamespaceLocks(
            stripes=int(conf.get("tdfs.namenode.lock.stripes", 8)),
            depth=int(conf.get("tdfs.namenode.lock.stripe.depth", 2)))
        #: back-compat alias: the structural/global lock, still named
        #: "namespace" in the rank table and metric labels. Holding it
        #: alone does NOT exclude striped ops — quiesced-state readers
        #: (tests, status pages) are fine, mutators must go through
        #: _locked()/locks.structural()
        self.lock = self.locks.global_lock
        #: the block/datanode-plane lock — short sections, no journaling
        self._blk = self.locks.blocks
        #: leaf mutex for the quota usage cache (_quota_usage): charged
        #: from any stripe, so the per-entry += must not race; plain
        #: unranked Lock because nothing ever blocks under it
        self._quota_mu = threading.Lock()
        self.default_replication = int(conf.get("dfs.replication", 3))
        self.default_block_size = int(conf.get("dfs.block.size",
                                               8 * 1024 * 1024))
        self.safemode_threshold = float(conf.get("dfs.safemode.threshold.pct",
                                                 0.999))
        self.lease_hard_limit = float(conf.get("tdfs.lease.hard.limit.s", 60))

        # persisted state: namespace + counters (image ∪ edits)
        self.namespace, self.counters = FSImage.load(name_dir)
        for op in FSEditLog.replay(name_dir):
            self.apply_op(self.namespace, self.counters, op)
        self.counters.setdefault("next_block", 1)
        self.counters.setdefault("gen", 1)
        self._edits_segment_bytes = int(
            float(conf.get("tdfs.edits.segment.mb", 16)) * 1024 * 1024)
        self.edits = FSEditLog(name_dir,
                               segment_bytes=self._edits_segment_bytes)
        #: sealed segments shipped to a secondary, purged on put_image
        self._checkpoint_segments: list[str] = []
        #: checkpoint epoch token (≈ CheckpointSignature): bumped by every
        #: get_name_state fetch AND every in-process checkpoint; put_image
        #: must echo the token of the LATEST fetch or it is refused — a
        #: stale secondary upload can never purge segments its merged
        #: image does not cover
        self._ckpt_token = 0
        #: serializes the checkpoint flows (save_namespace /
        #: get_name_state / put_image) against each other so their
        #: image + sealed-segment file I/O can run OUTSIDE the namespace
        #: lock: the token protocol already refuses cross-process
        #: staleness; this mutex removes the in-process interleavings
        #: (two concurrent checkpoints double-applying sealed segments).
        #: Always acquired BEFORE self.lock, never while holding it.
        self._ckpt_mu = threading.Lock()

        # permission model ≈ FSNamesystem/FSPermissionChecker: owner/group/
        # mode per inode; the NN process user is the superuser; identity is
        # the (signed) simple-auth user asserted on each RPC. In-process
        # calls (monitor threads, lease recovery) carry no RPC user and
        # bypass checks — they ARE the namesystem.
        self.permissions_enabled = conf.get_boolean("dfs.permissions", True)
        import getpass
        self.superuser = str(conf.get("tdfs.superuser", "")
                             or getpass.getuser())
        self.supergroup = str(conf.get("dfs.permissions.supergroup",
                                       "supergroup"))
        # root inode: superuser-owned 0755 like a formatted HDFS
        # namespace. JOURNALED like any mkdir (the "format" record) —
        # an un-journaled root would be re-stamped with a fresh mtime
        # by every restart that replays from a checkpoint image, so the
        # namespace would never be byte-identical across a crash
        if "/" not in self.namespace:
            op = {"op": "mkdir", "path": "/", "t": _now(),
                  "o": self.superuser, "g": self.supergroup, "m": 0o755}
            self.edits.log(op)
            self.apply_op(self.namespace, self.counters, op)
        root = self.namespace["/"]
        root.setdefault("owner", self.superuser)
        root.setdefault("group", self.supergroup)
        root.setdefault("mode", 0o755)
        #: corrupt replicas reported by clients: bid -> {addr}
        self.corrupt_replicas: dict[int, set[str]] = {}
        #: reverse index bid -> owning path, kept alongside the other
        #: volatile block maps — report_bad_block's permission lookup must
        #: not scan the namespace under the lock
        self.block_to_path: dict[int, str] = {
            b[0]: p for p, ino in self.namespace.items()
            if ino.get("type") == "file" for b in ino.get("blocks", [])}
        #: addr -> "decommissioning" | "decommissioned" (admin-driven,
        #: ≈ the exclude-file + refreshNodes workflow). Journaled through
        #: 'decommission' ops into counters so an NN restart cannot
        #: silently return a draining node to service.
        self.decommissioning: dict[str, str] = \
            self.counters.setdefault("decommissioning", {})
        # volatile state, rebuilt at runtime
        self.block_locations: dict[int, set[str]] = {}   # bid -> {dn addr}
        self.block_sizes: dict[int, int] = {}            # reported sizes
        self.datanodes: dict[str, dict] = {}             # addr -> info
        self.commands: dict[str, list[dict]] = {}        # addr -> pending
        self.leases: dict[str, dict] = {}                # client -> lease

        #: incremental per-quota-dir usage cache: qpath -> [inodes, bytes]
        #: (≈ INodeDirectoryWithQuota's cached counts) — quota checks must
        #: not rescan the namespace under the lock on every write.
        #: Maintained by the mutators via _charge, re-derived at every
        #: checkpoint (self-healing against conservative drift from
        #: lease-recovery closes). Needs block_sizes initialized above.
        self._quota_usage: dict[str, list] = {}
        self._rebuild_quota_usage()

        # The safemode denominator counts only CLOSED files' blocks —
        # matching the live accounting, where blocks enter
        # total_known_blocks at complete/close. A file open at the
        # crash may hold a journaled add_block the writer never pushed
        # to any DataNode; counting it would hold _reported_fraction
        # below threshold FOREVER (no replica exists to report).
        # HDFS likewise excludes under-construction blocks from
        # SafeModeInfo's blockTotal.
        self.total_known_blocks = sum(
            len(i.get("blocks", [])) for i in self.namespace.values()
            if i.get("type") == "file" and not i.get("uc"))
        self.safemode = self.total_known_blocks > 0
        # none of a restart-survivor uc file's blocks are in the
        # denominator, so the eventual close/lease-recovery delta adds
        # ALL of them (len(blocks) - 0) — same contract as create,
        # where post-open blocks wait for complete to be counted
        self._uc_counted: dict[str, int] = {
            p: 0 for p, i in self.namespace.items()
            if i.get("type") == "file" and i.get("uc")}

        # rack awareness ≈ FSNamesystem's clusterMap (NetworkTopology)
        from tpumr.net import NetworkTopology, resolver_from_conf
        self.topology = NetworkTopology(resolver_from_conf(conf))

        #: cluster-wide hot-block view folded from the bounded
        #: SpaceSaving slices datanodes piggyback on heartbeats
        #: (hotblocks.py) — served at /hotblocks + get_hot_blocks
        self.hot_blocks = HotBlockTable(
            k=int(conf.get("tpumr.dn.hotblocks.k", 64)))
        # hot-block auto-replication policy (hotblock_check): when one
        # block draws more than `share` of cluster reads, raise its
        # replica target toward the cap; the boost decays back once the
        # block cools (the DN sketches decay too, so share follows the
        # CURRENT mix, not history)
        self.hot_share = float(conf.get("tdfs.hotblocks.replicate.share",
                                        0.3))
        self.hot_min_reads = int(conf.get(
            "tdfs.hotblocks.replicate.min.reads", 200))
        self.hot_cap = int(conf.get("tdfs.hotblocks.replicate.cap", 4))
        self.hot_cool_s = float(conf.get("tdfs.hotblocks.cool.s", 15.0))
        #: bid -> {"boost": target_replicas, "hot_mono": last_hot_ts} —
        #: consulted by replication_check, guarded by self._blk
        self.hot_boost: dict[int, dict] = {}

        # audit log ≈ FSNamesystem.logAuditEvent: one line per namespace
        # mutation on the dedicated "tpumr.nn.audit" logger, rate-capped
        # per second so a create storm cannot turn the audit trail into
        # the bottleneck it documents (suppressions are counted, never
        # silent)
        self._audit_enabled = conf.get_boolean("tpumr.nn.audit.enabled",
                                               False)
        self._audit_rate = int(conf.get("tpumr.nn.audit.rate.limit", 200))
        self._audit_log = logging.getLogger("tpumr.nn.audit")
        self._audit_window = -1
        self._audit_in_window = 0
        self.audit_emitted = 0
        self.audit_suppressed = 0

    # ------------------------------------------------------------ journal

    @staticmethod
    def apply_op(namespace: dict, counters: dict, op: dict) -> None:
        """Replay one journaled op onto a bare namespace. Shared by startup
        replay and checkpoint merge (editlog.checkpoint)."""
        kind = op["op"]
        p = op.get("path")
        if kind == "mkdir":
            namespace[p] = {"type": "dir", "mtime": op["t"],
                            "owner": op.get("o", ""),
                            "group": op.get("g", ""),
                            "mode": op.get("m", 0o755)}
        elif kind == "create":
            namespace[p] = {"type": "file", "blocks": [], "uc": True,
                            "replication": op["r"], "block_size": op["bs"],
                            "mtime": op["t"], "client": op.get("c", ""),
                            "owner": op.get("o", ""),
                            "group": op.get("g", ""),
                            "mode": op.get("m", 0o644)}
        elif kind == "append_open":
            namespace[p]["uc"] = True
            namespace[p]["client"] = op.get("c", "")
        elif kind == "add_block":
            namespace[p]["blocks"].append([op["bid"], 0])
        elif kind == "block_size":
            for b in namespace[p]["blocks"]:
                if b[0] == op["bid"]:
                    b[1] = op["size"]
        elif kind == "abandon":
            if p in namespace:  # tolerate journals from older builds
                namespace[p]["blocks"] = [b for b in namespace[p]["blocks"]
                                          if b[0] != op["bid"]]
        elif kind == "close":
            inode = namespace[p]
            inode["uc"] = False
            inode.pop("client", None)
            if "sizes" in op:
                for b in inode["blocks"]:
                    b[1] = op["sizes"].get(str(b[0]), b[1])
        elif kind == "rename":
            dst = op["dst"]
            moved = [(k, v) for k, v in namespace.items()
                     if k == p or k.startswith(p.rstrip("/") + "/")]
            for k, v in moved:
                del namespace[k]
                namespace[dst + k[len(p):]] = v
        elif kind == "delete":
            for k in [k for k in namespace
                      if k == p or k.startswith(p.rstrip("/") + "/")]:
                del namespace[k]
        elif kind == "set_repl":
            namespace[p]["replication"] = op["r"]
        elif kind == "chmod":
            namespace[p]["mode"] = op["m"]
        elif kind == "chown":
            if op.get("o"):
                namespace[p]["owner"] = op["o"]
            if op.get("g"):
                namespace[p]["group"] = op["g"]
        elif kind == "set_quota":
            ino = namespace[p]
            for field_name, key in (("ns_quota", "nsq"), ("sp_quota", "spq")):
                if key in op:
                    if op[key] is None or op[key] < 0:
                        ino.pop(field_name, None)
                    else:
                        ino[field_name] = op[key]
        elif kind == "decommission":
            d = counters.setdefault("decommissioning", {})
            if op.get("state"):
                d[op["addr"]] = op["state"]
            else:
                d.pop(op["addr"], None)
        elif kind == "counters":
            # allocator counters apply as a MONOTONIC max: with striped
            # locking two add_blocks in different stripes may journal
            # their counter bumps out of allocation order, and replaying
            # the smaller value last would re-issue a block id
            for k, v in op["values"].items():
                if k in ("next_block", "gen") and isinstance(v, int):
                    old = counters.get(k)
                    counters[k] = max(old, v) \
                        if isinstance(old, int) else v
                else:
                    counters[k] = v

    def _log(self, op: dict) -> None:
        self.edits.log(op)

    def _audit(self, cmd: str, src: str, dst: "str | None" = None,
               perm: "str | None" = None) -> None:
        """HDFS-style audit line (``ugi= ip= cmd= src= dst= perm=``) for
        one SUCCESSFUL namespace mutation — called after the journal
        append, so an audited op is always a durable op."""
        if not self._audit_enabled:
            return
        window = int(time.monotonic())
        if window != self._audit_window:
            self._audit_window = window
            self._audit_in_window = 0
        self._audit_in_window += 1
        if self._audit_rate and self._audit_in_window > self._audit_rate:
            self.audit_suppressed += 1
            return
        self.audit_emitted += 1
        self._audit_log.info(
            "ugi=%s ip=- cmd=%s src=%s dst=%s perm=%s",
            self._caller() or self.superuser, cmd, src,
            "-" if dst is None else dst, "-" if perm is None else perm)

    def bind_metrics(self, reg: Any) -> None:
        """Attach the namespace-lock wait/hold and editlog histograms —
        the lock and journal exist before the metrics registry does, so
        they late-bind exactly like the master's lock classes."""
        from tpumr.metrics.histogram import BYTES
        self.locks.bind_metrics(reg)
        self.edits.bind_metrics(
            reg.histogram("nn_editlog_append_seconds"),
            reg.histogram("nn_editlog_sync_seconds"),
            reg.histogram("nn_editlog_batch_bytes", bounds=BYTES),
            reg.histogram("nn_editlog_group_ops"))

    # ------------------------------------------------------------ helpers

    def _locked(self, *paths: str, ensure: "str | None" = None):
        """Lock context for an op on ``paths``: their stripes in index
        order, or structural when any path is too shallow to stripe.
        ``ensure``: the op will _ensure_parents this path — when a
        MISSING ancestor is itself too shallow to stripe (a new
        top-level dir), creating it is structural work, decided here
        with lock-free point reads before anything is acquired."""
        if ensure is not None:
            p = self._parent_of(ensure)
            while p != "/" and p not in self.namespace:
                if self.locks.stripe_index(p) is None:
                    return self.locks.structural()
                p = self._parent_of(p)
        return self.locks.for_paths(*paths)

    def _ns_items(self) -> "list[tuple[str, dict]]":
        """Point-in-time snapshot of the namespace dict for full scans
        that don't hold a lock excluding all mutators (blocks-plane
        sweeps, status pages). ``list(dict.items())`` is GIL-atomic in
        CPython — same contract lock_table() relies on — so a scan can
        never see a resize mid-iteration; individual inode dicts may
        still be mutated concurrently, which these scans tolerate
        (point-in-time staleness, never corruption)."""
        return list(self.namespace.items())

    def _check_safemode(self) -> None:
        if self.safemode:
            raise SafeModeError(
                "NameNode is in safe mode: "
                f"{self._reported_fraction():.3f} of "
                f"{self.total_known_blocks} blocks reported "
                f"(threshold {self.safemode_threshold})")

    def _reported_fraction(self) -> float:
        if self.total_known_blocks == 0:
            return 1.0
        # uc files mirror the denominator: their blocks are not in
        # total_known_blocks until close, so counting their reported
        # replicas here could push the fraction past threshold while
        # CLOSED files' blocks are still dark
        reported = sum(1 for _, i in self._ns_items()
                       if i.get("type") == "file" and not i.get("uc")
                       for b in i.get("blocks", [])
                       if self.block_locations.get(b[0]))
        return reported / self.total_known_blocks

    def _maybe_leave_safemode(self) -> None:
        if self.safemode and \
                self._reported_fraction() >= self.safemode_threshold:
            self.safemode = False

    def _ensure_parents(self, path: str,
                        user: "str | None" = None) -> None:
        parts = [p for p in path.split("/") if p]
        cur = ""
        for part in parts[:-1]:
            cur += "/" + part
            inode = self.namespace.get(cur)
            if inode is None:
                if not self.locks.covers(cur):
                    # striped context, missing ancestor OUTSIDE the held
                    # stripes: _locked()'s pre-check saw it present, so
                    # a structural delete won the race since — fail like
                    # any create under a just-deleted tree (a retry
                    # re-runs the pre-check and escalates)
                    raise FileNotFoundError(
                        f"{cur} (parent deleted concurrently)")
                op = {"op": "mkdir", "path": cur, "t": _now(),
                      "o": user or self.superuser, "g": self.supergroup,
                      "m": 0o755}
                self._log(op)
                self.apply_op(self.namespace, self.counters, op)
                self._charge(cur, 1, 0)
            elif inode["type"] != "dir":
                raise NotADirectoryError(cur)

    def _inode(self, path: str) -> dict:
        inode = self.namespace.get(path)
        if inode is None:
            raise FileNotFoundError(path)
        return inode

    # ------------------------------------------------------------ permissions

    @staticmethod
    def _caller() -> "str | None":
        from tpumr.ipc.rpc import current_rpc_user
        return current_rpc_user()

    def _groups_of(self, user: str) -> set:
        """Static group mapping from conf (``tpumr.user.groups.<user>`` =
        comma list) ≈ the reference's configurable GroupMappingServiceProvider
        — no JNI/shell group lookup on the NameNode's hot path."""
        gs = self.conf.get(f"tpumr.user.groups.{user}")
        return {s.strip() for s in str(gs).split(",")} if gs else set()

    @staticmethod
    def _parent_of(path: str) -> str:
        return path.rstrip("/").rsplit("/", 1)[0] or "/"

    def _check_access(self, path: str, want: int,
                      user: "str | None") -> None:
        """rwx bit check (want: 4=r, 2=w, 1=x) ≈ FSPermissionChecker.check.
        None user = in-process caller (the namesystem itself); superuser
        bypasses everything."""
        if (not self.permissions_enabled or user is None
                or user == self.superuser):
            return
        inode = self.namespace.get(path)
        if inode is None:
            return
        # same defaults get_status displays — enforcement and ls must
        # never disagree about what a missing mode means
        mode = inode.get("mode",
                         0o755 if inode.get("type") == "dir" else 0o644)
        owner = inode.get("owner", "")
        group = inode.get("group", "")
        # pre-permission inodes (replayed from old journals) have no
        # owner: everyone gets the owner bits — an upgrade must not lock
        # users out of trees they created before permissions existed
        if user == owner or owner == "":
            ok = (mode >> 6) & want
        elif group and group in self._groups_of(user):
            ok = (mode >> 3) & want
        else:
            ok = mode & want
        if not ok:
            access = {4: "READ", 2: "WRITE", 1: "EXECUTE"}.get(want, want)
            raise PermissionError(
                f"Permission denied: user={user}, access={access}, "
                f"inode={path} (owner={owner or '?'}, "
                f"mode={oct(mode & 0o777)})")

    def _check_parent_write(self, path: str, user: "str | None") -> None:
        """WRITE on the nearest EXISTING ancestor dir — creating a deep
        path checks where the subtree attaches, like the reference's
        checkAncestorAccess."""
        p = self._parent_of(path)
        while p != "/" and p not in self.namespace:
            p = self._parent_of(p)
        self._check_access(p, 2, user)

    def _check_superuser(self, what: str) -> None:
        user = self._caller()
        if (self.permissions_enabled and user is not None
                and user != self.superuser):
            raise PermissionError(
                f"Permission denied: only the superuser may {what}")

    # ------------------------------------------------------------ quotas

    def _quota_ancestors(self, path: str) -> "list[tuple[str, dict]]":
        """Ancestor dirs of ``path`` (inclusive) carrying a quota."""
        out = []
        p = path
        while True:
            ino = self.namespace.get(p)
            if ino is not None and ("ns_quota" in ino or "sp_quota" in ino):
                out.append((p, ino))
            if p == "/":
                return out
            p = self._parent_of(p)

    def _subtree_usage(self, root: str) -> "tuple[int, int]":
        """(inode_count, consumed_bytes) under ``root`` — consumed =
        block bytes × replication, the reference's diskspace accounting
        (INodeDirectoryWithQuota). Computed on demand: quota dirs are
        rare and ops on them tolerate the walk."""
        prefix = "/" if root == "/" else root.rstrip("/") + "/"
        inodes = 0
        consumed = 0
        for p, ino in self._ns_items():
            if p == root or p == "/" or not p.startswith(prefix):
                continue
            inodes += 1
            if ino.get("type") == "file":
                repl = ino.get("replication", 1)
                consumed += sum(self.block_sizes.get(b[0], b[1])
                                for b in ino.get("blocks", [])) * repl
        return inodes, consumed

    def _missing_ancestors(self, path: str) -> int:
        """How many intermediate dirs _ensure_parents would create —
        they count against namespace quotas too (the reference charges
        every new INode, not just the leaf)."""
        n = 0
        p = self._parent_of(path)
        while p != "/" and p not in self.namespace:
            n += 1
            p = self._parent_of(p)
        return n

    def _rebuild_quota_usage(self) -> None:
        """One scan re-deriving every quota dir's cached counters."""
        usage: dict[str, list] = {}
        for p, ino in self._ns_items():
            if ino.get("type") == "dir" and ("ns_quota" in ino
                                             or "sp_quota" in ino):
                usage[p] = None
        for q in usage:
            usage[q] = list(self._subtree_usage(q))
        self._quota_usage = usage

    def _charge(self, path: str, d_inodes: int, d_bytes: int) -> None:
        """Apply a usage delta at ``path`` to every quota-carrying PROPER
        ancestor's cached counters. No-op when no quotas exist. A quota
        dir's counters may be charged from ANY stripe (ancestors are
        not covered by the op's stripe set), hence the leaf mutex."""
        if not self._quota_usage:
            return
        with self._quota_mu:
            p = self._parent_of(path)
            while True:
                u = self._quota_usage.get(p)
                if u is not None:
                    u[0] += d_inodes
                    u[1] += d_bytes
                if p == "/":
                    return
                p = self._parent_of(p)

    def _check_quota(self, path: str, new_inodes: int,
                     new_bytes: int,
                     skip_ancestors_of: "str | None" = None) -> None:
        """≈ FSDirectory.verifyQuota: adding ``new_inodes`` namespace
        entries / ``new_bytes`` replicated bytes at ``path`` must fit
        every quota-carrying ancestor. ``skip_ancestors_of``: for renames,
        quota dirs that ALREADY contain the source subtree are exempt
        (the usage moves within them, net zero)."""
        skip = {q for q, _ in self._quota_ancestors(skip_ancestors_of)} \
            if skip_ancestors_of is not None else set()
        for qpath, ino in self._quota_ancestors(path):
            if qpath in skip:
                continue
            ns_q = ino.get("ns_quota")
            sp_q = ino.get("sp_quota")
            if ns_q is None and sp_q is None:
                continue
            cached = self._quota_usage.get(qpath)
            inodes, consumed = cached if cached is not None \
                else self._subtree_usage(qpath)
            if ns_q is not None and new_inodes \
                    and inodes + new_inodes > ns_q:
                raise QuotaExceededError(
                    f"namespace quota of {qpath} exceeded: "
                    f"quota={ns_q}, count={inodes + new_inodes}")
            if sp_q is not None and new_bytes \
                    and consumed + new_bytes > sp_q:
                raise QuotaExceededError(
                    f"space quota of {qpath} exceeded: quota={sp_q} B, "
                    f"consumed={consumed} B, requested={new_bytes} B")

    def set_quota(self, path: str, ns_quota: "int | None" = None,
                  sp_quota: "int | None" = None) -> None:
        """≈ ClientProtocol.setQuota (dfsadmin -setQuota/-setSpaceQuota):
        superuser only; None leaves a dimension unchanged, -1 clears it."""
        with self._locked(path):
            self._check_safemode()
            self._check_superuser("set quotas")
            inode = self._inode(path)
            if inode["type"] != "dir":
                raise NotADirectoryError(f"quotas apply to dirs: {path}")
            op: dict = {"op": "set_quota", "path": path}
            if ns_quota is not None:
                op["nsq"] = None if ns_quota < 0 else int(ns_quota)
            if sp_quota is not None:
                op["spq"] = None if sp_quota < 0 else int(sp_quota)
            self._log(op)
            self.apply_op(self.namespace, self.counters, op)
            self._audit("setQuota", path)
            if "ns_quota" in inode or "sp_quota" in inode:
                # (re)derive this dir's counters at admin time — the one
                # place a full subtree scan is acceptable
                usage = list(self._subtree_usage(path))
                with self._quota_mu:
                    self._quota_usage[path] = usage
            else:
                with self._quota_mu:
                    self._quota_usage.pop(path, None)

    # ------------------------------------------------------------ client ops

    def create(self, path: str, client: str, replication: int | None,
               block_size: int | None, overwrite: bool) -> dict:
        with self._locked(path, ensure=path):
            self._check_safemode()
            user = self._caller()
            existing = self.namespace.get(path)
            if existing is not None:
                if existing["type"] == "dir":
                    raise IsADirectoryError(path)
                if existing.get("uc"):
                    raise LeaseError(
                        f"{path} already being created by "
                        f"{existing.get('client')}")
                if not overwrite:
                    raise FileExistsError(path)
                # overwrite is a truncate, not an unlink: WRITE on the
                # file itself suffices (HDFS startFile semantics) — the
                # internal delete must not re-check the parent dir
                self._check_access(path, 2, user)
                self._delete_impl(path, recursive=True)
            else:
                # a NEW namespace entry needs write on the parent
                self._check_parent_write(path, user)
                self._check_quota(
                    path, new_inodes=1 + self._missing_ancestors(path),
                    new_bytes=0)
            self._ensure_parents(path, user)
            r = replication or self.default_replication
            bs = block_size or self.default_block_size
            op = {"op": "create", "path": path, "r": r, "bs": bs,
                  "t": _now(), "c": client,
                  "o": user or self.superuser, "g": self.supergroup,
                  "m": 0o644}
            self._log(op)
            self.apply_op(self.namespace, self.counters, op)
            self._charge(path, 1, 0)
            with self._blk:
                lease = self.leases.setdefault(
                    client, {"paths": set(), "renewed": _now()})
                lease["paths"].add(path)
                # wall-clock "renewed" stays for the report surface;
                # expiry (lease_check) compares the monotonic twin so an
                # NTP step can neither mass-expire nor immortalize
                lease["renewed"] = _now()
                lease["renewed_mono"] = time.monotonic()
            self._audit("create", path)
            return {"replication": r, "block_size": bs}

    def append(self, path: str, client: str) -> dict:
        """Reopen a complete file for writing (≈ ClientProtocol.append,
        hdfs/DFSClient.java append path). BLOCK-GRANULAR by design:
        appended data lands in NEW blocks (short tail blocks stay
        short) — the reference appends into the last block under a new
        generation stamp; immutable whole-block datanode storage here
        makes new-blocks the honest equivalent (divergence documented in
        docs/OPERATIONS.md)."""
        with self._locked(path):
            self._check_safemode()
            user = self._caller()
            inode = self._inode(path)
            if inode["type"] != "file":
                raise IsADirectoryError(path)
            if inode.get("uc"):
                raise LeaseError(
                    f"{path} already open for writing by "
                    f"{inode.get('client')}")
            self._check_access(path, 2, user)
            op = {"op": "append_open", "path": path, "c": client,
                  "t": _now()}
            self._log(op)
            self.apply_op(self.namespace, self.counters, op)
            with self._blk:
                # pre-existing blocks are already in total_known_blocks
                self._uc_counted[path] = len(inode.get("blocks", []))
                lease = self.leases.setdefault(
                    client, {"paths": set(), "renewed": _now()})
                lease["paths"].add(path)
                lease["renewed"] = _now()
                lease["renewed_mono"] = time.monotonic()
            self._audit("append", path)
            return {"block_size": inode["block_size"],
                    "replication": inode.get("replication", 1)}

    def fsync(self, path: str, client: str, last_block_size: int) -> None:
        """Publish the last block's true size while the file stays open
        (≈ ClientProtocol.fsync — the hflush visibility point: readers
        see everything up to the last fsync'd block, never the writer's
        unflushed buffer)."""
        with self._locked(path):
            inode = self._inode(path)
            if not inode.get("uc") or inode.get("client") != client:
                raise LeaseError(
                    f"{client} does not hold the lease on {path}")
            if inode["blocks"] and last_block_size >= 0:
                bid = inode["blocks"][-1][0]
                op = {"op": "block_size", "path": path, "bid": bid,
                      "size": last_block_size}
                self._log(op)
                self.apply_op(self.namespace, self.counters, op)
                # settle the optimistic full-block charge now; the
                # client resets its prev-size so add_block/close never
                # re-settle the same block
                self._charge(path, 0,
                             (last_block_size - inode["block_size"])
                             * inode.get("replication", 1))

    def add_block(self, path: str, client: str,
                  prev_block_size: int = -1,
                  excluded: list[str] | None = None) -> dict:
        with self._locked(path):
            self._check_safemode()
            inode = self._inode(path)
            if not inode.get("uc") or inode.get("client") != client:
                raise LeaseError(f"{client} does not hold the lease on {path}")
            if inode["blocks"] and prev_block_size >= 0:
                bid = inode["blocks"][-1][0]
                op = {"op": "block_size", "path": path, "bid": bid,
                      "size": prev_block_size}
                self._log(op)
                self.apply_op(self.namespace, self.counters, op)
                # the previous block was charged a FULL block up front;
                # its real size is now known — settle the difference
                self._charge(path, 0,
                             (prev_block_size - inode["block_size"])
                             * inode.get("replication", 1))
            # space quota: a new block may consume up to block_size ×
            # replication (verifyQuota charges the full block up front)
            self._check_quota(path, new_inodes=0,
                              new_bytes=inode["block_size"]
                              * inode.get("replication", 1))
            with self._blk:
                # id allocation under the blocks lock (any stripe may
                # allocate); journal order may differ from allocation
                # order across stripes — apply_op's monotonic-max on
                # these counters makes replay order-independent
                bid = self.counters["next_block"]
                gen = self.counters["gen"]
                self.counters["next_block"] = bid + 1
                targets = self._choose_targets(inode["replication"],
                                               set(excluded or []))
            self._log({"op": "counters", "values":
                       {"next_block": bid + 1, "gen": gen}})
            if not targets:
                raise IOError("no DataNodes available for replication")
            op = {"op": "add_block", "path": path, "bid": bid}
            self._log(op)
            self.apply_op(self.namespace, self.counters, op)
            self._charge(path, 0,
                         inode["block_size"] * inode.get("replication", 1))
            with self._blk:
                self.block_to_path[bid] = path
            return {"block_id": bid, "gen": gen, "targets": targets}

    def abandon_block(self, path: str, client: str, block_id: int) -> None:
        """Client hit a pipeline failure: drop the block and let it retry
        (≈ ClientProtocol.abandonBlock). Validated BEFORE journaling — a
        bad op must never reach the edit log (replay has no error
        handling by design: a journaled op is a committed fact), and only
        the lease holder of an under-construction file may abandon, else
        any client could strip blocks from closed files."""
        with self._locked(path):
            inode = self.namespace.get(path)
            if inode is None or inode.get("type") != "file":
                raise FileNotFoundError(path)
            if not inode.get("uc") or inode.get("client") != client:
                raise LeaseError(
                    f"{client} does not hold the lease on {path}")
            if not any(b[0] == block_id for b in inode.get("blocks", [])):
                return  # retried abandon: already gone, nothing to charge
            op = {"op": "abandon", "path": path, "bid": block_id}
            self._log(op)
            self.apply_op(self.namespace, self.counters, op)
            self._charge(path, 0, -inode["block_size"]
                         * inode.get("replication", 1))
            with self._blk:
                self.block_to_path.pop(block_id, None)

    def complete(self, path: str, client: str, last_block_size: int) -> None:
        with self._locked(path):
            inode = self._inode(path)
            if not inode.get("uc") or inode.get("client") != client:
                raise LeaseError(f"{client} does not hold the lease on {path}")
            sizes = {}
            if inode["blocks"] and last_block_size >= 0:
                sizes[str(inode["blocks"][-1][0])] = last_block_size
            op = {"op": "close", "path": path, "sizes": sizes}
            self._log(op)
            self.apply_op(self.namespace, self.counters, op)
            self._audit("completeFile", path)
            if sizes:  # settle the last block's optimistic full charge
                self._charge(path, 0,
                             (last_block_size - inode["block_size"])
                             * inode.get("replication", 1))
            with self._blk:
                self.total_known_blocks += (len(inode["blocks"])
                                            - self._uc_counted.pop(path, 0))
                lease = self.leases.get(client)
                if lease:
                    lease["paths"].discard(path)

    def renew_lease(self, client: str) -> None:
        with self._blk:
            lease = self.leases.get(client)
            if lease:
                lease["renewed"] = _now()
                lease["renewed_mono"] = time.monotonic()

    def get_block_locations(self, path: str) -> list[dict]:
        with self._locked(path):
            inode = self._inode(path)
            if inode["type"] != "file":
                raise IsADirectoryError(path)
            self._check_access(path, 4, self._caller())
            out = []
            with self._blk:
                for bid, size in inode["blocks"]:
                    # shuffled, not sorted: with hot-block auto-replication
                    # adding replicas, clients that all read locations[0]
                    # would keep hammering one datanode — randomizing the
                    # order spreads a hot block's reads across its replicas
                    locs = list(self.block_locations.get(bid, ()))
                    random.shuffle(locs)
                    out.append({"block_id": bid,
                                "size": self.block_sizes.get(bid, size),
                                "locations": locs})
            return out

    # ------------------------------------------------------------ namespace

    def mkdirs(self, path: str) -> bool:
        with self._locked(path, ensure=path):
            self._check_safemode()
            if path in self.namespace:
                return self.namespace[path]["type"] == "dir"
            user = self._caller()
            self._check_parent_write(path, user)
            self._check_quota(
                path, new_inodes=1 + self._missing_ancestors(path),
                new_bytes=0)
            # parents only — creating the target through _ensure_parents
            # AND the op below would double-charge its quota inode
            self._ensure_parents(path, user)
            op = {"op": "mkdir", "path": path, "t": _now(),
                  "o": user or self.superuser, "g": self.supergroup,
                  "m": 0o755}
            self._log(op)
            self.apply_op(self.namespace, self.counters, op)
            self._charge(path, 1, 0)
            self._audit("mkdirs", path)
            return True

    def delete(self, path: str, recursive: bool = True) -> bool:
        # _locked(path) covers the whole subtree: every descendant of a
        # deep-enough path shares its stripe (see nslock.py)
        with self._locked(path):
            self._check_safemode()
            if path not in self.namespace:
                return False
            self._check_access(self._parent_of(path), 2, self._caller())
            out = self._delete_impl(path, recursive)
            if out:
                self._audit("delete", path)
            return out

    def _delete_impl(self, path: str, recursive: bool) -> bool:
        """Delete body, no permission check — for callers that already
        authorized the operation (create-with-overwrite checks WRITE on
        the file; re-checking the parent here would wrongly deny an
        owner overwriting their own file in a read-only dir)."""
        inode = self.namespace.get(path)
        if inode is None:
            return False
        children = [k for k in list(self.namespace)
                    if k.startswith(path.rstrip("/") + "/")]
        if inode["type"] == "dir" and children and not recursive:
            raise OSError(f"{path} is a non-empty directory")
        # schedule replica invalidation on the owning DataNodes; tally
        # the removed usage for the quota counters in the same pass
        doomed: list[int] = []
        removed_bytes = 0
        counted_removed = 0
        with self._blk:
            for k in children + [path]:
                node = self.namespace.get(k, {})
                if node.get("type") == "file":
                    blocks = node.get("blocks", [])
                    doomed.extend(b[0] for b in blocks)
                    repl = node.get("replication", 1)
                    # only blocks actually IN total_known_blocks leave it:
                    # a uc file's post-open blocks were never added (its
                    # pre-open count lives in _uc_counted), so decrementing
                    # per doomed block would drift the safemode denominator
                    counted_removed += (self._uc_counted.pop(k, 0)
                                        if node.get("uc") else len(blocks))
                    if node.get("uc") and blocks:
                        # the in-flight last block was charged a FULL block
                        # at add_block and never settled — refund what was
                        # charged, not its (still-zero) recorded size, or
                        # the phantom charge outlives the file
                        removed_bytes += (
                            sum(self.block_sizes.get(b[0], b[1])
                                for b in blocks[:-1])
                            + node["block_size"]) * repl
                    else:
                        removed_bytes += sum(
                            self.block_sizes.get(b[0], b[1])
                            for b in blocks) * repl
        with self._quota_mu:
            for k in children + [path]:
                self._quota_usage.pop(k, None)
        op = {"op": "delete", "path": path}
        self._log(op)
        self.apply_op(self.namespace, self.counters, op)
        self._charge(path, -(len(children) + 1), -removed_bytes)
        with self._blk:
            for bid in doomed:
                for addr in self.block_locations.pop(bid, set()):
                    self.commands.setdefault(addr, []).append(
                        {"type": "delete", "block_id": bid})
                self.block_sizes.pop(bid, None)
                self.block_to_path.pop(bid, None)
                self.hot_boost.pop(bid, None)
            self.total_known_blocks = max(
                0, self.total_known_blocks - counted_removed)
        return True

    def rename(self, src: str, dst: str) -> bool:
        # both subtrees' stripes, ascending (nslock sorts the union).
        # The dir-target rewrite below only APPENDS a component, which
        # never changes a >=depth path's stripe key, so locking the
        # caller's dst up front stays correct.
        with self._locked(src, dst, ensure=dst):
            self._check_safemode()
            if src not in self.namespace:
                return False
            user = self._caller()
            self._check_access(self._parent_of(src), 2, user)
            if dst in self.namespace and self.namespace[dst]["type"] == "dir":
                dst = dst.rstrip("/") + "/" + src.rsplit("/", 1)[-1]
            if dst in self.namespace:
                return False
            self._check_parent_write(dst, user)
            # the moved subtree charges dst-side quotas (FSDirectory.
            # verifyQuotaForRename); quota dirs already containing src
            # are net-zero and exempt
            sub_inodes, sub_bytes = self._subtree_usage(src)
            src_ino = self.namespace[src]
            if src_ino.get("type") == "file":
                sub_bytes += sum(self.block_sizes.get(b[0], b[1])
                                 for b in src_ino.get("blocks", [])) \
                    * src_ino.get("replication", 1)
            self._check_quota(
                dst,
                new_inodes=1 + sub_inodes + self._missing_ancestors(dst),
                new_bytes=sub_bytes, skip_ancestors_of=src)
            self._ensure_parents(dst, user)
            op = {"op": "rename", "path": src, "dst": dst}
            self._log(op)
            self.apply_op(self.namespace, self.counters, op)
            # blocks moved with their files: refresh the reverse index
            prefix = dst.rstrip("/") + "/"
            with self._blk:
                for k, v in self._ns_items():
                    if (k == dst or k.startswith(prefix)) \
                            and v.get("type") == "file":
                        for b in v.get("blocks", []):
                            self.block_to_path[b[0]] = k
            # quota counters: the subtree's usage leaves src's ancestors
            # and lands under dst's; cached entries for quota dirs INSIDE
            # the subtree move key
            src_prefix = src.rstrip("/") + "/"
            with self._quota_mu:
                moved_q = [(k, v) for k, v in self._quota_usage.items()
                           if k == src or k.startswith(src_prefix)]
                for k, v in moved_q:
                    del self._quota_usage[k]
                    self._quota_usage[dst + k[len(src):]] = v
            # open-file counted-block entries move with their paths, or
            # a later close would pop a stale/absent key and corrupt the
            # safemode denominator
            with self._blk:
                moved_uc = [k for k in self._uc_counted
                            if k == src or k.startswith(src_prefix)]
                for k in moved_uc:
                    self._uc_counted[dst + k[len(src):]] = \
                        self._uc_counted.pop(k)
            self._charge(src, -(1 + sub_inodes), -sub_bytes)
            self._charge(dst, 1 + sub_inodes, sub_bytes)
            self._audit("rename", src, dst=dst)
            return True

    def set_replication(self, path: str, replication: int) -> bool:
        with self._locked(path):
            self._check_safemode()
            inode = self._inode(path)
            if inode["type"] != "file":
                return False
            self._check_access(path, 2, self._caller())
            old = inode.get("replication", 1)
            size = sum(self.block_sizes.get(b[0], b[1])
                       for b in inode.get("blocks", []))
            if replication > old:
                self._check_quota(path, new_inodes=0,
                                  new_bytes=size * (replication - old))
            op = {"op": "set_repl", "path": path, "r": replication}
            self._log(op)
            self.apply_op(self.namespace, self.counters, op)
            self._charge(path, 0, size * (replication - old))
            self._audit("setReplication", path, perm=str(replication))
            return True

    def set_permission(self, path: str, mode: int) -> None:
        """chmod ≈ FSNamesystem.setPermission: owner or superuser only."""
        with self._locked(path):
            self._check_safemode()
            inode = self._inode(path)
            user = self._caller()
            if (self.permissions_enabled and user is not None
                    and user != self.superuser
                    and user != inode.get("owner", "")):
                raise PermissionError(
                    f"Permission denied: only the owner "
                    f"({inode.get('owner', '?')}) or the superuser may "
                    f"chmod {path}")
            op = {"op": "chmod", "path": path, "m": int(mode) & 0o7777}
            self._log(op)
            self.apply_op(self.namespace, self.counters, op)
            self._audit("setPermission", path,
                        perm=oct(int(mode) & 0o7777))

    def set_owner(self, path: str, owner: "str | None" = None,
                  group: "str | None" = None) -> None:
        """chown ≈ FSNamesystem.setOwner: owner changes need the superuser;
        the file owner may change its group to one of their own groups."""
        with self._locked(path):
            self._check_safemode()
            inode = self._inode(path)
            user = self._caller()
            if self.permissions_enabled and user is not None \
                    and user != self.superuser:
                if owner:
                    raise PermissionError(
                        "Permission denied: only the superuser may change "
                        f"the owner of {path}")
                if group and (user != inode.get("owner", "")
                              or group not in self._groups_of(user)):
                    raise PermissionError(
                        f"Permission denied: user={user} may not move "
                        f"{path} into group {group}")
            op = {"op": "chown", "path": path, "o": owner or "",
                  "g": group or ""}
            self._log(op)
            self.apply_op(self.namespace, self.counters, op)
            self._audit("setOwner", path,
                        perm=f"{owner or ''}:{group or ''}")

    def get_status(self, path: str) -> dict:
        with self._locked(path):
            inode = self._inode(path)
            perms = {"owner": inode.get("owner", ""),
                     "group": inode.get("group", ""),
                     "mode": inode.get("mode",
                                       0o755 if inode["type"] == "dir"
                                       else 0o644)}
            if inode["type"] == "dir":
                return {"path": path, "is_dir": True, "length": 0,
                        "mtime": inode.get("mtime", 0), **perms}
            length = sum(self.block_sizes.get(bid, size)
                         for bid, size in inode["blocks"])
            return {"path": path, "is_dir": False, "length": length,
                    "replication": inode["replication"],
                    "block_size": inode["block_size"],
                    "mtime": inode.get("mtime", 0),
                    "under_construction": bool(inode.get("uc")), **perms}

    def list_status(self, path: str) -> list[dict]:
        with self._locked(path):
            inode = self._inode(path)
            if inode["type"] != "dir":
                return [self.get_status(path)]
            prefix = path.rstrip("/") + "/"
            # snapshot scan: a shallow dir's listing spans stripes this
            # op does not hold — names from a GIL-atomic key snapshot,
            # statuses re-validated per child by get_status
            names = {k for k in list(self.namespace)
                     if k.startswith(prefix) and k != path
                     and "/" not in k[len(prefix):]}
            return [self.get_status(k) for k in sorted(names)]

    def exists(self, path: str) -> bool:
        # lock-free: a single dict membership test is GIL-atomic, and
        # any striped answer would be equally stale by return time
        return path in self.namespace

    # ------------------------------------------------------------ datanodes

    def register_datanode(self, addr: str, capacity: int) -> None:
        # rack resolution may exec the operator script — never under the
        # namesystem lock (a slow script would stall the control plane)
        rack = self.topology.add(addr)
        # admission check (may lazily read the hosts files) outside the
        # lock, like rack resolution above; the cached include/exclude
        # sets are replaced atomically by refresh_nodes
        admission = self._dn_admission(addr)
        with self._blk:
            if admission == "refuse":
                # ≈ DisallowedDatanodeException: host absent from a
                # configured dfs.hosts include list
                raise PermissionError(
                    f"datanode {addr} is not in the dfs.hosts include "
                    f"list; registration refused")
            self.datanodes[addr] = {"addr": addr, "capacity": capacity,
                                    "used": 0, "last_seen": _now(),
                                    # monotonic twin of last_seen: the
                                    # expiry deadline must survive NTP
                                    # steps (last_seen stays wall-clock
                                    # for the report/display surface)
                                    "seen_mono": time.monotonic(),
                                    "blocks": 0, "rack": rack}
            self.commands.setdefault(addr, [])
            if admission == "drain" and addr not in self.decommissioning:
                # excluded hosts register and immediately start draining
                # (verifyNodeRegistration's "registered but being
                # decommissioned" case)
                self._log_decommission(addr, "decommissioning")

    def dn_heartbeat(self, addr: str, used: int, capacity: int,
                     block_count: int,
                     hot_blocks: "dict | None" = None) -> list[dict]:
        with self._blk:
            info = self.datanodes.get(addr)
            if info is None:
                # unknown (expired / NN restarted): tell it to re-register
                # and send a fresh block report (≈ DNA_REGISTER)
                return [{"type": "register"}]
            info.update(used=used, capacity=capacity, last_seen=_now(),
                        seen_mono=time.monotonic(), blocks=block_count)
            cmds = self.commands.get(addr, [])
            self.commands[addr] = []
        # fold the piggybacked read-frequency slice OUTSIDE the
        # namespace lock (the hot-block table has its own leaf mutex);
        # a replace-fold means a re-delivered heartbeat is idempotent
        self.hot_blocks.fold(addr, hot_blocks)
        return cmds

    def block_report(self, addr: str, blocks: list[list[int]]) -> list[int]:
        """Full report: rebuild this node's locations; returns block ids the
        node should delete (orphans of deleted files)."""
        with self._blk:
            known = {bid for _, i in self._ns_items()
                     if i.get("type") == "file"
                     for bid, _ in i.get("blocks", [])}
            invalid: list[int] = []
            for locs in self.block_locations.values():
                locs.discard(addr)
            for bid, size in blocks:
                if bid in known:
                    self.block_locations.setdefault(bid, set()).add(addr)
                    self.block_sizes[bid] = size
                else:
                    invalid.append(bid)
            self._maybe_leave_safemode()
            return invalid

    def block_received(self, addr: str, block_id: int, size: int) -> None:
        with self._blk:
            self.block_locations.setdefault(block_id, set()).add(addr)
            self.block_sizes[block_id] = size
            self._maybe_leave_safemode()

    def _choose_targets(self, replication: int,
                        excluded: set[str]) -> list[str]:
        """Rack-aware placement ≈ ReplicationTargetChooser: the second
        replica goes to a DIFFERENT rack than the first (rack-failure
        tolerance), remaining replicas spread by load. On a flat topology
        (all /default-rack) this collapses to spread-by-load."""
        # decommissioning nodes take no NEW replicas (they are draining)
        live = [a for a, d in self.datanodes.items()
                if a not in excluded and a not in self.decommissioning]
        live.sort(key=lambda a: (self.datanodes[a]["used"], random.random()))
        if len(live) <= 1 or replication <= 1:
            return live[:replication]
        chosen = [live[0]]
        first_rack = self.topology.rack_of(live[0])
        rest = live[1:]
        off_rack = [a for a in rest
                    if self.topology.rack_of(a) != first_rack]
        if off_rack:
            chosen.append(off_rack[0])
            rest = [a for a in rest if a != off_rack[0]]
        for a in rest:
            if len(chosen) >= replication:
                break
            chosen.append(a)
        return chosen[:replication]

    # ------------------------------------------------------------ monitors

    def heartbeat_check(self, expiry_s: float) -> None:
        """Remove dead DataNodes; their replicas become under-replicated
        (≈ FSNamesystem.heartbeatCheck → removeDatanode)."""
        with self._blk:
            now = time.monotonic()
            dead = [a for a, d in self.datanodes.items()
                    if now - d.get("seen_mono", now) > expiry_s]
            for addr in dead:
                del self.datanodes[addr]
                self.commands.pop(addr, None)
                for locs in self.block_locations.values():
                    locs.discard(addr)
        for addr in dead:
            # a dead node's read counts leave the hot-block view with it
            self.hot_blocks.drop(addr)

    def replication_check(self) -> int:
        """One ReplicationMonitor sweep: schedule copies for
        under-replicated finalized blocks, deletes for over-replicated.
        Returns the number of commands scheduled. A hot-block boost
        (hotblock_check) raises a block's target above the file's
        replication; when the boost expires the same over-replication
        branch that trims manual set_replication drops trims it back."""
        with self._blk:
            if self.safemode or not self.datanodes:
                return 0
            healthy_nodes = [a for a in self.datanodes
                             if a not in self.decommissioning]
            scheduled = 0
            for path, inode in self._ns_items():
                if inode.get("type") != "file" or inode.get("uc"):
                    continue
                base_want = min(inode["replication"],
                                max(1, len(healthy_nodes)))
                for bid, _ in inode["blocks"]:
                    boost = self.hot_boost.get(bid, {}).get("boost", 0)
                    want = min(max(base_want, boost),
                               max(1, len(healthy_nodes)))
                    locs = {a for a in self.block_locations.get(bid, set())
                            if a in self.datanodes}
                    # replicas on draining nodes don't count toward the
                    # target (decommission = copy everything off first),
                    # but they remain valid COPY SOURCES
                    good = {a for a in locs
                            if a not in self.decommissioning}
                    if locs and len(good) < want:
                        targets = self._choose_targets(
                            want - len(good), excluded=locs)
                        if targets:
                            src = sorted(good or locs)[0]
                            self.commands.setdefault(src, []).append(
                                {"type": "replicate", "block_id": bid,
                                 "targets": targets})
                            scheduled += 1
                    elif len(good) > want:
                        for addr in sorted(good)[want:]:
                            self.commands.setdefault(addr, []).append(
                                {"type": "delete", "block_id": bid})
                            self.block_locations[bid].discard(addr)
                            scheduled += 1
            return scheduled

    def hotblock_check(self) -> int:
        """One hot-block policy sweep: close the loop from the cluster
        read-frequency view (datanode SpaceSaving sketches folded by
        dn_heartbeat) to replica placement. A block whose share of all
        tracked reads crosses ``tdfs.hotblocks.replicate.share`` (with a
        minimum absolute read count, so an idle cluster's 100%-share
        singleton block isn't "hot") gets a replication BOOST up to
        ``tdfs.hotblocks.replicate.cap``; the next replication_check
        sweep schedules the extra copies. A block that stops being hot
        for ``tdfs.hotblocks.cool.s`` loses the boost and the same sweep
        trims the extra replicas back. Returns boosted + expired count
        (a "changed" tally for the monitor log)."""
        rows = self.hot_blocks.top(32)
        total = self.hot_blocks.total_reads()
        now = time.monotonic()
        changed = 0
        with self._blk:
            if self.safemode:
                return 0
            cap = min(self.hot_cap, max(1, len(self.datanodes)))
            for r in rows:
                try:
                    bid = int(r["block"])
                except (TypeError, ValueError):
                    continue
                share = (r["reads"] / total) if total else 0.0
                if share >= self.hot_share and r["reads"] >= \
                        self.hot_min_reads:
                    if bid not in self.hot_boost:
                        changed += 1
                    self.hot_boost[bid] = {
                        "boost": cap, "share": share, "hot_mono": now}
            for bid in list(self.hot_boost):
                if now - self.hot_boost[bid]["hot_mono"] > self.hot_cool_s:
                    del self.hot_boost[bid]
                    changed += 1
        return changed

    def decommission_check(self) -> None:
        """Promote draining nodes to 'decommissioned' once every block
        they host has enough replicas elsewhere (≈ FSNamesystem.
        checkDecommissionStateInternal)."""
        with self._blk:
            for addr, state in list(self.decommissioning.items()):
                if state != "decommissioning":
                    continue
                if addr not in self.datanodes:
                    # died mid-drain: its blocks were NOT verified safe —
                    # stay 'decommissioning' so the operator sees the
                    # drain never completed (never report a dead node as
                    # safely decommissioned)
                    continue
                done = True
                for bid, locs in self.block_locations.items():
                    if addr not in locs:
                        continue
                    path = self.block_to_path.get(bid)
                    ino = self.namespace.get(path) if path else None
                    if ino is None:
                        continue
                    healthy = [a for a in self.datanodes
                               if a not in self.decommissioning]
                    want = min(ino.get("replication", 1),
                               max(1, len(healthy)))
                    good = {a for a in locs if a in self.datanodes
                            and a not in self.decommissioning}
                    if len(good) < want:
                        done = False
                        break
                if done:
                    self._log_decommission(addr, "decommissioned")

    def _log_decommission(self, addr: str, state: "str | None") -> None:
        op = {"op": "decommission", "addr": addr, "state": state}
        self._log(op)
        self.apply_op(self.namespace, self.counters, op)
        # counters may have been swapped by a checkpoint reload: re-bind
        self.decommissioning = self.counters.setdefault(
            "decommissioning", {})

    def refresh_nodes(self) -> dict:
        """≈ FSNamesystem.refreshNodes (dfsadmin -refreshNodes):
        re-read ``dfs.hosts`` / ``dfs.hosts.exclude`` and reconcile
        every known DataNode — removed-from-include ⇒ decommissioned
        outright; newly excluded ⇒ start draining; removed from exclude
        ⇒ stop draining. The stop case only applies when at least one
        hosts file is configured: an operator draining nodes via
        ``-decommission ADDR start`` (our addr-keyed alternative the
        reference lacks) must not have the drain silently canceled by a
        refresh against NO lists — a deliberate, documented divergence.
        Registration of disallowed hosts is refused
        (≈ verifyNodeRegistration / DisallowedDatanodeException)."""
        from tpumr.utils.hostsfile import read_hosts_lists
        # file I/O BEFORE the namesystem lock (same principle as rack
        # resolution in register_datanode: a slow NFS-mounted hosts
        # file must not stall every namespace RPC)
        include, exclude = read_hosts_lists(
            self.conf, "dfs.hosts", "dfs.hosts.exclude")
        with self._blk:
            self._check_superuser("refresh datanode admission lists")
            self._dn_include, self._dn_exclude = include, exclude
            # "configured" = the operator manages admission via FILES
            # (key set, even if currently empty — emptying the exclude
            # file is exactly how the reference un-drains everything);
            # only with NO keys do manual addr-keyed drains survive
            configured = bool(self.conf.get("dfs.hosts")) \
                or bool(self.conf.get("dfs.hosts.exclude"))
            changed: dict[str, str] = {}
            for addr in list(self.datanodes) + list(self.decommissioning):
                host = addr.split(":")[0]
                state = self.decommissioning.get(addr)
                if include is not None and host not in include:
                    # case 2 — but never flip a DEAD mid-drain node to
                    # "decommissioned": its blocks were not confirmed
                    # safe elsewhere (the decommission_check invariant)
                    if state != "decommissioned" \
                            and addr in self.datanodes:
                        self._log_decommission(addr, "decommissioned")
                        changed[addr] = "decommissioned"
                elif host in exclude:
                    if state is None:                    # case 3
                        self._log_decommission(addr, "decommissioning")
                        changed[addr] = "decommissioning"
                elif configured and state is not None:   # case 4
                    self._log_decommission(addr, None)
                    changed[addr] = "in-service"
            return {"included": (sorted(include) if include is not None
                                 else "*"),
                    "excluded": sorted(exclude),
                    "changed": changed}

    def _dn_admission(self, addr: str) -> str:
        """'refuse' (not in a configured include list), 'drain' (in the
        exclude list — registers, then decommissions, the reference's
        verifyNodeRegistration contract), or 'ok'."""
        if not hasattr(self, "_dn_include"):
            from tpumr.utils.hostsfile import read_hosts_lists
            self._dn_include, self._dn_exclude = read_hosts_lists(
                self.conf, "dfs.hosts", "dfs.hosts.exclude")
        host = addr.split(":")[0]
        if self._dn_include is not None and host not in self._dn_include:
            return "refuse"
        if host in self._dn_exclude:
            return "drain"
        return "ok"

    def set_decommission(self, addr: str, action: str = "start") -> str:
        """Admin: start/stop draining a DataNode (≈ dfsadmin exclude +
        refreshNodes). Journaled — the drain survives NN restarts.
        Returns the node's current state."""
        with self._blk:
            self._check_superuser("decommission datanodes")
            if action == "start" and addr not in self.decommissioning:
                self._log_decommission(addr, "decommissioning")
            elif action == "stop":
                self._log_decommission(addr, None)
            return self.decommissioning.get(addr, "in-service")

    def lease_check(self) -> None:
        """Expire hard-limit leases: finalize the file with whatever blocks
        were reported (lease recovery, simplified). Two-phase under
        striping: collect expired (client, paths) under the blocks lock,
        then recover each path under ITS stripe (journaling needs the
        stripe, and leases rank ABOVE stripes so the reverse nesting
        would violate the rank order). Each path re-validates — a writer
        renewing or completing between the phases wins."""
        # expiry runs on the monotonic twin (renewed_mono): a
        # wall-clock step must not mass-expire every writer's lease
        now = time.monotonic()
        with self._blk:
            expired = [(client, sorted(lease["paths"]))
                       for client, lease in self.leases.items()
                       if now - lease.get("renewed_mono", now)
                       > self.lease_hard_limit]
        for client, paths in expired:
            for path in paths:
                with self._locked(path):
                    with self._blk:
                        lease = self.leases.get(client)
                        if lease is None or now - lease.get(
                                "renewed_mono", now) <= \
                                self.lease_hard_limit:
                            break  # renewed since phase 1: nothing to do
                        inode = self.namespace.get(path)
                        if inode is None or not inode.get("uc") \
                                or inode.get("client") != client:
                            lease["paths"].discard(path)
                            continue
                        sizes = {str(bid): self.block_sizes.get(bid, size)
                                 for bid, size in inode["blocks"]}
                    op = {"op": "close", "path": path, "sizes": sizes}
                    self._log(op)
                    self.apply_op(self.namespace, self.counters, op)
                    with self._blk:
                        self.total_known_blocks += (
                            len(inode["blocks"])
                            - self._uc_counted.pop(path, 0))
                        lease = self.leases.get(client)
                        if lease is not None:
                            lease["paths"].discard(path)
            with self._blk:
                lease = self.leases.get(client)
                if lease is not None and not lease["paths"] \
                        and now - lease.get("renewed_mono", now) \
                        > self.lease_hard_limit:
                    del self.leases[client]

    # ------------------------------------------------------------ fsck

    def report_bad_block(self, block_id: int, addr: str) -> None:
        """Client found a checksum-corrupt replica (≈ ClientProtocol.
        reportBadBlocks): forget the location, tell the node to delete its
        copy, and let replication_check re-replicate from a good one.
        Safety rails: the caller must be able to READ the owning file
        (a report is as destructive as a delete), unknown blocks/locations
        are ignored, and the LAST live replica is never invalidated — a
        spurious report (or a transport error mistaken for corruption)
        must not be able to destroy the only copy (the HDFS rule)."""
        with self._blk:
            locs = self.block_locations.get(block_id)
            if not locs or addr not in locs:
                return
            path = self.block_to_path.get(block_id)
            if path is not None:
                self._check_access(path, 4, self._caller())
            self.corrupt_replicas.setdefault(block_id, set()).add(addr)
            if len(locs) <= 1:
                return  # recorded as corrupt, but keep the last copy
            locs.discard(addr)
            self.commands.setdefault(addr, []).append(
                {"type": "delete", "block_id": block_id})

    def fsck(self, path: str = "/") -> dict:
        """Namespace health walk ≈ NamenodeFsck.check: per-file block
        accounting against live replica locations. Needs a CONSISTENT
        namespace × block-map view, so it takes the structural lock
        (all stripes) plus the blocks lock — the one reader that still
        pays the full stop-the-world price, by design."""
        with self.locks.structural(), self._blk:
            report: dict = {"path": path, "files": 0, "dirs": 0,
                            "blocks": 0, "size": 0,
                            "under_replicated": [], "missing": [],
                            "corrupt": [], "over_replicated": [],
                            "open_files": []}
            prefix = "/" if path == "/" else path.rstrip("/") + "/"
            for p in sorted(self.namespace):
                if not (p == path or p.startswith(prefix)):
                    continue
                inode = self.namespace[p]
                if inode["type"] == "dir":
                    report["dirs"] += 1
                    continue
                if inode.get("uc"):
                    report["open_files"].append(p)
                    continue
                report["files"] += 1
                want = inode.get("replication", 1)
                for bid, size in inode.get("blocks", []):
                    report["blocks"] += 1
                    report["size"] += self.block_sizes.get(bid, size)
                    live = len(self.block_locations.get(bid, ()))
                    if bid in self.corrupt_replicas and live == 0:
                        report["corrupt"].append(
                            {"path": p, "block_id": bid,
                             "bad_replicas":
                                 sorted(self.corrupt_replicas[bid])})
                    elif live == 0:
                        report["missing"].append(
                            {"path": p, "block_id": bid})
                    elif live < want:
                        report["under_replicated"].append(
                            {"path": p, "block_id": bid,
                             "live": live, "want": want})
                    elif live > want:
                        report["over_replicated"].append(
                            {"path": p, "block_id": bid,
                             "live": live, "want": want})
            report["healthy"] = not (report["missing"] or report["corrupt"])
            return report

    def trash_emptier_check(self) -> int:
        """One Emptier pass over EVERY user's trash (≈ Trash.Emptier,
        which runs on the NameNode): seal each /user/<u>/.Trash/Current
        into a timestamp checkpoint and delete checkpoints older than
        fs.trash.interval. In-process calls bypass permissions — the
        emptier acts as the namesystem. Returns checkpoints expunged."""
        import re as _re
        interval_s = float(self.conf.get("fs.trash.interval", 0)) * 60
        if interval_s <= 0:
            return 0
        # key-snapshot scans (GIL-atomic): the emptier only needs a
        # candidate list — rename/delete below take their own stripes
        # and re-validate, so a racing writer is handled there
        roots = [p for p in list(self.namespace)
                 if _re.match(r"^/user/[^/]+/\.Trash$", p)]
        expunged = 0
        now = _now()
        for root in roots:
            current = root + "/Current"
            if current in self.namespace:
                ts = int(now)
                while f"{root}/{ts}" in self.namespace:
                    ts += 1
                self.rename(current, f"{root}/{ts}")
            stamps = [p for p in list(self.namespace)
                      if p.startswith(root + "/")
                      and p[len(root) + 1:].isdigit()
                      and "/" not in p[len(root) + 1:]]
            for stamp in stamps:
                if now - int(stamp.rsplit("/", 1)[1]) >= interval_s:
                    self.delete(stamp, recursive=True)
                    expunged += 1
        return expunged

    # ------------------------------------------------------------ admin

    def save_namespace(self) -> None:
        """Checkpoint in place (image ∪ edits → image; purge merged
        segments). Only the roll and the quota rebuild run under the
        namespace lock — the merge itself reads SEALED segments and the
        image, both owned by ``_ckpt_mu``, so a multi-second replay no
        longer stalls every client RPC (it used to run entirely under
        the lock)."""
        with self._ckpt_mu:
            with self.locks.structural():
                sealed = self.edits.roll()
                self._ckpt_token += 1  # invalidate any in-flight 2NN cycle
                self._checkpoint_segments = []
            namespace, counters = FSImage.load(self.name_dir)
            for op in FSEditLog.replay(self.name_dir, sealed):
                self.apply_op(namespace, counters, op)
            FSImage.save(self.name_dir, namespace, counters)
            FSEditLog.purge(sealed)
            with self.locks.structural():
                self._rebuild_quota_usage()  # self-heal conservative drift

    def edits_bytes(self) -> int:
        """On-disk journal size (auto-checkpoint trigger input)."""
        return self.edits.total_bytes()

    def get_name_state(self) -> dict:
        """Secondary checkpoint fetch (≈ GetImageServlet): ship the image
        plus every SEALED edit segment — as a LIST, preserving segment
        boundaries so the secondary's replay keeps per-segment torn-tail
        recovery (a concatenated blob would let one torn segment swallow
        the ops of every later one). The journal is rolled first; sealed
        segments are purged only when the merged image comes back with
        this fetch's token (put_image)."""
        import os
        from tpumr.dfs.editlog import IMAGE_NAME
        with self._ckpt_mu:
            with self.locks.structural():
                sealed = self.edits.roll()
                self._checkpoint_segments = sealed
                self._ckpt_token += 1  # fetch supersedes any earlier one
                token = self._ckpt_token
            # shipping the image + sealed segments is pure file I/O on
            # state frozen by _ckpt_mu — reading it under the namespace
            # lock would stall every client RPC for the transfer
            image = b"{}"
            img_path = os.path.join(self.name_dir, IMAGE_NAME)
            if os.path.exists(img_path):
                with open(img_path, "rb") as f:
                    image = f.read()
            segments = []
            for seg in sealed:
                try:
                    with open(seg, "rb") as f:
                        segments.append(f.read())
                except FileNotFoundError:
                    pass
            return {"image": image, "segments": segments,
                    "token": token}

    def put_image(self, image: bytes, token: int = -1) -> None:
        """Secondary checkpoint upload (≈ putFSImage + rollFSImage): make
        the merged image durable, THEN purge the segments it covers. The
        token must be the one handed out by the LATEST get_name_state —
        an upload from a superseded fetch (another secondary rolled the
        journal since, or an in-process checkpoint ran) is refused, since
        purging would delete edits its image does not contain."""
        import os
        from tpumr.dfs.editlog import IMAGE_NAME
        with self._ckpt_mu:
            with self.lock:
                # the token can't move while we hold _ckpt_mu (every
                # bump happens under it), so checking here then writing
                # outside the namespace lock is race-free in-process
                if token != self._ckpt_token:
                    raise RuntimeError(
                        "checkpoint signature mismatch: this merge is "
                        "from a superseded get_name_state fetch — "
                        "discarding it")
                segs = list(self._checkpoint_segments)
            tmp = os.path.join(self.name_dir, IMAGE_NAME + ".ckpt")
            with open(tmp, "wb") as f:
                f.write(image)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, os.path.join(self.name_dir, IMAGE_NAME))
            FSEditLog.purge(segs)
            with self.lock:
                self._checkpoint_segments = []

    def get_blocks(self, addr: str, max_blocks: int = 16) -> list[dict]:
        """Blocks hosted on one DataNode (≈ NamenodeProtocol.getBlocks —
        the balancer's feed)."""
        with self._blk:
            out = []
            for bid, locs in self.block_locations.items():
                if addr in locs:
                    out.append({"block_id": bid,
                                "size": self.block_sizes.get(bid, 0),
                                "locations": sorted(locs)})
                    if len(out) >= max_blocks:
                        break
            return out

    def remove_replica(self, addr: str, block_id: int) -> None:
        """Drop one replica (balancer move completion): forget the location
        and tell the node to delete its copy."""
        with self._blk:
            self.block_locations.get(block_id, set()).discard(addr)
            self.commands.setdefault(addr, []).append(
                {"type": "delete", "block_id": block_id})

    def datanode_report(self) -> list[dict]:
        with self._blk:
            out = []
            for addr, d in self.datanodes.items():
                row = dict(d)
                row["state"] = self.decommissioning.get(addr, "in-service")
                out.append(row)
            # decommissioned nodes that already left the cluster
            for addr, state in self.decommissioning.items():
                if addr not in self.datanodes:
                    out.append({"addr": addr, "state": state})
            return out

    def get_hot_blocks(self, n: int = 16) -> list[dict]:
        """Cluster-wide hottest blocks (merged datanode sketches),
        annotated with the owning path — the feed a future
        replicate/devcache-pin policy consumes (ROADMAP "DFS at
        production scale")."""
        rows = self.hot_blocks.top(int(n))
        with self._blk:
            for r in rows:
                try:
                    bid = int(r["block"])
                except (TypeError, ValueError):
                    r["path"] = ""
                    continue
                r["path"] = self.block_to_path.get(bid, "")
                r["replicas"] = len(self.block_locations.get(bid, ()))
                r["boost"] = self.hot_boost.get(bid, {}).get("boost", 0)
        return rows


#: method → service keys ≈ HDFSPolicyProvider: client ops (incl. the
#: dfsadmin surface, which rides ClientProtocol in the reference and is
#: additionally superuser-gated inside the namesystem), DataNode
#: reporting, and the 2NN/balancer NamenodeProtocol tier
NAMENODE_POLICY = {
    m: ["security.datanode.protocol.acl"]
    for m in ("register_datanode", "dn_heartbeat", "block_report",
              "block_received")
}
NAMENODE_POLICY.update({
    m: ["security.namenode.protocol.acl"]
    for m in ("get_name_state", "put_image", "get_blocks",
              "remove_replica")
})
NAMENODE_POLICY["report_bad_block"] = [
    "security.client.protocol.acl", "security.datanode.protocol.acl"]
NAMENODE_POLICY["refresh_service_acl"] = [
    "security.refresh.policy.protocol.acl"]
NAMENODE_POLICY["get_protocol_version"] = [
    "security.client.protocol.acl", "security.datanode.protocol.acl",
    "security.namenode.protocol.acl"]


class NameNode:
    """RPC daemon front (≈ NameNode.java): hosts the namesystem plus the
    monitor threads (heartbeat expiry, replication, lease recovery)."""

    def __init__(self, name_dir: str, conf: Any, host: str = "127.0.0.1",
                 port: int = 0) -> None:
        self.conf = conf
        self.ns = FSNamesystem(name_dir, conf)
        self.dn_expiry_s = float(conf.get("tdfs.datanode.expiry.s", 10))
        # metrics live on the daemon whether or not HTTP is enabled —
        # the lock/editlog/op histograms must exist for the flight
        # recorder and ``simulate -dfs`` even on a headless NN
        from tpumr.metrics import MetricsSystem
        self.metrics = MetricsSystem("namenode")
        self._mreg = self.metrics.new_registry("namenode")
        self.ns.bind_metrics(self._mreg)
        #: lazily-created per-op latency hists (nn_op_seconds{op=}) —
        #: the flight recorder windows these
        self._op_hists: dict[str, Any] = {}
        from tpumr.security import rpc_secret
        self._rpc_secret = rpc_secret(conf)
        self._server = RpcServer(self, host=host, port=port,
                                 secret=self._rpc_secret)
        # per-method rpc_<method> latency/request-size hists + inflight
        # gauges, same auto-instrumentation as the master's server
        self._server.metrics = self.metrics.new_registry("rpc")
        # per-service delegation tokens (≈ ClientProtocol.
        # getDelegationToken / DelegationTokenSecretManager): the
        # NameNode issues + tracks liveness for ITS tokens; JobTracker
        # tokens are a different service's and don't verify here
        from tpumr.security.tokens import TokenStore
        self.token_store = TokenStore(conf)
        self._server.token_store = self.token_store
        # service-level authorization ≈ hadoop-policy.xml (off unless
        # tpumr.security.authorization=true)
        from tpumr.security.authorize import ServiceAuthorizationManager
        self._server.authz = ServiceAuthorizationManager(
            conf, NAMENODE_POLICY, "security.client.protocol.acl")
        # impersonation rules (hadoop.proxyuser.*) are consulted from
        # the daemon conf; without this, doas frames are rejected
        self._server.proxy_conf = conf
        self._stop = threading.Event()
        self.killed = False
        self._monitor = threading.Thread(target=self._monitor_loop,
                                         name="nn-monitors", daemon=True)
        self._http: Any = None
        self._http_port = int(conf.get("tdfs.http.port", -1))
        self.sampler: Any = None  # set by _build_http when prof enabled
        self.flightrec: Any = None  # armed in start() when SLO set

    def start(self) -> "NameNode":
        self._server.start()
        self._monitor.start()
        if self._http_port >= 0:
            self._http = self._build_http(self._http_port).start()
        # armed AFTER http so breach bundles carry folded stacks when
        # the profiler is on; tpumr.nn.incident.slo.ms=0 keeps it off
        from tpumr.metrics.flightrec import NNFlightRecorder
        self.flightrec = NNFlightRecorder.from_conf(self.conf, self,
                                                    self.sampler)
        if self.flightrec is not None:
            self.flightrec.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self.flightrec is not None:
            self.flightrec.stop()
        if self.sampler is not None:
            self.sampler.stop()
        if self._http is not None:
            self._http.stop()
        self._server.stop()
        self.ns.edits.close()

    def kill(self) -> None:
        """SIGKILL-equivalent (the ``nn.crash`` / ``nn_restart`` chaos
        model): stop serving WITHOUT the clean-shutdown editlog close —
        the journal fd is abandoned exactly as a dead process leaves
        it, so the next NameNode on this name_dir must come up through
        image load + editlog replay (with torn-tail sealing) and earn
        its way out of safemode from block reports. In-flight client
        RPCs fail on the wire and ride the client retry policy."""
        self.killed = True
        self._stop.set()
        if self.flightrec is not None:
            self.flightrec.stop()
        if self.sampler is not None:
            self.sampler.stop()
        if self._http is not None:
            self._http.stop()
        self._server.stop()

    @property
    def http_url(self) -> "str | None":
        return self._http.url if self._http is not None else None

    def _build_http(self, port: int):
        """Status endpoints ≈ webapps/hdfs dfshealth.jsp + NameNodeMXBean."""
        from tpumr.http import StatusHttpServer
        srv = StatusHttpServer("namenode", port=port)

        # uniform /metrics (same payload shape as the mapred daemons —
        # one scraper config covers the whole cluster); the system
        # itself lives on the daemon (__init__) so the lock/op/editlog
        # series exist even before/without HTTP
        ms = self.metrics
        reg = self._mreg

        def _ns_gauges() -> dict:
            # lock-free snapshot scan (see FSNamesystem._ns_items): a
            # scrape must never queue behind — or stall — client ops
            items = self.ns._ns_items()
            return {
                "datanodes": len(self.ns.datanodes),
                "safemode": int(self.ns.safemode),
                "files": sum(1 for _, i in items
                             if i.get("type") == "file"),
                "blocks": sum(len(i.get("blocks", []))
                              for _, i in items),
                "audit_emitted": self.ns.audit_emitted,
                "audit_suppressed": self.ns.audit_suppressed,
            }

        reg.set_gauge("namespace", _ns_gauges)
        srv.attach_metrics(ms)

        # continuous profiler: same knob as the mapred daemons, so
        # enabling tpumr.prof.enabled lights /stacks + /flame here too
        from tpumr.metrics.sampler import StackSampler
        self.sampler = StackSampler.from_conf(self.conf, ms)
        if self.sampler is not None:
            self.sampler.start()
            self.sampler.attach_http(srv)

        def summary(q: dict) -> dict:
            ns = self.ns
            items = ns._ns_items()  # lock-free snapshot, like _ns_gauges
            files = sum(1 for _, i in items
                        if i.get("type") == "file")
            dirs = sum(1 for _, i in items
                       if i.get("type") == "dir")
            blocks = sum(len(i.get("blocks", []))
                         for _, i in items)
            return {"files": files, "directories": dirs, "blocks": blocks,
                    "safemode": ns.safemode,
                    "datanodes": len(ns.datanodes)}

        srv.add_json("namenode", summary)
        srv.add_json("datanodes", lambda q: self.ns.datanode_report())
        srv.add_json("fsck", lambda q: self.ns.fsck(q.get("path", "/")))

        # cluster-wide hot-block ranking (merged datanode SpaceSaving
        # slices) — a TOP-LEVEL tool surface like /metrics: the future
        # replicate/devcache-pin policy and operators read the same rows
        def hotblocks(q: dict) -> dict:
            n = int(q.get("n", 16))
            return {"total_reads": self.ns.hot_blocks.total_reads(),
                    "top": self.ns.get_hot_blocks(n)}

        srv.add_raw("hotblocks", hotblocks)
        srv.add_json("hotblocks", hotblocks)

        # incident bundles, same endpoints as the master so one
        # operator workflow covers both roles
        def incidents_json(q: dict) -> list:
            return (self.flightrec.list_incidents()
                    if self.flightrec is not None else [])

        def incident_raw(q: dict) -> dict:
            if self.flightrec is None:
                raise ValueError(
                    "NN flight recorder disabled "
                    "(tpumr.nn.incident.slo.ms is 0)")
            return self.flightrec.read_incident(q["name"])

        srv.add_json("incidents", incidents_json)
        srv.add_raw("incident", incident_raw)

        # HTML view ≈ webapps/hdfs/dfshealth.jsp
        from tpumr.http import html_escape, html_table

        fsck_cache: dict = {"ts": 0.0, "report": None}

        def cached_fsck() -> dict:
            """The full fsck walk holds the namesystem lock — cache it so
            dashboard refreshes/scrapers can't stall client RPCs by
            hammering '/' (≈ dfshealth.jsp reads cached FSNamesystem
            counters, it does not run fsck per request)."""
            import time as _time
            now = _time.monotonic()
            if fsck_cache["report"] is None or \
                    now - fsck_cache["ts"] > 10.0:
                fsck_cache["report"] = self.ns.fsck("/")
                fsck_cache["ts"] = now
            return fsck_cache["report"]

        def index_page(q: dict) -> str:
            s = summary(q)
            fsck = cached_fsck()
            rows = []
            for d in self.ns.datanode_report():
                cap = max(1, d.get("capacity", 1))
                used = d.get("used", 0)
                rows.append([
                    d.get("addr", "?"), d.get("rack", "?"),
                    f"{d.get('blocks', 0)}",
                    f"{used / 1e6:.1f} MB",
                    f"{100 * used / cap:.1f}%",
                ])
            health = ("<span class='ok'>HEALTHY</span>"
                      if fsck["healthy"]
                      else "<span class='bad'>CORRUPT</span>")
            return (
                f"<h1>NameNode — {html_escape(self.ns.name_dir)}</h1>"
                f"<p>{s['files']} files · {s['directories']} dirs · "
                f"{s['blocks']} blocks · "
                f"{'SAFEMODE · ' if s['safemode'] else ''}"
                f"{s['datanodes']} datanodes · filesystem {health}</p>"
                f"<p>under-replicated {len(fsck['under_replicated'])} · "
                f"missing {len(fsck['missing'])} · corrupt "
                f"{len(fsck['corrupt'])}</p><h2>DataNodes</h2>"
                + html_table(["address", "rack", "blocks", "used",
                              "used %"], rows))

        srv.add_page("index", index_page)
        return srv

    @property
    def address(self) -> tuple[str, int]:
        return self._server.address

    def _monitor_loop(self) -> None:
        interval = float(self.conf.get("tdfs.replication.interval.s", 1.0))
        # journal growth bound: checkpoint in-process once edits pass this
        # size, so the journal stays bounded even with no secondary
        # (≈ dfs.namenode.checkpoint.txns-style trigger); 0 disables
        auto_ckpt = int(float(self.conf.get(
            "tdfs.edits.auto.checkpoint.mb", 256)) * 1024 * 1024)
        # trash emptier cadence ≈ fs.trash.checkpoint.interval: default
        # one pass per trash interval, never more often than the monitor
        trash_every = float(self.conf.get(
            "fs.trash.checkpoint.interval.s",
            max(60.0, float(self.conf.get("fs.trash.interval", 0)) * 60)))
        from tpumr.utils.fi import fires
        last_trash = time.monotonic()
        while not self._stop.wait(interval):
            if fires("nn.crash", self.conf):
                # SIGKILL-equivalent chaos seam: the whole daemon dies
                # between monitor sweeps — restart/replay/safemode (and
                # clients riding RPC retries) are the quarry's predator
                self.kill()
                return
            try:
                self.ns.heartbeat_check(self.dn_expiry_s)
                # boosts must be set before the sweep that acts on them
                self.ns.hotblock_check()
                self.ns.replication_check()
                self.ns.lease_check()
                self.ns.decommission_check()
                self.token_store.purge_expired()
                if auto_ckpt and self.ns.edits_bytes() > auto_ckpt:
                    self.ns.save_namespace()
                if time.monotonic() - last_trash >= trash_every:
                    last_trash = time.monotonic()
                    self.ns.trash_emptier_check()
            except Exception:  # noqa: BLE001 — monitors must survive
                pass

    # ------------------------------------------------------------ RPC surface
    # thin delegation so the RPC registry exposes exactly the protocol

    def _op(self, name: str):
        """Per-op latency timer (``nn_op_seconds{op=}``, the labeled-
        family convention) wrapping each namespace RPC, plus the
        ``nn.op.slow`` fault seam — the stall lands inside the timed
        window but BEFORE the namespace lock, modelling a slow disk /
        GC pause on the op path; because the histogram sees it, it
        drives the NN incident e2e the way jt.heartbeat.slow drives
        the master's."""
        from tpumr.utils.fi import fires
        delay_s = 0.0
        if fires("nn.op.slow", self.conf):
            from tpumr.core import confkeys
            delay_s = confkeys.get_int(
                self.conf, "tpumr.fi.nn.op.slow.ms") / 1000.0
        h = self._op_hists.get(name)
        if h is None:
            h = self._mreg.histogram(f"nn_op_seconds|op={name}")
            self._op_hists[name] = h
        if not delay_s:
            return h.time()
        return self._op_stalled(h, delay_s)

    @staticmethod
    @contextlib.contextmanager
    def _op_stalled(h, delay_s: float):
        with h.time():
            time.sleep(delay_s)
            yield

    def get_protocol_version(self) -> int:
        return PROTOCOL_VERSION

    def create(self, path, client, replication=None, block_size=None,
               overwrite=True):
        with self._op("create"):
            return self.ns.create(path, client, replication, block_size,
                                  overwrite)

    def append(self, path, client):
        with self._op("append"):
            return self.ns.append(path, client)

    def fsync(self, path, client, last_block_size):
        with self._op("fsync"):
            return self.ns.fsync(path, client, last_block_size)

    def _mint_access(self, block_id, mode):
        """Short-lived per-block DataNode access stamp for the calling
        user (≈ BlockTokenSecretManager.generateToken, attached to
        located blocks). Only block-id-granting RPCs mint, so a
        canceled/expired delegation token stops yielding fresh stamps —
        DN access dies within the stamp lifetime."""
        if self._rpc_secret is None:
            return None
        from tpumr.ipc.rpc import current_rpc_user
        from tpumr.security.tokens import mint_block_access
        lifetime = float(self.conf.get("tpumr.block.access.lifetime.s",
                                       3600.0))
        return mint_block_access(self._rpc_secret,
                                 str(current_rpc_user() or ""),
                                 block_id, mode, lifetime)

    def add_block(self, path, client, prev_block_size=-1, excluded=None):
        with self._op("add_block"):
            out = self.ns.add_block(path, client, prev_block_size,
                                    excluded)
            access = self._mint_access(out["block_id"], "rw")
            if access is not None:
                out["access"] = access
            return out

    def abandon_block(self, path, client, block_id):
        with self._op("abandon_block"):
            return self.ns.abandon_block(path, client, block_id)

    def complete(self, path, client, last_block_size):
        with self._op("complete"):
            return self.ns.complete(path, client, last_block_size)

    def renew_lease(self, client):
        with self._op("renew_lease"):
            return self.ns.renew_lease(client)

    def get_block_locations(self, path):
        with self._op("get_block_locations"):
            out = self.ns.get_block_locations(path)
            if self._rpc_secret is not None:
                for b in out:
                    access = self._mint_access(b["block_id"], "r")
                    if access is not None:
                        b["access"] = access
            return out

    def mkdirs(self, path):
        with self._op("mkdirs"):
            return self.ns.mkdirs(path)

    # per-service delegation tokens ≈ ClientProtocol.getDelegationToken/
    # renewDelegationToken/cancelDelegationToken (DFSClient token path)

    def get_delegation_token(self, renewer=""):
        from tpumr.security.tokens import issue_for_caller
        return issue_for_caller(self.token_store, self._rpc_secret,
                                renewer)

    def renew_delegation_token(self, wire):
        from tpumr.ipc.rpc import current_rpc_user
        from tpumr.security.tokens import verify_wire
        tok = verify_wire(self._rpc_secret, wire)
        return self.token_store.renew(tok, str(current_rpc_user() or ""))

    def cancel_delegation_token(self, wire):
        from tpumr.ipc.rpc import current_rpc_user
        from tpumr.security.tokens import verify_wire
        tok = verify_wire(self._rpc_secret, wire)
        self.token_store.cancel(tok, str(current_rpc_user() or ""))
        return True

    def delete(self, path, recursive=True):
        with self._op("delete"):
            return self.ns.delete(path, recursive)

    def rename(self, src, dst):
        with self._op("rename"):
            return self.ns.rename(src, dst)

    def set_replication(self, path, replication):
        with self._op("set_replication"):
            return self.ns.set_replication(path, replication)

    def set_permission(self, path, mode):
        with self._op("set_permission"):
            return self.ns.set_permission(path, mode)

    def set_owner(self, path, owner=None, group=None):
        with self._op("set_owner"):
            return self.ns.set_owner(path, owner, group)

    def fsck(self, path="/"):
        with self._op("fsck"):
            return self.ns.fsck(path)

    def report_bad_block(self, block_id, addr):
        with self._op("report_bad_block"):
            return self.ns.report_bad_block(block_id, addr)

    def set_quota(self, path, ns_quota=None, sp_quota=None):
        with self._op("set_quota"):
            return self.ns.set_quota(path, ns_quota, sp_quota)

    def set_decommission(self, addr, action="start"):
        with self._op("set_decommission"):
            return self.ns.set_decommission(addr, action)

    def get_status(self, path):
        with self._op("get_status"):
            return self.ns.get_status(path)

    def list_status(self, path):
        with self._op("list_status"):
            return self.ns.list_status(path)

    def exists(self, path):
        with self._op("exists"):
            return self.ns.exists(path)

    def register_datanode(self, addr, capacity):
        with self._op("register_datanode"):
            return self.ns.register_datanode(addr, capacity)

    def dn_heartbeat(self, addr, used, capacity, block_count,
                     hot_blocks=None):
        with self._op("dn_heartbeat"):
            return self.ns.dn_heartbeat(addr, used, capacity,
                                        block_count, hot_blocks)

    def block_report(self, addr, blocks):
        with self._op("block_report"):
            return self.ns.block_report(addr, blocks)

    def block_received(self, addr, block_id, size):
        with self._op("block_received"):
            return self.ns.block_received(addr, block_id, size)

    def get_hot_blocks(self, n=16):
        with self._op("get_hot_blocks"):
            return self.ns.get_hot_blocks(n)

    def refresh_nodes(self):
        with self._op("refresh_nodes"):
            return self.ns.refresh_nodes()

    def refresh_service_acl(self) -> dict:
        """≈ RefreshAuthorizationPolicyProtocol.refreshServiceAcl
        (dfsadmin -refreshServiceAcl): re-read the policy (incl.
        tpumr.policy.file) live. The call itself is authorized by
        security.refresh.policy.protocol.acl; like the reference it
        refuses when authorization is off (a refresh that silently
        guards nothing misleads the operator)."""
        from tpumr.security.authorize import ServiceAuthorizationManager
        if self._server.authz is None or not self._server.authz.enabled:
            raise PermissionError(
                "service authorization is disabled "
                "(tpumr.security.authorization)")
        fresh = ServiceAuthorizationManager(
            self.conf, NAMENODE_POLICY, "security.client.protocol.acl")
        self._server.authz = fresh
        return fresh.acl_specs()

    def safemode(self, action="get"):
        if action == "leave":
            self.ns.safemode = False
        elif action == "enter":
            self.ns.safemode = True
        return self.ns.safemode

    def save_namespace(self):
        with self._op("save_namespace"):
            return self.ns.save_namespace()

    def get_name_state(self):
        with self._op("get_name_state"):
            return self.ns.get_name_state()

    def put_image(self, image, token=-1):
        with self._op("put_image"):
            return self.ns.put_image(image, token)

    def get_blocks(self, addr, max_blocks=16):
        with self._op("get_blocks"):
            return self.ns.get_blocks(addr, max_blocks)

    def remove_replica(self, addr, block_id):
        with self._op("remove_replica"):
            return self.ns.remove_replica(addr, block_id)

    def datanode_report(self):
        with self._op("datanode_report"):
            return self.ns.datanode_report()
