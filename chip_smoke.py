#!/usr/bin/env python3
"""chip_smoke.py — prove that the cluster path runs on the chip.

Drives the path a deployment uses (docs/OPERATIONS.md "Bring-up"): a
``tpumr jobtracker`` and a ``tpumr tasktracker`` as child processes, jobs
submitted with ``tpumr examples ...`` from further child processes. The TRACKER process owns the chip. This parent, the jobtracker
and the clients never initialise a JAX backend: the device is read from
the tracker's start-up log, never from ``jax.devices()`` here.

One chip (the default, as the driver runs it):

1. K-Means, the north-star job: ``--seed`` points as a ``.npy``
   (100M x 16 f32, k=16, 4M rows per split = 25 maps), three iterations
   (iteration 1 cold, 2-3 on the HBM split cache), iteration-1 centroids
   against a chunked numpy reference written here;
2. TeraSort through the device shuffle: teragen 10M rows, ``terasort
   --device-shuffle``, teravalidate; output multiset == teragen's;
3. the Pallas assign kernel once, as a job (16M x 16, one iteration), its
   centroids against the same job on the XLA path.

``--chips 4`` runs only what exists across chips: TeraSort 10M rows on
the four-device mesh against the same input through the host shuffle,
and K-Means iteration 1 on a tracker with four TPU slots (four distinct
devices, read from the tracker's ``tpu:stage`` spans).

Each phase prints one JSON line of observations (sizes, wall seconds,
seconds the tracker spent compiling, counters). They are observations
for the next issue, not metrics. The LAST line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
or, with a non-zero exit code, ``{"ok": false, ...}``. Any failed phase,
comparison or counter check fails the script; nothing is caught and
survived.

``--size tiny`` is the rehearsal: same phases at toy sizes, runnable with
``JAX_PLATFORMS=cpu`` (add ``XLA_FLAGS=--xla_force_host_platform_device_
count=4`` for ``--chips 4``). It still ends ``"ok": false`` without a
chip, after its phases ran.
"""

from __future__ import annotations

import argparse
import ast
import glob
import json
import multiprocessing
import os
import re
import shutil
import signal
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, ".chip_smoke")

D, K = 16, 16  # the north-star widths (BASELINE.json); never cut

#: rows are the only thing "full" could ever cut: it is the size every
#: record in the repo quotes, at the shipped heartbeat. "tiny" is the
#: rehearsal of the control flow: toy rows, fewer splits, a fast beat.
SIZES = {
    "full": {"km_rows": 100_000_000, "km_split": 4_000_000, "km_iters": 3,
             "pallas_rows": 16_000_000, "tera_rows": 10_000_000,
             # cut from the issue's 40M rows: the three mesh programs
             # compile for ~4 min cold at any size and four chips cost
             # four times the chip budget per second (CHANGES.md, PR 21)
             "tera4_rows": 10_000_000, "tera_maps": 8, "daemon_defs": []},
    "tiny": {"km_rows": 64_000, "km_split": 8_000, "km_iters": 2,
             "pallas_rows": 32_000, "tera_rows": 20_000,
             "tera4_rows": 40_000, "tera_maps": 4,
             "daemon_defs": ["-D", "tpumr.heartbeat.interval.ms=100"]},
}

#: job settings of the K-Means workload: the whole data set stays
#: HBM-resident across iterations (the default of 2048 MB would evict
#: it); tracing on, so the tracker's tpu:stage spans name the devices
KMEANS_DEFS = ["-D", "tpumr.tpu.split.cache.mb=14000",
               "-D", "tpumr.trace.enabled=true"]

#: On the chip a f32 jnp.dot at default precision is one bf16 pass
#: (ops/kmeans.py), so points near a cluster boundary may be assigned
#: differently from the f32 reference. Stated tolerances, for unit-
#: variance data: the job's centroids lie within CENTROID_TOL (max abs
#: coordinate error) of the plain f32 reference, and the numpy emulation
#: of the bf16 pass (written here) assigns at most ASSIGN_DIFF_TOL of
#: the points differently from it. PERF.md has the prediction.
CENTROID_TOL = 0.02
ASSIGN_DIFF_TOL = 0.02

BACKEND = "tpumr.BackendCounter"
JOBC = "tpumr.JobCounter"


class SmokeFailure(Exception):
    pass


def emit(phase: str, **obs) -> None:
    print(json.dumps({"phase": phase, **obs}, sort_keys=True), flush=True)


def log(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


# ------------------------------------------------------------ processes


def child_env(extra: "dict | None" = None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra or {})
    return env


def accel_fds(pid: int) -> "list[str]":
    """Accelerator device nodes a process holds open (Linux /proc)."""
    held = set()
    try:
        for fd in os.listdir(f"/proc/{pid}/fd"):
            try:
                target = os.readlink(f"/proc/{pid}/fd/{fd}")
            except OSError:
                continue
            if target.startswith(("/dev/accel", "/dev/vfio/")):
                held.add(target)
    except OSError:
        pass
    return sorted(held)


def children_of(pid: int) -> "list[int]":
    """Direct and indirect child processes of ``pid`` (Linux /proc)."""
    parent_of = {}
    for entry in os.listdir("/proc"):
        if entry.isdigit():
            try:
                with open(f"/proc/{entry}/stat") as f:
                    # pid (comm) state ppid ...; comm may hold spaces
                    parent_of[int(entry)] = int(
                        f.read().rsplit(")", 1)[1].split()[1])
            except (OSError, IndexError, ValueError):
                continue
    found, frontier = [], [pid]
    while frontier:
        frontier = [c for c, p in parent_of.items() if p in frontier]
        found += frontier
    return found


def kill_session(pid: int) -> None:
    try:
        os.killpg(pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


class Daemon:
    """One ``python -m tpumr.cli <daemon>`` child in its own session,
    output to a log file this parent reads."""

    def __init__(self, name: str, args: "list[str]",
                 env_extra: "dict | None" = None) -> None:
        self.name = name
        self.log_path = os.path.join(WORK, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "tpumr.cli"] + args, cwd=REPO,
            env=child_env(env_extra), stdout=self._log, stderr=self._log,
            start_new_session=True)

    def text(self, since: int = 0) -> str:
        with open(self.log_path, "rb") as f:
            f.seek(since)
            return f.read().decode("utf-8", "replace")

    def wait_for(self, pattern: str, timeout: float) -> "re.Match":
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            m = re.search(pattern, self.text())
            if m:
                return m
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"{self.name} exited rc={self.proc.returncode} before "
                    f"printing {pattern!r}:\n{self.text()[-3000:]}")
            time.sleep(0.2)
        raise SmokeFailure(f"{self.name} never printed {pattern!r} in "
                           f"{timeout:.0f}s:\n{self.text()[-3000:]}")

    def compile_seconds(self, since: int = 0) -> "tuple[float, int]":
        """(seconds, count) of the XLA compilations this process logged
        (JAX_LOG_COMPILES=1) past byte offset ``since`` of its log."""
        secs = [float(s) for s in re.findall(
            r"Finished XLA compilation of .* in ([0-9.eE+-]+) sec",
            self.text(since))]
        return round(sum(secs), 3), len(secs)

    def stop(self, timeout: float = 30.0) -> int:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                pass
        kill_session(self.proc.pid)  # whatever is left of its session
        rc = self.proc.wait()
        self._log.close()
        return rc


class Cluster:
    """jobtracker + one tasktracker; clients through ``tpumr examples``."""

    def __init__(self, name: str, tpu_slots: int, daemon_defs: "list[str]",
                 tracker_defs: "list[str] | None" = None) -> None:
        self.name = name
        self.tpu_slots = tpu_slots
        self.daemon_defs = daemon_defs
        self.tracker_defs = tracker_defs or []
        self.history = os.path.join(WORK, f"{name}-history")
        self.daemons: "list[Daemon]" = []
        self.foreign_device_holders: "dict[str, list[str]]" = {}
        self.tracker_device_nodes: "list[str]" = []
        self.device: "dict | None" = None
        self._client_seq = 0
        self._consumed: "set[str]" = set()

    def start(self) -> "Cluster":
        common = ["-D", f"tpumr.history.dir={self.history}"] \
            + self.daemon_defs
        self.jt = Daemon(f"{self.name}-jobtracker",
                         common + ["jobtracker", "-port", "0"])
        self.daemons.append(self.jt)
        m = self.jt.wait_for(r"JobMaster up at ([\w.]+):(\d+)", 60)
        self.addr = f"{m.group(1)}:{m.group(2)}"
        # slots: 1 (or 4) TPU map slots; CPU map and reduce slots stay at
        # the shipped defaults of conf/tpumr-site.example.toml unless the
        # caller overrides them. JAX_LOG_COMPILES makes JAX itself log
        # each compilation's seconds, which compile_seconds() sums.
        self.tt = Daemon(
            f"{self.name}-tasktracker",
            common + ["-D", f"mapred.local.dir={WORK}/{self.name}-local",
                      "-D", "mapred.tasktracker.map.tpu.tasks.maximum="
                            f"{self.tpu_slots}"]
            + self.tracker_defs + ["tasktracker", "-jt", self.addr],
            env_extra={"JAX_LOG_COMPILES": "1"})
        self.daemons.append(self.tt)
        # the tracker names the devices behind its TPU slots, then prints
        # its banner; a tracker with TPU slots and no TPU device (and no
        # explicit CPU request) exits here instead
        self.tt.wait_for(r"NodeRunner up", 300)
        m = re.search(r"TPU slot devices: (\{.*\})", self.tt.text())
        if m is None:
            raise SmokeFailure("tracker did not name its TPU slot devices:"
                               f"\n{self.tt.text()[-2000:]}")
        self.device = json.loads(m.group(1))
        return self

    def client(self, args: "list[str]", timeout: float,
               generic_defs: "list[str] | None" = None) -> dict:
        """Run ``tpumr -D mapred.job.tracker=<addr> examples <args>`` to
        completion; a non-zero exit fails the script. While it runs, note
        any process but the tracker that holds a device node: the client
        itself, and the tracker's process-isolated task children."""
        self._client_seq += 1
        tag = f"{self.name}-client{self._client_seq:02d}-{args[0]}"
        out_path = os.path.join(WORK, f"{tag}.out")
        err_path = os.path.join(WORK, f"{tag}.err")
        cmd = [sys.executable, "-m", "tpumr.cli",
               "-D", f"mapred.job.tracker={self.addr}"] \
            + (generic_defs or []) + ["examples"] + args
        t0 = time.monotonic()
        with open(out_path, "wb") as out, open(err_path, "wb") as err:
            proc = subprocess.Popen(cmd, cwd=REPO, env=child_env(),
                                    stdout=out, stderr=err,
                                    start_new_session=True)
            try:
                while proc.poll() is None:
                    for who, pid in [(tag, proc.pid)] + [
                            (f"{tag}-task-child-{p}", p)
                            for p in children_of(self.tt.proc.pid)]:
                        held = accel_fds(pid)
                        if held:
                            self.foreign_device_holders[who] = held
                    if time.monotonic() - t0 > timeout:
                        raise SmokeFailure(
                            f"client {args[0]} still running after "
                            f"{timeout:.0f}s")
                    time.sleep(0.25)
            finally:
                kill_session(proc.pid)
                proc.wait()
        wall = time.monotonic() - t0
        with open(out_path, "r", errors="replace") as f:
            stdout = f.read()
        if proc.returncode != 0:
            with open(err_path, "r", errors="replace") as f:
                stderr = f.read()
            raise SmokeFailure(
                f"tpumr examples {' '.join(args)} exited "
                f"{proc.returncode}:\n{stdout[-1500:]}\n{stderr[-3000:]}")
        return {"wall_s": round(wall, 3), "stdout": stdout}

    def rollup(self, job_name: str) -> dict:
        """The per-job stats rollup (metrics-<jobid>.json, what ``tpumr
        job stats`` prints) of the job of this name that no earlier call
        returned. The master writes it before a client can see the job
        finished, so it is there when the client has exited."""
        deadline = time.monotonic() + 20
        while True:
            for p in sorted(glob.glob(os.path.join(self.history,
                                                   "metrics-job_*.json"))):
                if p in self._consumed:
                    continue
                with open(p) as f:
                    r = json.load(f)
                if r["job_name"] != job_name:
                    continue
                self._consumed.add(p)
                if r["state"] != "SUCCEEDED":
                    raise SmokeFailure(f"job {job_name}: {r['state']}")
                return r
            if time.monotonic() > deadline:
                raise SmokeFailure(f"no new stats rollup for job "
                                   f"{job_name!r} in {self.history}")
            time.sleep(0.2)

    def stage_devices(self, job_id: str, n_spans: int) -> "dict[str, int]":
        """device string -> number of ``tpu:stage`` spans the TRACKER
        recorded for this job: where splits were actually put. Span
        files trail job completion by a flush, so poll for ``n_spans``."""
        deadline = time.monotonic() + 15
        while True:
            devices: "dict[str, int]" = {}
            for p in glob.glob(os.path.join(self.history,
                                            f"trace-{job_id}.*.jsonl")):
                with open(p) as f:
                    for line in f:
                        span = json.loads(line)
                        if span.get("name") == "tpu:stage":
                            dev = span["attributes"].get("device", "?")
                            devices[dev] = devices.get(dev, 0) + 1
            if sum(devices.values()) >= n_spans or \
                    time.monotonic() > deadline:
                return devices
            time.sleep(0.3)

    def stop(self) -> None:
        """Stop the daemons cleanly; note who held a device node."""
        for d in self.daemons:
            if d is not self.tt and d.proc.poll() is None:
                held = accel_fds(d.proc.pid)
                if held:
                    self.foreign_device_holders[d.name] = held
        if self.tt.proc.poll() is None:
            self.tracker_device_nodes = accel_fds(self.tt.proc.pid)
        rcs = {d.name: d.stop() for d in reversed(self.daemons)}
        self.daemons = []
        bad = {n: rc for n, rc in rcs.items() if rc != 0}
        if bad:
            raise SmokeFailure(f"daemons did not stop cleanly: {bad}")

    def kill(self) -> None:
        for d in reversed(self.daemons):
            d.stop(timeout=5)
        self.daemons = []


def counter(rollup: dict, group: str, name: str) -> int:
    return int((rollup["counters"].get(group) or {}).get(name, 0))


# ------------------------------------------------- K-Means data + reference


def _bf16(x: np.ndarray) -> np.ndarray:
    """float32 rounded to bfloat16 (nearest even), as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def _partials(assign: np.ndarray, block: np.ndarray, k: int):
    """Per-cluster (sums [k, d], counts [k]) of one block of rows."""
    onehot = np.zeros((block.shape[0], k), np.float32)
    onehot[np.arange(block.shape[0]), assign] = 1.0
    return onehot.T @ block, np.bincount(assign, minlength=k)


def _chunk_rng(seed: int, index: int) -> np.random.Generator:
    return np.random.default_rng([seed, index])


def _gen_chunk(job: tuple):
    """Pool worker: make chunk ``index`` of the points file from the seed,
    write it in place, and return the chunk's reference partials — the
    plain f32 assignment (nearest centroid, ``|c|² - 2x·c`` in f32,
    block sums accumulated in f64) and the numpy emulation of the chip's
    bf16 pass (what ops/kmeans.py computes there: both matmuls see their
    f32 inputs rounded to bf16)."""
    path, data_start, seed, index, lo, rows, cents = job
    block = _chunk_rng(seed, index).standard_normal((rows, D),
                                                    dtype=np.float32)
    with open(path, "r+b") as f:
        f.seek(data_start + lo * D * 4)
        f.write(memoryview(block).cast("B"))
    k = cents.shape[0]
    cb = _bf16(cents)
    c2 = np.sum(cents * cents, axis=1)
    ref_s, ref_c = np.zeros((k, D)), np.zeros(k, np.int64)
    emu_s, emu_c = np.zeros((k, D)), np.zeros(k, np.int64)
    differ = 0
    for a in range(0, rows, 1 << 16):
        x = block[a:a + (1 << 16)]
        ref = np.argmin(c2[None, :] - 2.0 * (x @ cents.T), axis=1)
        s, c = _partials(ref, x, k)
        ref_s += s
        ref_c += c
        xb = _bf16(x)
        x2 = np.sum(x * x, axis=1, keepdims=True)
        emu = np.argmin(x2 - 2.0 * (xb @ cb.T) + c2[None, :], axis=1)
        s, c = _partials(emu, xb, k)   # the sums matmul rounds points too
        emu_s += s
        emu_c += c
        differ += int(np.count_nonzero(ref != emu))
    return ref_s, ref_c, emu_s, emu_c, differ


def make_points(path: str, rows: int, chunk_rows: int, seed: int) -> dict:
    """Write the ``rows x D`` f32 ``.npy`` in bulk from ``seed`` (a pool of
    processes, one chunk each) and return the iteration-1 reference for
    centroids seeded from the first K rows, as the kmeans driver seeds
    them. Chunk i depends only on (seed, i): a file of fewer rows is a
    prefix of a file of more."""
    t0 = time.monotonic()
    header = np.lib.format.header_data_from_array_1_0(
        np.empty((0, D), np.float32))
    header["shape"] = (rows, D)
    with open(path, "wb") as f:
        np.lib.format.write_array_header_1_0(f, header)
        data_start = f.tell()
        f.truncate(data_start + rows * D * 4)
    cents = _chunk_rng(seed, 0).standard_normal(
        (min(chunk_rows, rows), D), dtype=np.float32)[:K].copy()
    jobs = [(path, data_start, seed, i, lo, min(chunk_rows, rows - lo), cents)
            for i, lo in enumerate(range(0, rows, chunk_rows))]
    workers = max(1, min(len(jobs), (os.cpu_count() or 2) - 1, 12))
    with multiprocessing.get_context("spawn").Pool(workers) as pool:
        parts = pool.map(_gen_chunk, jobs, chunksize=1)
    ref_s, ref_c, emu_s, emu_c, differ = (sum(p[i] for p in parts)
                                          for i in range(5))

    def centroids(s, c):
        new = cents.astype(np.float64)
        hit = c > 0
        new[hit] = s[hit] / c[hit][:, None]
        return new

    return {"seed_centroids": cents,
            "ref_centroids": centroids(ref_s, ref_c),
            "emu_centroids": centroids(emu_s, emu_c),
            "emu_assign_diff_share": differ / rows,
            "gen_s": round(time.monotonic() - t0, 3)}


def read_centroids(out_dir: str, seeds: np.ndarray) -> np.ndarray:
    """New centroids of one kmeans iteration from its part files
    (``cid<TAB>[coords]``); a cluster that got no point keeps its seed."""
    cents = seeds.astype(np.float64)
    seen = 0
    for p in sorted(glob.glob(os.path.join(out_dir, "part-*"))):
        with open(p) as f:
            for line in f:
                cid, _, val = line.rstrip("\n").partition("\t")
                cents[int(cid)] = np.asarray(ast.literal_eval(val))
                seen += 1
    if seen == 0:
        raise SmokeFailure(f"no centroid records under {out_dir}")
    if cents.shape != (K, D) or not np.isfinite(cents).all():
        raise SmokeFailure(f"centroids under {out_dir} are not finite "
                           f"[{K}, {D}]")
    return cents


# ------------------------------------------------------- TeraSort checking


def _row_hash(keys: np.ndarray, values: np.ndarray) -> np.ndarray:
    """One 64-bit hash per 100-byte row (multiply-mix over 13 words)."""
    n = keys.shape[0]
    buf = np.zeros((n, 104), np.uint8)
    buf[:, :10] = keys
    buf[:, 10:100] = values
    words = buf.view(np.uint64)
    mult = (np.arange(1, words.shape[1] + 1, dtype=np.uint64)
            * np.uint64(0x9E3779B97F4A7C15)) | np.uint64(1)
    h = (words * mult[None, :]).sum(axis=1, dtype=np.uint64)
    h ^= h >> np.uint64(29)
    h *= np.uint64(0xBF58476D1CE4E5B9)
    h ^= h >> np.uint64(32)
    return h


def read_parts(dir_path: str) -> "list[dict]":
    """Per part file, in name order: rows, an order-independent checksum
    of the rows, first and last key, and whether keys never decrease."""
    from tpumr.io import sequencefile   # the repo's file format (no jax)
    parts = []
    for p in sorted(glob.glob(os.path.join(dir_path, "part-*"))):
        with open(p, "rb") as f:
            batch = sequencefile.Reader(f).read_batch_range(
                0, os.path.getsize(p))
        n = len(batch.key_offsets) - 1
        if n == 0:
            parts.append({"rows": 0, "checksum": 0, "first": None,
                          "last": None, "sorted": True})
            continue
        if batch.key_data.size != n * 10 or batch.value_data.size != n * 90:
            raise SmokeFailure(f"{p}: rows are not 10+90 bytes")
        keys = batch.key_data.reshape(n, 10)
        values = batch.value_data.reshape(n, 90)
        # big-endian 10 bytes as (u64, u16): lexicographic order
        hi = keys[:, :8].copy().view(">u8")[:, 0]
        lo = keys[:, 8:].copy().view(">u2")[:, 0]
        in_order = bool(np.all((hi[1:] > hi[:-1])
                               | ((hi[1:] == hi[:-1]) & (lo[1:] >= lo[:-1]))))
        parts.append({
            "rows": n,
            "checksum": int(_row_hash(keys, values).sum(dtype=np.uint64)),
            "first": keys[0].tobytes().hex(),
            "last": keys[-1].tobytes().hex(),
            "sorted": in_order})
    if not parts:
        raise SmokeFailure(f"no part files under {dir_path}")
    return parts


def multiset(parts: "list[dict]") -> "tuple[int, int]":
    return (sum(p["rows"] for p in parts),
            sum(p["checksum"] for p in parts) % (1 << 64))


def check_sorted_output(parts: "list[dict]", what: str) -> None:
    """Independent of teravalidate: every part in order, and each part's
    first key not below the previous part's last."""
    prev = None
    for i, p in enumerate(parts):
        if not p["sorted"]:
            raise SmokeFailure(f"{what}: part {i} is not in key order")
        if p["rows"] and prev is not None and p["first"] < prev:
            raise SmokeFailure(f"{what}: part {i} starts below part "
                               f"{i - 1}'s last key")
        if p["rows"]:
            prev = p["last"]


# ----------------------------------------------------------------- the run

SORT_OBS = ("terasort_wall_s", "terasort_job_wall_s", "teravalidate_wall_s",
            "tracker_compile_s", "tracker_compiles")


class Smoke:
    def __init__(self, args: argparse.Namespace) -> None:
        self.args = args
        self.size = SIZES[args.size]
        #: chip-only checks that failed in a rehearsal; they fail the
        #: script at the end instead of at once, so the phases still run
        self.no_chip: "list[str]" = []
        self.rehearsal = False
        self.cluster: "Cluster | None" = None
        self.device: "dict | None" = None

    def chip_check(self, ok: bool, msg: str) -> None:
        if ok:
            return
        if not self.rehearsal:
            raise SmokeFailure(msg)
        self.no_chip.append(msg)

    def start(self, name: str, tpu_slots: int,
              tracker_defs: "list[str] | None" = None) -> Cluster:
        t0 = time.monotonic()
        c = self.cluster = Cluster(name, tpu_slots,
                                   self.size["daemon_defs"], tracker_defs)
        dev = self.device = c.start().device
        emit("cluster_up", cluster=name, tracker_devices=dev,
             tpu_slots=tpu_slots, seconds=round(time.monotonic() - t0, 3))
        if dev["platform"] != "tpu":
            if self.args.size != "tiny":
                raise SmokeFailure(
                    f"the tracker's TPU slots are on platform "
                    f"{dev['platform']!r}, not a chip; only --size tiny "
                    f"rehearses without one")
            self.rehearsal = True
            self.no_chip.append(f"tracker devices are {dev['platform']}")
        self.chip_check(dev["count"] == self.args.chips,
                        f"expected {self.args.chips} device(s), the "
                        f"tracker has {dev['count']}")
        return c

    def stop(self) -> None:
        c, self.cluster = self.cluster, None
        c.stop()
        emit("cluster_down", cluster=c.name,
             tracker_device_nodes=c.tracker_device_nodes,
             other_device_holders=c.foreign_device_holders)
        if c.foreign_device_holders:
            raise SmokeFailure(
                "a process other than the tracker opened the accelerator: "
                f"{c.foreign_device_holders}")

    # -- K-Means

    def kmeans_job_checks(self, r: dict, n_maps: int, first: bool) -> dict:
        tpu = counter(r, BACKEND, "TPU_MAP_TASKS")
        cpu = counter(r, BACKEND, "CPU_MAP_TASKS")
        staged = counter(r, BACKEND, "TPU_DEVICE_BYTES_STAGED")
        demoted = counter(r, JOBC, "TPU_DEMOTIONS")
        name = r["job_name"]
        if tpu <= 0:
            raise SmokeFailure(f"{name}: no map task ran on a TPU slot")
        if tpu + cpu != n_maps:
            raise SmokeFailure(f"{name}: TPU {tpu} + CPU {cpu} map tasks "
                               f"!= {n_maps} splits")
        if first and staged <= 0:
            raise SmokeFailure(f"{name}: nothing was staged to the device")
        if demoted != 0:
            raise SmokeFailure(f"{name}: {demoted} TPU demotion(s)")
        if counter(r, JOBC, "FAILED_MAP_TASKS"):
            raise SmokeFailure(f"{name}: failed map attempts")
        return {"job": name, "job_id": r["job_id"],
                "job_wall_s": round(r["wall_time"], 3),
                "TPU_MAP_TASKS": tpu, "CPU_MAP_TASKS": cpu,
                "TPU_DEVICE_BYTES_STAGED": staged, "TPU_DEMOTIONS": demoted,
                **{f"{side}_map_{stat}_s": round(
                    r[f"map_latency_{side}"].get(stat, 0.0), 3)
                   for side in ("tpu", "cpu") for stat in ("mean", "max")}}

    @staticmethod
    def compare_centroids(what: str, got: np.ndarray, want: np.ndarray,
                          tol: float) -> float:
        err = float(np.max(np.abs(got - want)))
        if not err <= tol:
            raise SmokeFailure(f"{what}: max abs centroid error {err:.3g} "
                               f"exceeds the stated tolerance {tol}")
        return err

    def phase_kmeans(self, c: Cluster, iters: int, tag: str = "kmeans"
                     ) -> dict:
        """The K-Means job; returns the iteration-1 job's checks."""
        rows, split = self.size["km_rows"], self.size["km_split"]
        n_maps = -(-rows // split)
        points = os.path.join(WORK, "points.npy")
        ref = make_points(points, rows, split, self.args.seed)
        if ref["emu_assign_diff_share"] > ASSIGN_DIFF_TOL:
            raise SmokeFailure(
                f"bf16 emulation assigns {ref['emu_assign_diff_share']:.3%}"
                f" of the points differently, above {ASSIGN_DIFF_TOL:.0%}")
        log_at = os.path.getsize(c.tt.log_path)
        out = os.path.join(WORK, f"{tag}-out")
        run = c.client(["kmeans", f"file://{points}", f"file://{out}",
                        "-k", str(K), "-i", str(iters),
                        "--split-rows", str(split)] + KMEANS_DEFS,
                       timeout=900)
        compile_s, compiles = c.tt.compile_seconds(log_at)
        per_iter = [self.kmeans_job_checks(
            c.rollup(f"kmeans-iter-{i}"), n_maps, first=(i == 0))
            for i in range(iters)]
        got = read_centroids(os.path.join(out, "iter0"),
                             ref["seed_centroids"])
        err_ref = self.compare_centroids(
            f"{tag} iteration 1 vs numpy f32 reference", got,
            ref["ref_centroids"], CENTROID_TOL)
        err_emu = float(np.max(np.abs(got - ref["emu_centroids"])))
        emit(tag, rows=rows, d=D, k=K, rows_per_split=split, maps=n_maps,
             iterations=iters, bytes=rows * D * 4, gen_s=ref["gen_s"],
             client_wall_s=run["wall_s"],
             cold_iteration_s=per_iter[0]["job_wall_s"],
             warm_iteration_s=[p["job_wall_s"] for p in per_iter[1:]],
             tracker_compile_s=compile_s, tracker_compiles=compiles,
             jobs=per_iter,
             centroid_max_abs_err_vs_f32_reference=err_ref,
             centroid_max_abs_err_vs_bf16_emulation=err_emu,
             emulated_bf16_assign_diff_share=ref["emu_assign_diff_share"],
             centroid_tolerance=CENTROID_TOL)
        os.remove(points)
        shutil.rmtree(out, ignore_errors=True)
        return per_iter[0]

    def phase_pallas(self, c: Cluster) -> None:
        rows, split = self.size["pallas_rows"], self.size["km_split"]
        n_maps = -(-rows // split)
        points = os.path.join(WORK, "points-pallas.npy")
        ref = make_points(points, rows, split, self.args.seed)
        results = {}
        for mode, defs in (("xla", []),
                           ("pallas", ["-D", "tpumr.kmeans.use.pallas=true"])):
            log_at = os.path.getsize(c.tt.log_path)
            out = os.path.join(WORK, f"pallas-{mode}-out")
            run = c.client(["kmeans", f"file://{points}", f"file://{out}",
                            "-k", str(K), "-i", "1",
                            "--split-rows", str(split)] + KMEANS_DEFS + defs,
                           timeout=600)
            compile_s, compiles = c.tt.compile_seconds(log_at)
            checks = self.kmeans_job_checks(c.rollup("kmeans-iter-0"),
                                            n_maps, first=(mode == "xla"))
            results[mode] = {
                "client_wall_s": run["wall_s"], **checks,
                "tracker_compile_s": compile_s,
                "tracker_compiles": compiles,
                "centroids": read_centroids(os.path.join(out, "iter0"),
                                            ref["seed_centroids"])}
            shutil.rmtree(out, ignore_errors=True)
        err = self.compare_centroids(
            "pallas vs xla job", results["pallas"].pop("centroids"),
            results["xla"]["centroids"], CENTROID_TOL)
        err_ref = self.compare_centroids(
            "xla job vs numpy f32 reference",
            results["xla"].pop("centroids"), ref["ref_centroids"],
            CENTROID_TOL)
        emit("pallas", rows=rows, d=D, k=K, rows_per_split=split,
             maps=n_maps, gen_s=ref["gen_s"], **results,
             centroid_max_abs_err_pallas_vs_xla=err,
             centroid_max_abs_err_xla_vs_f32_reference=err_ref,
             centroid_tolerance=CENTROID_TOL)
        os.remove(points)

    # -- TeraSort

    def teragen(self, c: Cluster, rows: int) -> "tuple[str, dict]":
        gen = os.path.join(WORK, "tera-gen")
        run = c.client(["teragen", str(rows), f"file://{gen}",
                        "-m", str(self.size["tera_maps"])], timeout=900)
        src = read_parts(gen)
        if multiset(src)[0] != rows:
            raise SmokeFailure(f"teragen wrote {multiset(src)[0]} rows, "
                               f"not {rows}")
        return gen, {"teragen_wall_s": run["wall_s"], "parts": src}

    def terasort(self, c: Cluster, gen: str, out: str, src: "list[dict]",
                 device: bool) -> dict:
        """terasort + teravalidate + this script's own reading of the
        output: in order, and the same multiset of rows as teragen's.
        The host shuffle runs its CPU tasks process-isolated (one child
        per attempt), so that they use the host's cores."""
        log_at = os.path.getsize(c.tt.log_path)
        sort = c.client(
            ["terasort", f"file://{gen}", f"file://{out}", "-r", "4"]
            + (["--device-shuffle"] if device else []), timeout=2400,
            generic_defs=(None if device else
                          ["-D", "tpumr.task.isolation=process"]))
        compile_s, compiles = c.tt.compile_seconds(log_at)
        r = c.rollup("terasort")
        val = c.client(["teravalidate", f"file://{out}",
                        f"file://{out}-validate"], timeout=900)
        if "Output is globally sorted." not in val["stdout"]:
            raise SmokeFailure(f"teravalidate did not pass: "
                               f"{val['stdout'][-500:]}")
        parts = read_parts(out)
        check_sorted_output(parts, out)
        if multiset(parts) != multiset(src):
            raise SmokeFailure(f"{out}: multiset of rows {multiset(parts)} "
                               f"!= teragen's {multiset(src)}")
        return {"terasort_wall_s": sort["wall_s"],
                "terasort_job_wall_s": round(r["wall_time"], 3),
                "teravalidate_wall_s": val["wall_s"],
                "tracker_compile_s": compile_s,
                "tracker_compiles": compiles,
                "rollup": r, "parts": parts}

    def device_sort_checks(self, r: dict, rows: int) -> dict:
        on_accel = counter(r, BACKEND, "DEVICE_SORT_ON_ACCEL")
        fallbacks = counter(r, BACKEND, "SHUFFLE_HOST_FALLBACKS")
        moved = counter(r, BACKEND, "TPU_SHUFFLE_RECORDS")
        if fallbacks != 0:
            raise SmokeFailure(f"device shuffle fell back to the host sort "
                               f"{fallbacks} time(s)")
        if moved != rows:
            raise SmokeFailure(f"the device shuffle moved {moved} records, "
                               f"not {rows}")
        self.chip_check(on_accel > 0, "DEVICE_SORT_ON_ACCEL is 0: the "
                                      "device sort did not run on a chip")
        return {"DEVICE_SORT_ON_ACCEL": on_accel,
                "SHUFFLE_HOST_FALLBACKS": fallbacks,
                "TPU_SHUFFLE_RECORDS": moved}

    def phase_terasort(self, c: Cluster) -> None:
        rows = self.size["tera_rows"]
        gen, g = self.teragen(c, rows)
        out = os.path.join(WORK, "tera-out")
        t = self.terasort(c, gen, out, g["parts"], device=True)
        emit("terasort", rows=rows, bytes=rows * 100, reduces=4,
             device_shuffle=True, teragen_wall_s=g["teragen_wall_s"],
             **{k: t[k] for k in SORT_OBS},
             **self.device_sort_checks(t["rollup"], rows),
             multiset_equal=True, part_rows=[p["rows"] for p in t["parts"]])
        for d in (gen, out, out + "-validate"):
            shutil.rmtree(d, ignore_errors=True)

    def phase_terasort_4(self, c: Cluster) -> None:
        """Device shuffle on the four-device mesh against the same input
        through the host shuffle."""
        rows = self.size["tera4_rows"]
        gen, g = self.teragen(c, rows)
        dev_out = os.path.join(WORK, "tera-device-out")
        host_out = os.path.join(WORK, "tera-host-out")
        dv = self.terasort(c, gen, dev_out, g["parts"], device=True)
        counters = self.device_sort_checks(dv["rollup"], rows)
        hs = self.terasort(c, gen, host_out, g["parts"], device=False)
        # same cuts (sampled from the same input): part i holds the same
        # rows either way — same count, same checksum, same key range
        same = ("rows", "checksum", "first", "last")
        if [[p[k] for k in same] for p in dv["parts"]] != \
                [[p[k] for k in same] for p in hs["parts"]]:
            raise SmokeFailure(
                f"part files differ between device and host shuffle: "
                f"{dv['parts']} vs {hs['parts']}")
        emit("terasort_4chip", rows=rows, bytes=rows * 100, reduces=4,
             teragen_wall_s=g["teragen_wall_s"],
             device={k: dv[k] for k in SORT_OBS},
             host={k: hs[k] for k in SORT_OBS},
             **counters, outputs_equal=True,
             part_rows=[p["rows"] for p in dv["parts"]],
             part_key_ranges=[[p["first"], p["last"]] for p in dv["parts"]])
        for d in (gen, dev_out, host_out, dev_out + "-validate",
                  host_out + "-validate"):
            shutil.rmtree(d, ignore_errors=True)

    def phase_kmeans_4(self, c: Cluster) -> None:
        job = self.phase_kmeans(c, iters=1, tag="kmeans_4slots")
        devices = c.stage_devices(job["job_id"], job["TPU_MAP_TASKS"])
        emit("kmeans_4slots_devices", stage_spans_by_device=devices)
        if len(devices) != 4:
            raise SmokeFailure(f"four TPU slots staged onto "
                               f"{len(devices)} device(s): {devices}")
        self.chip_check(all(d.upper().startswith("TPU") for d in devices),
                        f"staging devices are not TPU devices: "
                        f"{sorted(devices)}")

    # -- entry

    def run(self) -> None:
        if self.args.chips == 1:
            c = self.start("c1", tpu_slots=1)
            self.phase_kmeans(c, self.size["km_iters"])
            self.phase_terasort(c)
            self.phase_pallas(c)
        else:
            # the four-chip host has ~30 cores: CPU map and reduce slots
            # follow them, so the host-shuffle run the device shuffle is
            # compared with does not queue behind three slots
            c = self.start("c4", tpu_slots=4, tracker_defs=[
                "-D", "mapred.tasktracker.map.cpu.tasks.maximum=8",
                "-D", "mapred.tasktracker.reduce.tasks.maximum=4"])
            self.phase_terasort_4(c)
            self.phase_kmeans_4(c)
        self.stop()


def native_kits() -> dict:
    """Which native kits build from the checkout and load (no jax)."""
    from tpumr.utils.nativelib import load_native_lib
    return {kit: load_native_lib(kit, so) is not None
            for kit, so in (("tlz", "libtlz.so"),
                            ("textkit", "libtokencount.so"))}


def cache_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(
        description="Run the cluster path on the chip and check it.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--size", choices=sorted(SIZES), default="full")
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--keep", action="store_true",
                    help="keep the work directory (logs, data) at the end")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "tpumr")):
        print(f"chip_smoke.py: no tpumr package beside {__file__}; run it "
              f"from a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    # one compile cache for every process started here: the caller's
    # JAX_COMPILATION_CACHE_DIR if set (children inherit it and JAX reads
    # it), else the checkout's fixed default, which the code picks itself
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(REPO, ".jax_cache")
    smoke = Smoke(args)
    t0 = time.monotonic()
    error = None
    try:
        kits = native_kits()
        emit("setup", size=args.size, chips=args.chips, seed=args.seed,
             sizes=smoke.size, d=D, k=K, native_kits=kits,
             compile_cache_dir=cache_dir,
             compile_cache_entries_before=cache_entries(cache_dir),
             cpu_count=os.cpu_count(),
             disk_free_gb=round(shutil.disk_usage(WORK).free / 1e9, 1))
        if not kits["tlz"]:
            raise SmokeFailure("native/tlz did not build: the shuffle wire "
                               "codec would run on its fallback")
        smoke.run()
    except SmokeFailure as e:
        error = str(e)
    except Exception as e:  # noqa: BLE001 — reported, and the run fails
        import traceback
        traceback.print_exc()
        error = f"{type(e).__name__}: {e}"
    finally:
        if smoke.cluster is not None:
            smoke.cluster.kill()
    emit("done", seconds=round(time.monotonic() - t0, 3),
         compile_cache_entries_after=cache_entries(cache_dir))
    if error is None and smoke.no_chip:
        error = "no chip: " + "; ".join(smoke.no_chip)
    if error is not None:
        log(f"FAILED: {error}")
        log(f"logs kept under {WORK}")
        print(json.dumps({"ok": False, "error": error[:2000],
                          "device": smoke.device}))
        return 1
    if not args.keep:
        shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        k: smoke.device[k] for k in ("platform", "kind", "count")}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
