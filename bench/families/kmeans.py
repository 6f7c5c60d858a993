"""The K-Means family: points from the seed, one round per job through the
round driver, the plain numpy reference, and the comparison.

What is compared (``check``): for every round of the window, the centroids
the job wrote against the plain reference's for the same points and the
same given centroids (``centroid_gap``: the widest absolute gap over
clusters and coordinates). The reference computes each split in the
precision the configuration states for the slot it ran on: float32 on a
CPU slot, and on the chip float32 with both dots in one bfloat16 pass
(``chip``); where each map ran is read from the master's event log. It
reads the points file this module wrote from the seed and the centroids
the round driver fed the job; it imports nothing of the program. The
control (``bf16``) is the same reference with every array and every result
rounded to bfloat16.

The points are not exchangeable: a sawtooth drift in the row index, one
period per coordinate from the whole set down to 2 rows (``drift_periods``),
rides on the normal draw, so that no half of the rows, of a split or of the splits
stands for the whole (``faults`` reads what leaving one out costs).
"""

from __future__ import annotations

import ast
import glob
import json
import os
import subprocess
import sys
import time

import numpy as np

from bench.cluster import (BACKEND, JOBC, REPO, TASKC, BenchFailure,
                           child_env, counter, kill_session)
from bench.pool import worker_pool

BLOCK = 1 << 16


# ------------------------------------------------------------------ data


def _chunk_rng(seed: int, index: int) -> np.random.Generator:
    return np.random.default_rng([seed, index])


def drift_periods(rows: int, split_rows: int, d: int) -> np.ndarray:
    """One sawtooth period per coordinate, in rows. The last coordinates
    take 2, 8, 32, ... rows as far as a split reaches, counted from the
    split's first row (a tile of any power of two inside a split is half
    of one of them or a quarter of the next); the first ones go
    geometrically from the whole set down to one split, so that splits
    differ from one another."""
    fine = [2 * 4 ** i for i in range(d - 2) if 2 * 4 ** i <= split_rows]
    j = np.arange(d - len(fine)) / max(1, d - len(fine) - 1)
    coarse = np.rint(rows ** (1 - j) * split_rows ** j)
    return np.concatenate([coarse, fine[::-1]]).astype(np.int64)


def _gen_chunk(job: tuple) -> int:
    """Pool worker: chunk ``index`` of the points file, a function of
    (seed, index) and the rows' place in the set, written in place block
    by block."""
    path, data_start, seed, index, lo, rows, drift, periods, chunk = job
    rng = _chunk_rng(seed, index)
    first = np.where(periods < chunk, 0, lo)    # where a period counts from
    inv = 1.0 / periods
    with open(path, "r+b") as f:
        f.seek(data_start + lo * len(periods) * 4)
        for a in range(0, rows, BLOCK):
            n = min(BLOCK, rows - a)
            x = rng.standard_normal((n, len(periods)), dtype=np.float32)
            turns = (first[None, :] + np.arange(a, a + n)[:, None]) * inv
            # the sawtooth, centred: drift * (2 * phase - 1), phase at the
            # middle of the row
            x += (drift * (2.0 * (turns - np.floor(turns)) + inv - 1.0)
                  ).astype(np.float32)
            f.write(memoryview(x).cast("B"))
    return rows


def make_input(sizes: dict, seed: int, data_dir: str) -> dict:
    """``rows x d`` float32 points (a unit normal draw plus the drift) as
    one ``.npy``, made in bulk by a pool of processes, one split-sized
    chunk each."""
    rows, d, chunk = sizes["rows"], sizes["d"], sizes["split_rows"]
    path = os.path.join(data_dir, "points.npy")
    header = np.lib.format.header_data_from_array_1_0(
        np.empty((0, d), np.float32))
    header["shape"] = (rows, d)
    with open(path, "wb") as f:
        np.lib.format.write_array_header_1_0(f, header)
        data_start = f.tell()
        f.truncate(data_start + rows * d * 4)
    periods = drift_periods(rows, chunk, d)
    jobs = [(path, data_start, seed, i, lo, min(chunk, rows - lo),
             sizes["drift"], periods, chunk)
            for i, lo in enumerate(range(0, rows, chunk))]
    with worker_pool(len(jobs)) as p:
        written = sum(p.map(_gen_chunk, jobs, chunksize=1))
    if written != rows:
        raise BenchFailure(f"wrote {written} of {rows} rows")
    return {"points": path}


def rows_per_job(sizes: dict) -> int:
    return sizes["rows"]


# ------------------------------------------------------------- reference


def bf16(x: np.ndarray) -> np.ndarray:
    """float32 rounded to bfloat16 (nearest even), as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def _assign(x: np.ndarray, xb: np.ndarray, cents: np.ndarray, mode: str):
    """Nearest centroid of each row (``xb``: the rows in bfloat16), and
    the rows as the sums see them.

    ``f32``: plain float32, ``|c|^2 - 2 x.c`` (``|x|^2`` is the same for
    every centroid). ``chip``: the program's formula in float32 with both
    dots in one bfloat16 pass, as the configuration states for the chip:
    the dots see their inputs rounded to bfloat16 and accumulate in
    float32. ``bf16``, the control: every input and every result held in
    bfloat16."""
    if mode == "f32":
        c2 = np.sum(cents * cents, axis=1)
        return np.argmin(c2[None, :] - 2.0 * (x @ cents.T), axis=1), x
    cb = bf16(cents)
    if mode == "chip":
        x2 = np.sum(x * x, axis=1, keepdims=True)
        c2 = np.sum(cents * cents, axis=1)
        return np.argmin(x2 - 2.0 * (xb @ cb.T) + c2[None, :], axis=1), xb
    x2 = bf16(np.sum(bf16(xb * xb), axis=1, keepdims=True))
    c2 = bf16(np.sum(bf16(cb * cb), axis=1))
    d2 = bf16(bf16(x2 - bf16(2.0 * bf16(xb @ cb.T))) + c2[None, :])
    return np.argmin(d2, axis=1), xb


#: Rows a faulty map would keep, by their place in the set and the split
#: ``[lo, hi)``: what ``faults`` plants in the reference to read a fault.
KEEP = {
    "first_half_of_split": lambda g, lo, hi: g < lo + (hi - lo) // 2,
    "every_other_row": lambda g, lo, hi: g % 2 == 0,
    "every_other_tile": lambda g, lo, hi: ((g - lo) // 1024) % 2 == 0,
    "random_half": lambda g, lo, hi: np.random.default_rng(
        [int(lo), int(g[0])]).random(len(g)) < 0.5,
}


def _ref_chunk(job: tuple):
    """Pool worker: per-cluster sums and counts of one split's rows, for
    each round's given centroids in that round's precision here."""
    path, lo, hi, rounds, keep = job
    points = np.load(path, mmap_mode="r")
    k, d = rounds[0][0].shape
    out = [(np.zeros((k, d), np.float64), np.zeros(k, np.int64))
           for _ in rounds]
    for a in range(lo, hi, BLOCK):
        x = np.asarray(points[a:min(a + BLOCK, hi)])
        if keep:
            x = x[KEEP[keep](np.arange(a, a + len(x)), lo, hi)]
        xb = bf16(x) if any(m != "f32" for _, m in rounds) else None
        for (cents, mode), (sums, counts) in zip(rounds, out):
            assign, seen = _assign(x, xb, cents, mode)
            onehot = np.zeros((x.shape[0], k), np.float32)
            onehot[np.arange(x.shape[0]), assign] = 1.0
            sums += onehot.T @ seen
            counts += np.bincount(assign, minlength=k)
    # the control stores each split's sums, one matmul, in bfloat16
    return [(bf16(s.astype(np.float32)).astype(np.float64), c)
            if mode == "bf16" else (s, c)
            for (_, mode), (s, c) in zip(rounds, out)]


def reference_rounds(points_path: str, sizes: dict,
                     cents_list: "list[np.ndarray]", modes="f32",
                     keep: "str | None" = None,
                     lost_split: "int | None" = None
                     ) -> "list[np.ndarray]":
    """New centroids of one K-Means round for each given set of centroids,
    in ONE pass over the points (chunk = split, as the job sums them); a
    cluster that gets no point keeps its centroid. ``modes`` is one of
    ``f32``, ``chip``, ``bf16`` for every split of every round, or for
    each round a list with one for each split. ``keep`` and ``lost_split``
    plant a fault: rows a map leaves out, a split whose partial sums
    never reach the reduce."""
    rows, chunk = sizes["rows"], sizes["split_rows"]
    cents_list = [np.asarray(c, np.float32) for c in cents_list]
    los = list(range(0, rows, chunk))
    if isinstance(modes, str):
        modes = [[modes] * len(los)] * len(cents_list)
    jobs = [(points_path, lo, min(lo + chunk, rows),
             [(c, m[i]) for c, m in zip(cents_list, modes)], keep)
            for i, lo in enumerate(los)]
    with worker_pool(len(jobs)) as p:
        parts = p.map(_ref_chunk, jobs, chunksize=1)
    if lost_split is not None:
        del parts[lost_split]
    new = []
    for i, cents in enumerate(cents_list):
        s = sum(part[i][0] for part in parts)
        c = sum(part[i][1] for part in parts)
        out = cents.astype(np.float64)
        hit = c > 0
        out[hit] = s[hit] / c[hit][:, None]
        new.append(out)
    return new


def read_centroids(out_dir: str, given: np.ndarray) -> np.ndarray:
    """The new centroids a round's job wrote (``cid<TAB>[coords]`` in its
    part files); a cluster that got no point keeps the given one."""
    cents = np.asarray(given, np.float64).copy()
    seen = 0
    for p in sorted(glob.glob(os.path.join(out_dir, "part-*"))):
        with open(p) as f:
            for line in f:
                cid, _, val = line.rstrip("\n").partition("\t")
                cents[int(cid)] = np.asarray(ast.literal_eval(val))
                seen += 1
    if seen == 0:
        raise ValueError(f"no centroid records under {out_dir}")
    return cents


def centroid_gap(got: np.ndarray, want: np.ndarray) -> float:
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - want)))


# ------------------------------------------------------------ the client


class Session:
    """One round driver for the whole run: its first round on the fresh
    tracker is the cold job, as an iterating user runs them."""

    def __init__(self, cluster, sizes: dict, traffic: dict, inputs: dict,
                 out_dir: str, job_defs: "list[str]") -> None:
        self.cluster, self.sizes, self.inputs = cluster, sizes, inputs
        self.out_dir = out_dir
        self.job_defs = job_defs + list(traffic["job"].get("defs", []))
        self.proc = None
        self.started = 0

    def _start(self) -> None:
        self.started += 1
        self.out = os.path.join(self.out_dir, f"kmeans-out{self.started}")
        argv = [sys.executable,
                os.path.join(REPO, "bench", "families", "kmeans_client.py"),
                self.cluster.addr, f"file://{self.inputs['points']}",
                f"file://{self.out}", "-k", str(self.sizes["k"]),
                "--split-rows", str(self.sizes["split_rows"])]
        for d in self.job_defs:
            argv += ["-D", d]
        self._err = open(os.path.join(
            self.cluster.work, f"kmeans-client{self.started}.err"), "wb")
        self.proc = subprocess.Popen(
            argv, cwd=REPO, env=child_env({"JAX_PLATFORMS": "cpu"}),
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._err, text=True, start_new_session=True)
        self._read()    # "ready": the seed centroids are written

    def _read(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise BenchFailure(
                f"the round driver exited rc={self.proc.poll()}; see "
                f"{self._err.name}")
        return json.loads(line)

    def restart(self) -> None:
        """A fresh driver (with a fresh tracker, for the cold job)."""
        self.close()

    def submit(self) -> dict:
        if self.proc is None:
            self._start()
        t0 = time.monotonic()
        self.proc.stdin.write("round\n")
        self.proc.stdin.flush()
        ans = self._read()
        return {"name": ans["job_name"], "client_s": time.monotonic() - t0,
                "ok": ans["ok"], "round": ans["round"],
                "out": os.path.join(self.out, f"iter{ans['round']}"),
                "given": os.path.join(self.out,
                                      f"iter{ans['round']}.in.npy")}

    def close(self) -> None:
        if self.proc is None:
            return
        try:
            self.proc.stdin.write("quit\n")
            self.proc.stdin.flush()
            self.proc.wait(timeout=10)
        except (OSError, subprocess.TimeoutExpired):
            pass
        kill_session(self.proc.pid)
        self.proc.wait()
        self.proc.stdout.close()
        self.proc.stdin.close()
        self._err.close()
        self.proc = None


def job_failure(r: dict, sizes: dict, on_chip: bool) -> "str | None":
    """Why this job counts under ``failed`` whatever its output: PR 4's
    fault tolerance turns a broken device path into a passing job, and the
    job writes centroids only, so rows or partial sums that were lost on
    the way show in its counters alone."""
    n_maps = -(-sizes["rows"] // sizes["split_rows"])
    tpu = counter(r, BACKEND, "TPU_MAP_TASKS")
    cpu = counter(r, BACKEND, "CPU_MAP_TASKS")
    if r["state"] != "SUCCEEDED":
        return f"state {r['state']}"
    if tpu <= 0:
        return "no map task ran on a TPU slot"
    if tpu + cpu != n_maps:
        return f"TPU {tpu} + CPU {cpu} map tasks != {n_maps} splits"
    if counter(r, JOBC, "TPU_DEMOTIONS"):
        return "a TPU map was demoted to a CPU slot"
    if counter(r, JOBC, "FAILED_MAP_TASKS"):
        return "failed map attempts"
    if counter(r, TASKC, "MAP_INPUT_RECORDS") != sizes["rows"]:
        return (f"the maps read {counter(r, TASKC, 'MAP_INPUT_RECORDS')} "
                f"of {sizes['rows']} rows")
    if counter(r, TASKC, "REDUCE_INPUT_RECORDS") \
            != counter(r, TASKC, "MAP_OUTPUT_RECORDS"):
        return "the reduce did not get every record the maps wrote"
    return None


# ------------------------------------------------------------ comparison


def _modes(job: dict, n_splits: int) -> "list[str] | None":
    """The precision of each split of this round: ``chip`` where its map
    finished on the chip (``chip_maps``, from the master's event log),
    float32 elsewhere. None where the log and the rollup disagree."""
    chip = job.get("chip_maps")
    if chip is None or (chip and len(chip)
                        != (job.get("rollup") or {}).get("finished_tpu_maps")):
        return None
    return ["chip" if i in chip else "f32" for i in range(n_splits)]


def check(jobs: "list[dict]", sizes: dict, seed: int, inputs: dict,
          limits: dict) -> dict:
    """Every round of the window against the reference, one pass over the
    points. Returns ``{name: {"value": v, "limit": l}}``."""
    n_splits = -(-sizes["rows"] // sizes["split_rows"])
    modes = [_modes(j, n_splits) for j in jobs]
    known = [(j, m) for j, m in zip(jobs, modes) if m is not None]
    given = [np.load(j["given"]) for j, _ in known]
    want = reference_rounds(inputs["points"], sizes, given,
                            [m for _, m in known]) if known else []
    gap = 0.0 if len(known) == len(jobs) else float("inf")
    for (j, _), g, w in zip(known, given, want):
        try:
            got = read_centroids(j["out"], g)
        except (OSError, ValueError, SyntaxError):
            gap = float("inf")
            continue
        gap = max(gap, centroid_gap(got, w))
    return {"centroid_gap": {"value": gap,
                             "limit": limits["centroid_gap"]},
            "rounds_compared": {"value": len(jobs), "limit": None},
            "maps_on_chip": {"value": sum(m.count("chip")
                                          for _, m in known),
                             "limit": None}}


def _chain(sizes: dict, inputs: dict, rounds: "tuple[int, ...]"
           ) -> "list[np.ndarray]":
    """The centroids given to these rounds of the reference's own chain
    from the seed centroids (rounds 2 to 4 are a 45 s window's)."""
    cents = np.asarray(np.load(inputs["points"], mmap_mode="r")
                       [:sizes["k"]], np.float32)
    given = []
    for r in range(max(rounds) + 1):
        if r in rounds:
            given.append(cents)
        if r < max(rounds):
            cents = reference_rounds(inputs["points"], sizes,
                                     [cents])[0].astype(np.float32)
    return given


def control(sizes: dict, seed: int, inputs: dict, limits: dict,
            rounds: "tuple[int, ...]" = (2, 3, 4)) -> dict:
    """The control's reading at this size: the reference in bfloat16 put
    in the program's place with every map on the chip (the placement whose
    reference lies nearest to it), compared as ``check`` compares a job."""
    given = _chain(sizes, inputs, rounds)
    want = reference_rounds(inputs["points"], sizes, given, "chip")
    got = reference_rounds(inputs["points"], sizes, given, "bf16")
    gaps = [centroid_gap(g, w) for g, w in zip(got, want)]
    return {"centroid_gap": {"value": max(gaps), "least": min(gaps),
                             "limit": limits["centroid_gap"]}}


def fault_args(n_splits: int) -> dict:
    """The faults this cell can have, as arguments of ``reference_rounds``
    that plant them in the reference: rows a map leaves out, a split whose
    partial sums are lost. (A round that returns its centroids unchanged
    needs no pass.)"""
    args = {name: {"keep": name} for name in KEEP}
    args["first_split_lost"] = {"lost_split": 0}
    args["middle_split_lost"] = {"lost_split": n_splits // 2}
    return args


def faults(sizes: dict, seed: int, inputs: dict, limits: dict,
           rounds: "tuple[int, ...]" = (2, 3, 4)) -> dict:
    """Each fault's reading at this size, as ``check`` compares a job: the
    reference with the fault planted against the reference without, every
    map on the chip. The least over the rounds is the one that counts."""
    given = _chain(sizes, inputs, rounds)
    want = reference_rounds(inputs["points"], sizes, given, "chip")
    n_splits = -(-sizes["rows"] // sizes["split_rows"])
    out = {"state_unchanged": {
        "least": min(centroid_gap(w, g.astype(np.float64))
                     for w, g in zip(want, given)),
        "limit": limits["centroid_gap"]}}
    for name, fault in fault_args(n_splits).items():
        got = reference_rounds(inputs["points"], sizes, given, "chip",
                               **fault)
        out[name] = {"least": min(centroid_gap(g, w)
                                  for g, w in zip(got, want)),
                     "limit": limits["centroid_gap"]}
    return out
