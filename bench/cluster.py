"""jobtracker + one tasktracker + client processes, as a deployment runs
them. Copied from ``chip_smoke.py`` (PR 21) so that later PRs may change
the program and its smoke script but not the yardstick. The parent that
imports this never imports JAX: the device is what the tracker's ``TPU
slot devices:`` line says, the device's memory and trace come from the
tracker process through ``bench/tracker_main.py``."""

from __future__ import annotations

import glob
import json
import os
import re
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BACKEND = "tpumr.BackendCounter"
JOBC = "tpumr.JobCounter"
TASKC = "tpumr.TaskCounter"


class BenchFailure(Exception):
    """The run cannot give a result (a daemon died, a client hung)."""


def child_env(extra: "dict | None" = None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("BENCH_RUN", None)      # the driver's own; no child reads it
    env.update(extra or {})
    return env


def kill_session(pid: int) -> None:
    try:
        os.killpg(pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


def counter(rollup: dict, group: str, name: str) -> int:
    return int((rollup["counters"].get(group) or {}).get(name, 0))


class Daemon:
    """One python child in its own session, output to a log file."""

    def __init__(self, name: str, work: str, argv: "list[str]",
                 env_extra: "dict | None" = None) -> None:
        self.name = name
        self.log_path = os.path.join(work, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable] + argv, cwd=REPO, env=child_env(env_extra),
            stdout=self._log, stderr=self._log, start_new_session=True)

    def text(self, since: int = 0) -> str:
        with open(self.log_path, "rb") as f:
            f.seek(since)
            return f.read().decode("utf-8", "replace")

    def log_size(self) -> int:
        return os.path.getsize(self.log_path)

    def wait_for(self, pattern: str, timeout: float) -> "re.Match":
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            m = re.search(pattern, self.text())
            if m:
                return m
            if self.proc.poll() is not None:
                raise BenchFailure(
                    f"{self.name} exited rc={self.proc.returncode} before "
                    f"printing {pattern!r}:\n{self.text()[-3000:]}")
            time.sleep(0.1)
        raise BenchFailure(f"{self.name} never printed {pattern!r} in "
                           f"{timeout:.0f}s:\n{self.text()[-3000:]}")

    def compiles(self, since: int = 0) -> "tuple[float, int]":
        """(seconds, count) of the XLA compilations this process logged
        (JAX_LOG_COMPILES=1) past byte offset ``since`` of its log."""
        secs = [float(s) for s in re.findall(
            r"Finished XLA compilation of .* in ([0-9.eE+-]+) sec",
            self.text(since))]
        return sum(secs), len(secs)

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
    """``tpumr jobtracker`` + one ``tpumr tasktracker`` that owns the chip.
    The tracker runs through ``bench/tracker_main.py``: the same
    ``tpumr.cli.main``, plus a thread that answers the benchmark's
    requests for the device's memory statistics and a profiler trace."""

    def __init__(self, work: str, daemon_defs: "list[str]",
                 tracker_defs: "list[str]") -> None:
        self.work = work
        self.daemon_defs = daemon_defs
        self.tracker_defs = tracker_defs
        self.history = os.path.join(work, "history")
        self.control = os.path.join(work, "control")
        self.daemons: "list[Daemon]" = []
        self.device: "dict | None" = None
        self._seq = 0
        self._consumed: "set[str]" = set()

    def start(self) -> "Cluster":
        os.makedirs(self.control, exist_ok=True)
        common = ["-D", f"tpumr.history.dir={self.history}"] \
            + self.daemon_defs
        self.jt = Daemon("jobtracker", self.work,
                         ["-m", "tpumr.cli"] + common
                         + ["jobtracker", "-port", "0"])
        self.daemons.append(self.jt)
        m = self.jt.wait_for(r"JobMaster up at ([\w.]+):(\d+)", 60)
        self.addr = f"{m.group(1)}:{m.group(2)}"
        self.start_tracker()
        return self

    def start_tracker(self) -> None:
        self.tt = Daemon(
            "tasktracker", self.work,
            [os.path.join(REPO, "bench", "tracker_main.py"), self.control]
            + ["-D", f"tpumr.history.dir={self.history}"] + self.daemon_defs
            + ["-D", f"mapred.local.dir={self.work}/local"]
            + self.tracker_defs + ["tasktracker", "-jt", self.addr],
            env_extra={"JAX_LOG_COMPILES": "1"})
        self.daemons.append(self.tt)
        # a tracker with TPU slots and no TPU device (and no explicit CPU
        # request) exits here instead of printing its banner
        self.tt.wait_for(r"NodeRunner up", 300)
        m = re.search(r"TPU slot devices: (\{.*\})", self.tt.text())
        if m is None:
            raise BenchFailure("tracker did not name its TPU slot devices:"
                               f"\n{self.tt.text()[-2000:]}")
        self.device = json.loads(m.group(1))

    def restart_tracker(self) -> None:
        """A fresh tracker process against the same master (the cold job
        is the first job on a fresh tracker with the compile cache warm)."""
        self.daemons.remove(self.tt)
        self.tt.stop()
        os.rename(self.tt.log_path, self.tt.log_path + ".first")
        self.start_tracker()

    def run_client(self, tag: str, argv: "list[str]", timeout: float,
                   env_extra: "dict | None" = None) -> dict:
        """Run one client process to its end; ``argv`` follows the
        interpreter. Returns its wall seconds and output."""
        self._seq += 1
        base = os.path.join(self.work, f"client{self._seq:03d}-{tag}")
        t0 = time.monotonic()
        with open(base + ".out", "wb") as out, \
                open(base + ".err", "wb") as err:
            proc = subprocess.Popen(
                [sys.executable] + argv, cwd=REPO, env=child_env(env_extra),
                stdout=out, stderr=err, start_new_session=True)
            try:
                proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                pass
            finally:
                kill_session(proc.pid)
                proc.wait()
        wall = time.monotonic() - t0
        with open(base + ".out", "r", errors="replace") as f:
            stdout = f.read()
        with open(base + ".err", "r", errors="replace") as f:
            stderr = f.read()
        return {"wall_s": wall, "rc": proc.returncode, "stdout": stdout,
                "stderr": stderr}

    def tpumr_argv(self, args: "list[str]") -> "list[str]":
        """``tpumr -D mapred.job.tracker=<addr> <args>``, as a user types."""
        return ["-m", "tpumr.cli", "-D",
                f"mapred.job.tracker={self.addr}"] + args

    def rollup(self, job_name: str, timeout: float = 20.0) -> "dict | None":
        """The per-job stats rollup (``metrics-<jobid>.json``, what ``tpumr
        job stats`` prints) of the job of this name that no earlier call
        returned; None if the master wrote none."""
        deadline = time.monotonic() + timeout
        while True:
            for p in sorted(glob.glob(os.path.join(
                    self.history, "metrics-job_*.json"))):
                if p in self._consumed:
                    continue
                try:
                    with open(p) as f:
                        r = json.load(f)
                except ValueError:      # still being written
                    continue
                if r["job_name"] != job_name:
                    continue
                self._consumed.add(p)
                return r
            if time.monotonic() > deadline:
                return None
            time.sleep(0.1)

    def spans(self, job_ids: "list[str]", settle: float = 1.5
              ) -> "list[dict]":
        """Every span the daemons and tasks recorded for these jobs (files
        trail job completion by a flush, so wait ``settle`` first)."""
        time.sleep(settle)
        out = []
        for job_id in job_ids:
            for p in glob.glob(os.path.join(self.history,
                                            f"trace-{job_id}.*.jsonl")):
                with open(p) as f:
                    for line in f:
                        try:
                            span = json.loads(line)
                        except ValueError:
                            continue
                        span["job_id"] = job_id
                        out.append(span)
        return out

    def tpu_maps(self, job_id: str) -> "list[int] | None":
        """The map tasks of this job whose attempt finished on the TPU
        slot, from the master's event log (``<jobid>.jsonl``; complete once
        the master has stopped). None where a task finished twice or the
        log cannot be read: the placement is then not known."""
        maps: "dict[int, bool]" = {}
        try:
            with open(os.path.join(self.history, f"{job_id}.jsonl")) as f:
                for line in f:
                    ev = json.loads(line)
                    if ev.get("event") != "TASK_FINISHED" \
                            or not ev.get("is_map"):
                        continue
                    index = int(ev["attempt_id"].split("_")[-2])
                    if index in maps:
                        return None
                    maps[index] = bool(ev.get("run_on_tpu"))
        except (OSError, ValueError, KeyError, IndexError):
            return None
        return sorted(i for i, on_tpu in maps.items() if on_tpu)

    # -- requests to the tracker's control thread (tracker_main.py)

    def request(self, name: str, timeout: float = 60.0) -> dict:
        """Touch ``<control>/<name>.request`` and wait for the tracker's
        answer ``<name>.done`` (a JSON object)."""
        req = os.path.join(self.control, f"{name}.request")
        done = os.path.join(self.control, f"{name}.done")
        with open(req, "w"):
            pass
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if os.path.exists(done):
                with open(done) as f:
                    text = f.read()
                if text.endswith("\n"):
                    os.remove(done)
                    return json.loads(text)
            if self.tt.proc.poll() is not None:
                raise BenchFailure(f"tracker died before answering {name}")
            time.sleep(0.05)
        raise BenchFailure(f"tracker did not answer {name} in {timeout}s")

    def stop(self) -> "dict[str, int]":
        rcs = {d.name: d.stop() for d in reversed(self.daemons)}
        self.daemons = []
        return rcs
