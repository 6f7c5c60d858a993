"""What a map of one hybrid job costs on each backend: the estimate.

The hybrid scheduler (scheduler.py ``budget_of``) and the twin on an
idle chip (job_in_progress.py ``_obtain_tpu_twin``) both need to know,
in seconds, what a CPU map of this job costs and what one TURN of a TPU
slot costs. The reference learns both from finished maps of the job
alone (JobQueueTaskScheduler.java:127-178), which teaches nothing in a
job whose CPU maps never finish, and nothing on the first beat of any
job. This estimate also reads what needs no map to finish:

- ``t_tpu``: the mean interval between two launches of the job on one
  device while maps were pending throughout (map time plus the gap the
  beat leaves), measured on the master's clock, and kept apart for the
  turns ALONE and the turns BESIDE a running CPU map of the job: a numpy
  map in the tracker's interpreter costs the chip's map thread a factor
  of two to thirteen (0.054 s and 0.128 s a turn in
  ``kmeans-100m.rounds``), so a turn measured beside CPU maps says a CPU
  share pays, and with one mean the job stays where it started;
- ``t_cpu``: the mean of finished CPU maps, never less than the longest
  time a CPU attempt has run WITHOUT finishing (running now, or killed:
  an attempt that ran 16 s and was killed proves a CPU map costs over
  16 s);
- what the job before it with the same :func:`map_cost_key` ended with
  (the master keeps it: ``JobMaster._map_costs``), until this job's own
  evidence replaces it.

Pure arithmetic on stamps handed in: no clock, no lock, no conf. The
job (under its own lock) tells it of every map launch and end.
"""

from __future__ import annotations

import math
from typing import NamedTuple

#: samples (finished CPU maps; turns of a TPU slot) from which the job's
#: own mean stands alone: below it a carried number or a lower bound
#: still has a say
FEW = 3

#: an idle chip twins a running CPU map once the map has more than this
#: many turns of the chip left: doing it over costs one turn, the kill
#: and the beats around it about another
TWIN_TURNS = 3.0


class TurnCost(NamedTuple):
    """One turn of a TPU slot with no CPU map of the job running, and
    beside one; each stands in for the other until it is measured."""
    alone: float
    beside: float


class CarriedCost(NamedTuple):
    """What a finished job hands the next one with the same key."""
    t_cpu: float
    #: ``t_cpu`` is only a lower bound: no CPU map finished to give it
    cpu_is_bound: bool
    #: each 0.0 where neither this job nor one before it measured it
    t_tpu: TurnCost


class CpuCost(NamedTuple):
    seconds: float
    #: ``job`` | ``running`` | ``carried`` | ``none``
    source: str
    #: nothing ever finished behind this number: it says "at least"
    is_bound: bool

    def left_after(self, elapsed: float) -> float:
        """Seconds a CPU attempt that reports no progress and has run
        ``elapsed`` has left. Behind a mean, what is left of it (an
        attempt past it is a straggler for the ordinary speculation,
        not for the twin on an idle chip). Behind a bound alone nothing
        says when it ends: as long again as it has run, and no less
        than the bound."""
        left = max(0.0, self.seconds - elapsed)
        return max(left, elapsed) if self.is_bound else left


def map_cost_key(conf: dict, splits: "list[dict | None]") -> "tuple | None":
    """What the master can read at submit that fixes a map's cost, or
    None for a job with no device kernel: the kernel (or TPU pipes
    executable), the input it maps and how it is read, the knobs of the
    kernel's own conf (its ``tpumr.<kernel>.*`` keys that name no file),
    and the longest split in bytes. The kernel's
    side input (K-Means' centroids, matmul's B) is NOT in it: a loop
    writes a new file every round, and a loop is what carrying is for;
    where its size changes between jobs the job's own evidence corrects
    the carried numbers within a few turns."""
    kernel = str(conf.get("tpumr.map.kernel")
                 or conf.get("tpumr.pipes.tpu.executable") or "")
    if not kernel:
        return None
    family = "tpumr." + kernel.split("-", 1)[0] + "."
    knobs = tuple(sorted(
        (k, str(v)) for k, v in conf.items()
        if k.startswith(family) and "/" not in str(v)))
    longest = 0
    for s in splits:
        s = s or {}
        longest = max(longest, int(
            s.get("split_length")
            or int(s.get("num_rows") or 0) * int(s.get("row_bytes") or 0)))
    return (kernel, str(conf.get("mapred.input.format.class") or ""),
            str(conf.get("mapred.input.dir") or ""), knobs, longest)


class MapCostEstimate:
    def __init__(self) -> None:
        self.carried: "CarriedCost | None" = None
        # --- TPU side: turns of a slot ---
        #: beside a CPU map? -> [seconds, turns]
        self._turns: "dict[bool, list[float]]" = {False: [0.0, 0],
                                                  True: [0.0, 0]}
        #: (tracker, device) -> stamp of the job's last launch there
        self._last_launch: "dict[tuple, float]" = {}
        #: stamp of the last launch that left no TPU-eligible map
        #: pending: an interval that holds it is idleness, not a turn
        self._drained = -math.inf
        #: TPU attempts launched and not ended: while there is one, a
        #: chip is serving this job
        self._tpu_running: "set[str]" = set()
        # --- CPU side: attempts that have not finished ---
        #: attempt id -> launch stamp, CPU attempts not ended
        self.cpu_running: "dict[str, float]" = {}
        #: stamp of the last end of a CPU attempt
        self._cpu_last_end = -math.inf
        #: the longest a KILLED CPU attempt had run
        self._cpu_killed_max = 0.0

    # ------------------------------------------------------- evidence in

    def tpu_launched(self, aid: str, slot: tuple, now: float,
                     still_pending: bool) -> None:
        prev = self._last_launch.get(slot)
        if prev is not None and self._drained < prev:
            beside = bool(self.cpu_running) or self._cpu_last_end > prev
            turns = self._turns[beside]
            turns[0] += now - prev
            turns[1] += 1
        self._last_launch[slot] = now
        if not still_pending:
            self._drained = now
        self._tpu_running.add(aid)

    def cpu_launched(self, aid: str, now: float) -> None:
        self.cpu_running[aid] = now

    def attempt_ended(self, aid: str, now: float, killed: bool) -> None:
        self._tpu_running.discard(aid)
        start = self.cpu_running.pop(aid, None)
        if start is not None:
            self._cpu_last_end = now
            if killed:
                self._cpu_killed_max = max(self._cpu_killed_max,
                                           now - start)

    def forget_tpu(self) -> None:
        """The job lost its accelerator (quarantine): its turns say
        nothing any more, and nothing carried may stand in for them."""
        for turns in self._turns.values():
            turns[:] = [0.0, 0]
        self._last_launch.clear()
        self._tpu_running.clear()
        self.carried = None

    # ------------------------------------------------------ estimate out

    def tpu_serving(self) -> bool:
        return bool(self._tpu_running)

    def t_tpu_own(self, tpu_mean: float) -> float:
        """One turn by THIS job's evidence alone, alone or beside: its
        turns, or (before a slot has turned once) the mean runtime of
        its finished TPU maps, which leaves the gap out; 0.0 with
        neither."""
        seconds = self._turns[False][0] + self._turns[True][0]
        n = self._turns[False][1] + self._turns[True][1]
        return seconds / n if n else max(0.0, tpu_mean)

    def _turn(self, beside: bool, few: int) -> float:
        """This job's mean of the turns alone or beside once it has
        ``few`` of them, else the carried one, else 0.0."""
        seconds, n = self._turns[beside]
        if n >= few:
            return seconds / n
        return self.carried.t_tpu[beside] if self.carried is not None \
            else 0.0

    def t_tpu(self, tpu_mean: float) -> TurnCost:
        """A turn alone and a turn beside a CPU map: for each, the
        job's own after its first few turns of that kind, before that
        the carried one, else the few it has; a kind nobody has
        measured reads as the other, and with no turn at all both read
        the mean runtime of the finished TPU maps."""
        alone, beside = (self._turn(b, FEW) or self._turn(b, 1)
                         for b in (False, True))
        return TurnCost(alone or beside or max(0.0, tpu_mean),
                        beside or alone or max(0.0, tpu_mean))

    def t_cpu(self, now: float, n_finished: int,
              cpu_mean: float) -> CpuCost:
        if n_finished >= FEW:
            return CpuCost(cpu_mean, "job", False)
        if n_finished > 0:
            base = CpuCost(cpu_mean, "job", False)
        elif self.carried is not None and self.carried.t_cpu > 0:
            base = CpuCost(self.carried.t_cpu, "carried",
                           self.carried.cpu_is_bound)
        else:
            base = CpuCost(0.0, "none", True)
        unfinished = max(
            self._cpu_killed_max,
            now - min(self.cpu_running.values(), default=now))
        if unfinished > base.seconds:
            return CpuCost(unfinished, "running", base.is_bound)
        return base

    def to_carry(self, now: float, n_finished: int, cpu_mean: float,
                 tpu_mean: float) -> "CarriedCost | None":
        """What the next job starts from: of each kind of turn the
        job's own where it has any, else what it was handed; a job that
        never turned a slot hands on its finished TPU maps' mean."""
        cpu = self.t_cpu(now, n_finished, cpu_mean)
        turn = TurnCost(self._turn(False, 1), self._turn(True, 1))
        if not any(turn):
            turn = TurnCost(max(0.0, tpu_mean), 0.0)
        if cpu.seconds <= 0 and not any(turn):
            return None
        return CarriedCost(cpu.seconds, cpu.is_bound, turn)


def cpu_share(pending: int, n_cpu: int, n_tpu: int, t_cpu: float,
              t_tpu: TurnCost) -> int:
    """The implemented form of the reference's commented-out
    minimization (JobQueueTaskScheduler.java:181-219): the CPU share x of
    the pending maps that minimizes
    ``f(x, y) = max(⌈x/n_cpu⌉·t_cpu, ⌈y/n_tpu⌉·t_tpu)``, capped at the
    free CPU slots (0 when the optimum puts everything on the chip).
    ``t_tpu`` is the turn alone for x = 0 and the turn beside a CPU map
    for every other share. With a cost unknown it is the full share."""
    if pending == 0 or n_cpu == 0 or n_tpu == 0 \
            or t_cpu <= 0 or min(t_tpu) <= 0:
        return n_cpu

    def cpu_side(x: int) -> float:
        return math.ceil(x / n_cpu) * t_cpu

    def tpu_side(x: int) -> float:
        return math.ceil((pending - x) / n_tpu) * (
            t_tpu.beside if x else t_tpu.alone)

    def f(x: int) -> float:
        return max(cpu_side(x), tpu_side(x))

    # the answer is min(n_cpu, first argmin of f): the shares under
    # n_cpu one by one, and of the others (a 50,000-map job has as
    # many) only whether the best of them beats those. From x = 1 on f
    # is the larger of a rising and a falling step function, least
    # where they cross.
    few = [f(x) for x in range(min(n_cpu, pending + 1))]
    best = min(few)
    if pending >= n_cpu:
        lo, hi = n_cpu, pending
        while lo < hi:
            mid = (lo + hi) // 2
            if cpu_side(mid) >= tpu_side(mid):
                hi = mid
            else:
                lo = mid + 1
        if min(f(lo), f(max(n_cpu, lo - 1))) < best:
            return n_cpu
    return few.index(best)
