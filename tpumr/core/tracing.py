"""Distributed job tracing — spans across the whole control plane.

New capability beyond the reference (its GPU observability was log-only,
SURVEY.md §5): every stage of a job's life — submit → schedule → launch →
map → spill → shuffle fetch → merge → commit — is recorded as a SPAN
(``trace_id``, ``span_id``, ``parent_span_id``, name, role, backend,
start/end, attributes) so the question the hybrid CPU/TPU scheduler
lives or dies on ("where does wall-clock actually go?") is answerable
from one queryable timeline instead of grepping daemon logs.

Design:

- **One trace per job.** The JobMaster mints a ``trace_id`` at submit
  when ``tpumr.trace.enabled`` is true (job conf or master conf) and
  stores it in the job conf (``tpumr.trace.id``), which already flows to
  every tracker (get_job_conf) and child process (the task file). Span
  context crosses process boundaries on existing seams: launch actions
  carry the scheduling span's context on the Task, the umbilical task
  file ships it to isolated children, and shuffle fetch spans name their
  source address per fetch.
- **Off by default, near-zero cost.** Without the flag no tracer is
  consulted beyond a None check: the ambient helpers short-circuit on a
  thread-local read, and daemons never stamp trace context on tasks of
  untraced jobs.
- **Per-process JSONL flush.** Each daemon/process appends finished
  spans to ``<trace dir>/trace-<trace_id>.<role>-<uniq>.jsonl`` next to
  the job history (``tpumr.trace.dir``, default ``tpumr.history.dir``).
  One file per tracer instance — no cross-process append interleaving.
  The JobMaster merges the files on demand (``/tracejson?job=`` and the
  ``get_job_trace`` RPC) into Chrome trace-event JSON loadable by
  ``chrome://tracing`` / Perfetto.
- **Two clocks, each for what it is good at.** ``Span.start`` is
  wall-clock, because the files of several processes are merged on it;
  a span's LENGTH is taken from ``time.monotonic()`` (``end = start +
  elapsed``), so a step of the wall clock mid-span cannot make a negative
  or a stretched span.
- **One timeline with the device profiler.** In a process that has
  already imported ``jax`` (the tracker and its in-process tasks), an
  ambient :func:`span` of a traced job also enters
  ``jax.profiler.TraceAnnotation(name, span_id=..., trace_id=...)`` for
  its length, so a profile taken meanwhile shows ``tpu:execute`` or
  ``dshuffle:device`` on the host line above the device's operations, on
  the profiler's own clock. This module never imports ``jax`` itself.
- **Critical path.** :func:`critical_path` walks the span tree backward
  from the last-finishing leaf (the classic makespan-dominating chain)
  and reports each span's contribution — the measurement substrate every
  later perf PR benchmarks against.
"""

from __future__ import annotations

import json
import os
import re
import sys
import threading
import time
from contextlib import contextmanager
from typing import Any, Iterator

ENABLED_KEY = "tpumr.trace.enabled"
TRACE_ID_KEY = "tpumr.trace.id"
TRACE_DIR_KEY = "tpumr.trace.dir"
SAMPLE_KEY = "tpumr.trace.sample"

#: flush to disk once this many finished spans are buffered (spans also
#: flush explicitly at task/job completion so merges see fresh data)
FLUSH_THRESHOLD = 256

#: hard per-process buffer bound: when the flusher can't keep up (or no
#: trace dir is configured and nothing drains the buffer between
#: threshold flushes), the OLDEST buffered spans are dropped and counted
#: (``Tracer.dropped``) — a scale-harness run with hundreds of simulated
#: trackers must never let trace buffering grow without bound
MAX_BUFFERED = 8192

_id_lock = threading.Lock()
_id_counter = 0


def new_span_id() -> str:
    """Unique-enough 16-hex span id (random, no coordination needed)."""
    return os.urandom(8).hex()


def _uniq() -> int:
    global _id_counter
    with _id_lock:
        _id_counter += 1
        return _id_counter


def _safe_trace_id(trace_id: str) -> str:
    """Trace ids become file names — constrain to a safe alphabet."""
    return re.sub(r"[^A-Za-z0-9_.-]", "_", str(trace_id))[:128]


def trace_enabled(conf: Any) -> bool:
    """The one ``tpumr.trace.enabled`` predicate — handles typed confs
    (get_boolean) and plain submission dicts (string/bool values)."""
    try:
        return bool(conf.get_boolean(ENABLED_KEY, False))
    except (AttributeError, TypeError, ValueError):
        v = conf.get(ENABLED_KEY)
        return v is True or str(v).lower() in ("true", "1")


def trace_sample_rate(conf: Any) -> float:
    """Per-job head-sampling rate (``tpumr.trace.sample``, default 1.0):
    the master draws once at submit — a sampled-out job is simply not
    traced (no id minted, zero per-span cost anywhere), which is how a
    cluster runs hundreds of trackers with tracing on without the JSONL
    volume scaling with job count. Clamped to [0, 1]; a malformed value
    falls back to 1.0 (trace rather than silently lose everything)."""
    try:
        v = conf.get(SAMPLE_KEY)
    except (AttributeError, TypeError):
        return 1.0
    if v is None or v == "":
        return 1.0
    try:
        return min(1.0, max(0.0, float(v)))
    except (TypeError, ValueError):
        return 1.0


def trace_dir_from_conf(conf: Any) -> "str | None":
    """The one trace-sink resolution chain: ``tpumr.trace.dir``, else
    next to the job history (``tpumr.history.dir``), else None (spans
    buffered then dropped). Every daemon/CLI consults THIS so they can
    never write and read traces in different places."""
    d = conf.get(TRACE_DIR_KEY) or conf.get("tpumr.history.dir")
    return str(d) if d else None


class Span:
    """One timed operation. Mutable until :meth:`Tracer.finish`."""

    __slots__ = ("trace_id", "span_id", "parent_span_id", "name", "role",
                 "backend", "start", "end", "attributes", "_t0")

    def __init__(self, trace_id: str, span_id: str, parent_span_id: str,
                 name: str, role: str, backend: str = "",
                 start: float = 0.0, end: float = 0.0,
                 attributes: "dict | None" = None) -> None:
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_span_id = parent_span_id
        self.name = name
        self.role = role
        self.backend = backend
        self.start = start
        self.end = end
        self.attributes = attributes if attributes is not None else {}
        #: monotonic twin of ``start``: lengths never read the wall clock
        self._t0 = time.monotonic()

    def elapsed(self) -> float:
        """Monotonic seconds since the span was made."""
        return time.monotonic() - self._t0

    def backdate(self, start: float) -> "Span":
        """Move the start back to an earlier wall-clock reading, on both
        clocks: for a phase that is recorded once it is over."""
        self._t0 -= self.start - start
        self.start = start
        return self

    def set(self, **attrs: Any) -> "Span":
        self.attributes.update(attrs)
        return self

    @property
    def context(self) -> dict:
        """Wire-able propagation context ({trace_id, span_id})."""
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    @property
    def duration(self) -> float:
        if self.end:
            return max(0.0, self.end - self.start)
        return self.elapsed()

    def to_dict(self) -> dict:
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "parent_span_id": self.parent_span_id, "name": self.name,
                "role": self.role, "backend": self.backend,
                "start": self.start, "end": self.end,
                "attributes": self.attributes}


class Tracer:
    """Thread-safe per-process span buffer + JSONL flusher for one
    daemon role. Construct via :meth:`from_conf` (returns None when
    tracing is off — callers keep a ``tracer is None`` fast path)."""

    def __init__(self, role: str, trace_dir: "str | None" = None,
                 hostname: "str | None" = None) -> None:
        self.role = role
        self.trace_dir = trace_dir
        if hostname is None:
            import socket
            hostname = socket.gethostname()
        self.hostname = hostname
        self._lock = threading.Lock()
        self._finished: list[Span] = []
        #: spans dropped at the MAX_BUFFERED high-water mark (observable
        #: tell that the flusher fell behind the span rate)
        self.dropped = 0
        #: serializes the file-append phase of flush() — concurrent
        #: flushes (threshold thread + an explicit caller) must not
        #: interleave partial lines in one tracer's file
        self._flush_lock = threading.Lock()
        self._flush_pending = False
        #: per-tracer file suffix: many tracers (mini-cluster daemons)
        #: share a process; each appends to its OWN file so line writes
        #: never interleave
        self._fileid = f"{os.getpid():x}-{_uniq():x}"

    @classmethod
    def from_conf(cls, conf: Any, role: str) -> "Tracer | None":
        """A tracer when ``tpumr.trace.enabled`` is set, else None."""
        if not trace_enabled(conf):
            return None
        return cls(role, trace_dir=trace_dir_from_conf(conf))

    # ------------------------------------------------------------ spans

    def start_span(self, name: str, trace_id: str,
                   parent: "dict | Span | str | None" = None,
                   role: "str | None" = None, backend: str = "",
                   **attrs: Any) -> Span:
        if isinstance(parent, Span):
            parent_id = parent.span_id
        elif isinstance(parent, dict):
            parent_id = str(parent.get("span_id", ""))
        else:
            parent_id = parent or ""
        return Span(trace_id=str(trace_id), span_id=new_span_id(),
                    parent_span_id=parent_id, name=name,
                    role=role or self.role, backend=backend,
                    start=time.time(), attributes=dict(attrs))

    def finish(self, span: Span) -> Span:
        span.end = span.start + span.elapsed()
        span.attributes.setdefault("host", self.hostname)
        with self._lock:
            self._finished.append(span)
            n = len(self._finished)
            if n > MAX_BUFFERED:
                # flusher outrun (or no sink): shed the OLDEST spans —
                # bounded memory beats a complete-but-growing buffer
                shed = n - MAX_BUFFERED
                del self._finished[:shed]
                self.dropped += shed
                n = MAX_BUFFERED
        if n >= FLUSH_THRESHOLD:
            # finish() is called from hot paths that may hold daemon
            # locks (the master records schedule spans mid-heartbeat) —
            # the growth-bound flush must never do disk I/O there
            self._schedule_flush()
        return span

    def _schedule_flush(self) -> None:
        with self._lock:
            if self._flush_pending:
                return
            self._flush_pending = True

        def run() -> None:
            try:
                self.flush()
            finally:
                with self._lock:
                    self._flush_pending = False

        threading.Thread(target=run, name="trace-flush",
                         daemon=True).start()

    @contextmanager
    def span(self, name: str, trace_id: str,
             parent: "dict | Span | str | None" = None,
             role: "str | None" = None, backend: str = "",
             **attrs: Any) -> "Iterator[Span]":
        s = self.start_span(name, trace_id, parent=parent, role=role,
                            backend=backend, **attrs)
        try:
            yield s
        except BaseException as e:
            s.set(error=f"{type(e).__name__}: {e}")
            raise
        finally:
            self.finish(s)

    def instant(self, name: str, trace_id: str,
                parent: "dict | Span | str | None" = None,
                role: "str | None" = None, **attrs: Any) -> Span:
        """A zero-ish-duration marker span (scheduling decisions,
        penalty-box holds)."""
        s = self.start_span(name, trace_id, parent=parent, role=role,
                            **attrs)
        return self.finish(s)

    # ------------------------------------------------------------ flush

    def pending(self) -> "list[Span]":
        with self._lock:
            return list(self._finished)

    def flush(self) -> int:
        """Append buffered finished spans to per-trace JSONL files.
        Returns the number of spans written (0 when no dir is
        configured — spans are then dropped rather than growing without
        bound)."""
        with self._flush_lock:
            with self._lock:
                spans, self._finished = self._finished, []
            if not spans:
                return 0
            if not self.trace_dir:
                return 0
            by_trace: dict[str, list[Span]] = {}
            for s in spans:
                by_trace.setdefault(s.trace_id, []).append(s)
            written = 0
            try:
                os.makedirs(self.trace_dir, exist_ok=True)
                for tid, group in by_trace.items():
                    path = os.path.join(
                        self.trace_dir,
                        f"trace-{_safe_trace_id(tid)}."
                        f"{_safe_trace_id(self.role)}-{self._fileid}.jsonl")
                    # default=str: ambient spans accept arbitrary user
                    # attrs (numpy scalars, paths) — one unserializable
                    # value must not sink the whole batch
                    blob = "".join(json.dumps(s.to_dict(), default=str)
                                   + "\n" for s in group)
                    with open(path, "a") as f:
                        f.write(blob)
                    written += len(group)
            except Exception:  # noqa: BLE001 — tracing must never take
                return written  # a daemon down; spans lost, job is not
            return written


# ------------------------------------------------------------ ambient
# Thread-local "current tracer + span" so deep code (spill loops, the
# shuffle copier, the TPU runner) records child spans without threading
# a tracer through every signature. Disabled == one attribute lookup.

_ambient = threading.local()


@contextmanager
def activate(tracer: "Tracer | None", span: "Span | None"):
    """Install ``tracer``/``span`` as the calling thread's ambient trace
    context for the duration (task run threads, child main)."""
    prev = getattr(_ambient, "ctx", None)
    _ambient.ctx = (tracer, span) if tracer is not None else None
    try:
        yield
    finally:
        _ambient.ctx = prev


def capture() -> "tuple | None":
    """Snapshot the ambient context for hand-off to worker threads
    (the shuffle copier's fetch pool)."""
    return getattr(_ambient, "ctx", None)


@contextmanager
def activate_captured(ctx: "tuple | None"):
    prev = getattr(_ambient, "ctx", None)
    _ambient.ctx = ctx
    try:
        yield
    finally:
        _ambient.ctx = prev


def current() -> "tuple[Tracer, Span] | None":
    return getattr(_ambient, "ctx", None)


def _profiler_annotation(s: Span) -> Any:
    """A ``jax.profiler.TraceAnnotation`` carrying the span's name and
    ids, in a process where ``jax`` is already imported; None elsewhere
    (a client, the master, a child pinned off JAX never import it for a
    span's sake). Outside a profiler session the annotation is a no-op."""
    jax = sys.modules.get("jax")
    # getattr twice: another thread may be halfway through ``import jax``
    cls = getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)
    if cls is None:
        return None
    return cls(s.name, span_id=s.span_id, trace_id=s.trace_id)


@contextmanager
def span(name: str, backend: str = "", role: "str | None" = None,
         **attrs: Any) -> "Iterator[Span | None]":
    """Ambient child span: records under the thread's active span, or
    no-ops (yielding None) when tracing is inactive. Where ``jax`` is
    loaded the span is mirrored onto the profiler's host line."""
    ctx = getattr(_ambient, "ctx", None)
    if ctx is None:
        yield None
        return
    tracer, parent = ctx
    s = tracer.start_span(name, parent.trace_id, parent=parent,
                          role=role or parent.role, backend=backend,
                          **attrs)
    note = _profiler_annotation(s)
    prev = ctx
    _ambient.ctx = (tracer, s)
    if note is not None:
        note.__enter__()
    try:
        yield s
    except BaseException as e:
        s.set(error=f"{type(e).__name__}: {e}")
        raise
    finally:
        if note is not None:
            note.__exit__(None, None, None)
        _ambient.ctx = prev
        tracer.finish(s)


def instant(name: str, **attrs: Any) -> None:
    """Ambient marker span (no-op when tracing is inactive)."""
    ctx = getattr(_ambient, "ctx", None)
    if ctx is None:
        return
    tracer, parent = ctx
    tracer.instant(name, parent.trace_id, parent=parent, role=parent.role,
                   **attrs)


# ------------------------------------------------------------ merge/export


def read_trace_files(trace_dir: str, trace_id: str) -> "list[dict]":
    """All flushed spans of one trace, merged across every daemon's
    per-process file, sorted by start time."""
    import glob
    safe = _safe_trace_id(trace_id)
    spans: list[dict] = []
    for path in sorted(glob.glob(os.path.join(trace_dir,
                                              f"trace-{safe}.*.jsonl"))):
        try:
            with open(path) as f:
                for line in f:
                    if line.strip():
                        spans.append(json.loads(line))
        except (OSError, ValueError):
            continue
    spans.sort(key=lambda s: s.get("start", 0.0))
    return spans


def to_chrome_trace(spans: "list[dict]") -> dict:
    """Chrome trace-event JSON (the object form with ``traceEvents``):
    one complete ("ph":"X") event per span, processes = roles (with
    process_name metadata so chrome://tracing / Perfetto label the
    swimlanes), threads = per-role span rows keyed by host+attempt so
    concurrent tasks render on separate rows."""
    events: list[dict] = []
    role_pids: dict[str, int] = {}
    tids: dict[tuple, int] = {}
    next_tid: dict[int, int] = {}     # per-pid lane counter, O(1)/lane
    for s in spans:
        role = s.get("role", "?")
        pid = role_pids.get(role)
        if pid is None:
            pid = role_pids[role] = len(role_pids) + 1
            events.append({"name": "process_name", "ph": "M", "pid": pid,
                           "tid": 0, "args": {"name": role}})
        attrs = s.get("attributes") or {}
        lane = (pid, attrs.get("host", ""), attrs.get("attempt_id", ""))
        tid = tids.get(lane)
        if tid is None:
            tid = tids[lane] = next_tid[pid] = next_tid.get(pid, 0) + 1
            label = ":".join(str(p) for p in lane[1:] if p) or role
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": tid, "args": {"name": label}})
        start = float(s.get("start", 0.0))
        end = float(s.get("end", 0.0)) or start
        events.append({
            "name": s.get("name", "?"),
            "cat": role + ("," + s["backend"] if s.get("backend") else ""),
            "ph": "X",
            "ts": int(start * 1e6),
            "dur": max(1, int((end - start) * 1e6)),
            "pid": pid,
            "tid": tid,
            "args": {**attrs, "span_id": s.get("span_id", ""),
                     "parent_span_id": s.get("parent_span_id", "")},
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def critical_path(spans: "list[dict]") -> dict:
    """The chain of spans that determined the trace's makespan: from the
    root (no in-trace parent; longest), repeatedly descend into the
    child whose SUBTREE ends latest — the dependency chain the parent
    was last waiting on (a zero-duration scheduling marker whose task
    subtree runs long is on the path; a late bookkeeping leaf is not
    unless it really ended last). Returns the path with per-span
    durations and contribution percentages (self time = duration not
    covered by the chosen child's subtree), plus the trace makespan."""
    if not spans:
        return {"path": [], "total_s": 0.0, "self_total_s": 0.0,
                "makespan_s": 0.0}
    by_id = {s["span_id"]: s for s in spans if s.get("span_id")}
    children: dict[str, list[dict]] = {}
    for s in spans:
        p = s.get("parent_span_id", "")
        if p and p in by_id:
            children.setdefault(p, []).append(s)
    roots = [s for s in spans
             if not s.get("parent_span_id")
             or s["parent_span_id"] not in by_id]

    def dur(s: dict) -> float:
        return max(0.0, float(s.get("end", 0.0) or 0.0)
                   - float(s.get("start", 0.0)))

    sub_end: dict[str, float] = {}

    def subtree_end(s: dict) -> float:
        sid = s.get("span_id", "")
        cached = sub_end.get(sid)
        if cached is not None:
            return cached
        sub_end[sid] = float(s.get("end", 0.0) or 0.0)  # cycle guard
        out = max([float(s.get("end", 0.0) or 0.0)]
                  + [subtree_end(k) for k in children.get(sid, [])])
        sub_end[sid] = out
        return out

    EPS = 1e-9
    MAX_PATH = 512
    root = max(roots, key=dur)
    seen: set[str] = set()
    path_nodes: "list[tuple[dict, float]]" = []   # (span, self seconds)

    def decompose(node: dict) -> None:
        """Append ``node`` and its time-ordered critical chain: walking
        BACKWARD from node's end, repeatedly take the child whose
        subtree ends latest while still fitting before the current
        point — the dependency the remaining interval was waiting on.
        Gaps (waiting on something outside this subtree, e.g. a reduce
        stalled on map outputs) stay charged to the node's self time,
        which is exactly where an analyst should look next."""
        sid = node.get("span_id", "")
        if sid in seen or len(path_nodes) >= MAX_PATH:
            return
        seen.add(sid)
        kids = [k for k in children.get(sid, [])
                if k.get("span_id") not in seen]
        chain: list[dict] = []
        # walk back from where the node's SUBTREE finished — an instant
        # marker (schedule) has zero duration but its task subtree is
        # the whole point of following it
        cur = subtree_end(node)
        floor = float(node.get("start", 0.0))
        avail = list(kids)
        while cur > floor + EPS and avail:
            cands = [k for k in avail if subtree_end(k) <= cur + EPS]
            if not cands:
                break
            c = max(cands, key=subtree_end)
            avail.remove(c)
            chain.append(c)
            cur = float(c.get("start", 0.0))
        covered = sum(min(subtree_end(c),
                          float(node.get("end", 0.0) or 0.0))
                      - float(c.get("start", 0.0)) for c in chain)
        path_nodes.append((node, max(0.0, dur(node) - max(0.0, covered))))
        for c in reversed(chain):              # chronological order
            decompose(c)

    decompose(root)
    path = [{"span_id": n.get("span_id", ""),
             "name": n.get("name", "?"),
             "role": n.get("role", "?"),
             "backend": n.get("backend", ""),
             "duration_s": dur(n),
             "self_s": self_s,
             "attributes": n.get("attributes") or {}}
            for n, self_s in path_nodes]
    makespan = max((float(s.get("end", 0.0) or 0.0) for s in spans),
                   default=0.0) - min((float(s.get("start", 0.0))
                                       for s in spans), default=0.0)
    total_self = sum(p["self_s"] for p in path) or 1.0
    for p in path:
        p["contribution_pct"] = round(100.0 * p["self_s"] / total_self, 2)
    return {"path": path,
            "total_s": sum(p["duration_s"] for p in path),
            "self_total_s": sum(p["self_s"] for p in path),
            "makespan_s": max(0.0, makespan)}


#: swimlane colors per role (the jobtracker's /trace page); backend
#: overrides make hybrid placement visible at a glance
_LANE_COLORS = {"jobtracker": "#6246ea", "tasktracker": "#3b8ea5",
                "task": "#2cb67d", "shuffle": "#e8a33d"}
_BACKEND_COLORS = {"tpu": "#7f5af0", "cpu": "#2cb67d"}


def swimlane_svg(spans: "list[dict]", width: int = 960) -> str:
    """Self-contained SVG timeline: one row per span, grouped by role,
    x-scaled to the trace window. Escapes all span-derived text (span
    names can contain attempt ids but attributes are job-controlled)."""
    from html import escape
    if not spans:
        return "<p class='dim'>no spans</p>"
    t0 = min(float(s.get("start", 0.0)) for s in spans)
    t1 = max(float(s.get("end", 0.0) or s.get("start", 0.0))
             for s in spans)
    window = max(t1 - t0, 1e-6)
    order = {"jobtracker": 0, "tasktracker": 1, "task": 2}
    rows = sorted(spans, key=lambda s: (order.get(s.get("role", ""), 9),
                                        float(s.get("start", 0.0))))
    dropped = max(0, len(rows) - 400)
    rows = rows[:400]       # a 50k-map job must not render 50k rects —
    #                         the full trace is one click away in JSON
    left, row_h, pad = 260, 16, 2
    height = len(rows) * (row_h + pad) + 24
    parts = [f"<svg width='{width}' height='{height}' "
             f"font-family='monospace' font-size='11'>"]
    for i, s in enumerate(rows):
        start = float(s.get("start", 0.0))
        end = float(s.get("end", 0.0) or start)
        x = left + (start - t0) / window * (width - left - 10)
        w = max(1.0, (end - start) / window * (width - left - 10))
        y = i * (row_h + pad) + 14
        color = _BACKEND_COLORS.get(s.get("backend", ""),
                                    _LANE_COLORS.get(s.get("role", ""),
                                                     "#94a1b2"))
        label = (f"{s.get('role', '?')}/{s.get('name', '?')} "
                 f"{(s.get('attributes') or {}).get('attempt_id', '')}")
        parts.append(
            f"<text x='2' y='{y + 11}' fill='currentColor'>"
            f"{escape(label[:40])}</text>"
            f"<rect x='{x:.1f}' y='{y}' width='{w:.1f}' "
            f"height='{row_h}' fill='{color}' rx='2'>"
            f"<title>{escape(s.get('name', '?'))} "
            f"{end - start:.4f}s</title></rect>")
    parts.append(
        f"<text x='{left}' y='{height - 2}' fill='currentColor'>"
        f"window {window:.3f}s · "
        + (f"{dropped} spans not shown · " if dropped else "")
        + "<tspan fill='#7f5af0'>&#9632; tpu</tspan> "
        "<tspan fill='#2cb67d'>&#9632; cpu/task</tspan> "
        "<tspan fill='#3b8ea5'>&#9632; tracker</tspan> "
        "<tspan fill='#6246ea'>&#9632; master</tspan></text>")
    parts.append("</svg>")
    return "".join(parts)


def validate_chrome_trace(doc: Any) -> "list[str]":
    """Schema check for the trace-event format (used by tests and the
    CLI): returns a list of problems, empty when loadable."""
    problems: list[str] = []
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return ["not an object with a traceEvents array"]
    events = doc["traceEvents"]
    if not isinstance(events, list):
        return ["traceEvents is not an array"]
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"event {i} is not an object")
            continue
        ph = ev.get("ph")
        if ph not in ("X", "M", "B", "E", "i", "C"):
            problems.append(f"event {i}: unknown phase {ph!r}")
            continue
        if "pid" not in ev or "name" not in ev:
            problems.append(f"event {i}: missing pid/name")
        if ph == "X":
            if not isinstance(ev.get("ts"), int) \
                    or not isinstance(ev.get("dur"), int):
                problems.append(f"event {i}: X event needs int ts/dur")
    return problems
