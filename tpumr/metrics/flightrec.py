"""Flight recorder: automatic postmortem bundles when the master
breaches its own latency SLO.

The master's SLO is a dual p99 (heartbeat handling and per-tracker lag
under ``tpumr.prof.incident.slo.ms``) — but a breach in a LIVE cluster
evaporates before anyone can attach a profiler: by the time an operator
reads the page, the convoy that caused it is gone. The recorder closes
that gap. A watchdog thread on the master derives a
WINDOWED p99 each tick from the cumulative ``heartbeat_seconds`` /
``heartbeat_lag_seconds`` histograms (``typed()`` state diffed with
``typed_delta`` — the same mechanism the heartbeat cluster merge uses),
and on a breach writes one incident bundle: the profiler's folded
stacks for the breach window, the live InstrumentedRLock holder/waiter
table plus per-lock wait/hold distributions, rpc saturation and
heartbeat-phase snapshots, and the most recent buffered trace spans —
everything a postmortem needs, captured AT the breach, as one JSON file
under ``tpumr.prof.incident.dir``.

Bundles are rate-limited (``tpumr.prof.incident.cooldown.ms``): a
sustained breach produces exactly one bundle per cooldown window, not a
disk-filling stream. ``/incidents`` on the master lists them;
``validate_incident`` is the schema checker the e2e test (and any
external consumer) holds bundles against.

The scenario lab grew the watchdog two surfaces. Per-TRAFFIC-CLASS
windowed percentiles: the master's lazily-created
``class_assign_seconds`` / ``class_complete_seconds`` histograms are
windowed the same way each tick and judged against per-class SLOs
(``tpumr.scenario.slo.<class>.{assign,complete}.ms``), yielding an
online per-class verdict (``class_report``) plus a bounded per-tick
window history the overload e2e asserts recovery against. And the tick
is the master BROWNOUT's clock: every tick folds one pressure bit
(any windowed breach, heartbeat or class) into
``JobMaster.brownout_tick``, so sustained pressure engages ranked load
shedding and sustained calm releases it. Bundles carry the workload
context — active scenario name, per-class breakdown at breach time,
brownout level and recent transitions — so a bundle alone answers
"degrading for whom, and what was already shed".
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any

from tpumr.metrics.histogram import typed_delta

#: bundle schema tag — bump on incompatible shape changes
#: (2: reason rows carry per-row slo_s; workload context section)
SCHEMA = "tpumr-incident-2"

#: watchdog cadence: 1 s ticks make the breach window ~1 s, matching
#: the heartbeat cadence the SLO is defined over
TICK_S = 1.0


def typed_p99(t: "dict | None", q: float = 0.99) -> float:
    """Interpolated quantile of a ``Histogram.typed()`` (or
    ``typed_delta``) state — the windowed read the watchdog runs on,
    where no Histogram object exists to ask."""
    if not t or not t.get("count"):
        return 0.0
    bounds = list(t.get("bounds") or [])
    buckets = {int(k): int(v) for k, v in (t.get("buckets") or {}).items()}
    total = int(t["count"])
    rank = q * total
    seen = 0.0
    for i in range(len(bounds) + 1):
        c = buckets.get(i, 0)
        if not c:
            continue
        if seen + c >= rank:
            if i >= len(bounds):
                return float(t.get("max") or (bounds[-1] if bounds else 0.0))
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[i]
            frac = (rank - seen) / c
            return lo + (hi - lo) * min(1.0, max(0.0, frac))
        seen += c
    return float(t.get("max") or 0.0)


class FlightRecorder:
    """The master's SLO watchdog + incident writer. Owns one daemon
    thread; reads only racy-safe surfaces (cumulative histogram state,
    the metrics snapshot, the lock table, buffered spans) so arming it
    adds nothing to the heartbeat path."""

    def __init__(self, master: Any, sampler: Any, slo_ms: int,
                 cooldown_ms: int, incident_dir: str,
                 conf: Any = None) -> None:
        self.master = master
        self.sampler = sampler
        self.conf = conf
        self.slo_s = slo_ms / 1000.0
        self.cooldown_s = cooldown_ms / 1000.0
        self.incident_dir = incident_dir
        self._registry = sampler.registry if sampler is not None \
            else getattr(master, "_mreg", None)
        self._prev: "dict[str, dict]" = {}
        #: per-class online verdict state, keyed by class name
        self._class_state: "dict[str, dict]" = {}
        self._class_slo_cache: \
            "dict[str, tuple[float | None, float | None]]" = {}
        #: bounded per-tick history: per-class windowed p99s + brownout
        #: level — the overload e2e proves "interactive recovered WHILE
        #: brownout was active" from this, not from cumulative state
        self._window_history: "deque[dict]" = deque(maxlen=900)
        self._last_write_mono: "float | None" = None
        self._stop = threading.Event()
        self._thread: "threading.Thread | None" = None

    @classmethod
    def from_conf(cls, conf: Any, master: Any,
                  sampler: Any) -> "FlightRecorder | None":
        """None unless an incident dir can be derived
        (``tpumr.prof.incident.dir``, else next to the job history) AND
        something wants the watchdog: the profiler (folded stacks in
        every bundle) or brownout mode (the tick is the brownout's
        clock — a stacks-less recorder still windows SLOs, judges
        classes, and writes bundles with empty ``folded_stacks``)."""
        from tpumr.core import confkeys
        if sampler is None and not confkeys.get_boolean(
                conf, "tpumr.brownout.enabled"):
            return None
        d = conf.get("tpumr.prof.incident.dir") \
            or conf.get("tpumr.history.dir")
        if not d:
            return None
        return cls(
            master, sampler,
            slo_ms=confkeys.get_int(conf, "tpumr.prof.incident.slo.ms"),
            cooldown_ms=confkeys.get_int(
                conf, "tpumr.prof.incident.cooldown.ms"),
            incident_dir=os.path.join(str(d), "incidents"),
            conf=conf)

    # ------------------------------------------------------------ lifecycle

    def start(self) -> "FlightRecorder":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="prof-flightrec", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=2.0)
            self._thread = None

    def _loop(self) -> None:
        while not self._stop.wait(TICK_S):
            try:
                self._tick()
            except Exception:  # noqa: BLE001 — the watchdog must never
                pass           # take the master down with it

    # ------------------------------------------------------------ watchdog

    def _windowed_p99s(self) -> "list[tuple[str, float]]":
        """(metric, windowed p99 seconds) for each watched histogram —
        the delta since the previous tick, so a breach long past can't
        keep the cumulative p99 pinned above the SLO forever."""
        out = []
        for metric, hist in (
                ("heartbeat_seconds", self.master._hb_seconds),
                ("heartbeat_lag_seconds", self.master._hb_lag)):
            cur = hist.typed()
            delta = typed_delta(cur, self._prev.get(metric))
            self._prev[metric] = cur
            if delta and delta.get("count"):
                out.append((metric, typed_p99(delta)))
        return out

    def _class_slos(self, cls_name: str) \
            -> "tuple[float | None, float | None]":
        """(assign_slo_s, complete_slo_s) for one traffic class, from
        ``tpumr.scenario.slo.<class>.{assign,complete}.ms`` — None when
        unset (that side is observed but never judged)."""
        cached = self._class_slo_cache.get(cls_name)
        if cached is not None:
            return cached
        out = []
        for kind in ("assign", "complete"):
            raw = self.conf.get(
                f"tpumr.scenario.slo.{cls_name}.{kind}.ms") \
                if self.conf is not None else None
            try:
                out.append(float(raw) / 1000.0 if raw not in
                           (None, "") else None)
            except (TypeError, ValueError):
                out.append(None)
        self._class_slo_cache[cls_name] = (out[0], out[1])
        return self._class_slo_cache[cls_name]

    def _fold_classes(self) -> "list[tuple[str, str, float,"\
            " float | None, bool]]":
        """Window the master's per-class latency histograms (same
        typed-delta mechanism as the heartbeat SLOs) and fold the
        online verdict state. Returns (class, kind, p99_s, slo_s,
        breach) rows for windows that carried data."""
        rows: "list[tuple[str, str, float, float | None, bool]]" = []
        hists = getattr(self.master, "_class_hists", None) or {}
        for (kind, cls_name), hist in list(hists.items()):
            key = f"class_{kind}|{cls_name}"
            cur = hist.typed()
            delta = typed_delta(cur, self._prev.get(key))
            self._prev[key] = cur
            if not delta or not delta.get("count"):
                continue
            p99 = typed_p99(delta)
            slo = self._class_slos(cls_name)[0 if kind == "assign"
                                             else 1]
            breach = slo is not None and p99 > slo
            st = self._class_state.setdefault(cls_name, {})
            st[f"{kind}_windows"] = st.get(f"{kind}_windows", 0) + 1
            if breach:
                st[f"{kind}_breach_windows"] = \
                    st.get(f"{kind}_breach_windows", 0) + 1
            st[f"{kind}_last_p99_s"] = round(p99, 6)
            st[f"{kind}_ok"] = (not breach) if slo is not None else None
            rows.append((cls_name, kind, p99, slo, breach))
        return rows

    def _tick(self) -> None:
        hb = self._windowed_p99s()
        class_rows = self._fold_classes()
        breaches = [(m, p99, self.slo_s) for m, p99 in hb
                    if p99 > self.slo_s]
        breaches += [(f"class_{kind}_seconds|class={cls_name}", p99,
                      slo)
                     for cls_name, kind, p99, slo, breach in class_rows
                     if breach]
        # the brownout's clock: one pressure bit per tick — any
        # windowed breach, heartbeat or class, counts as pressure
        if getattr(self.master, "brownout", None) is not None:
            self.master.brownout_tick(bool(breaches))
        self._record_window(hb, class_rows)
        if not breaches:
            return
        now = time.monotonic()
        if self._last_write_mono is not None \
                and now - self._last_write_mono < self.cooldown_s:
            if self._registry is not None:
                self._registry.incr("incidents_suppressed")
            return
        self._last_write_mono = now
        self.write_incident(breaches)

    def _record_window(self, hb: "list[tuple[str, float]]",
                       class_rows: "list") -> None:
        brown = getattr(self.master, "brownout", None)
        rec: "dict[str, Any]" = {
            "t_mono": round(time.monotonic(), 3),
            "brownout_level": brown.level if brown is not None else 0,
            "heartbeat": {m: round(p, 6) for m, p in hb},
            "classes": {},
        }
        for cls_name, kind, p99, slo, breach in class_rows:
            c = rec["classes"].setdefault(cls_name, {})
            c[f"{kind}_p99_s"] = round(p99, 6)
            if slo is not None:
                c[f"{kind}_ok"] = not breach
        self._window_history.append(rec)

    def window_history(self) -> "list[dict]":
        """The bounded per-tick record (copy) — per-class windowed
        p99s, verdict bits, and the brownout level at each tick."""
        return list(self._window_history)

    def class_report(self) -> dict:
        """Machine-readable per-class verdicts: cumulative p50/p99 plus
        the online windowed state for both latency kinds, and one
        ``pass`` bit per class — the last data-carrying window must be
        under SLO and breached windows must stay a minority, so a class
        that RECOVERED under brownout passes while one still drowning
        fails. Classes without SLOs report latencies with ``pass``
        True (observed, never judged)."""
        hists = getattr(self.master, "_class_hists", None) or {}
        by_cls: "dict[str, dict]" = {}
        for (kind, cls_name), hist in list(hists.items()):
            by_cls.setdefault(cls_name, {})[kind] = hist
        out: "dict[str, dict]" = {}
        for cls_name in sorted(by_cls):
            slo_assign, slo_complete = self._class_slos(cls_name)
            st = self._class_state.get(cls_name, {})
            row: "dict[str, Any]" = {}
            ok = True
            for kind, slo in (("assign", slo_assign),
                              ("complete", slo_complete)):
                hist = by_cls[cls_name].get(kind)
                snap = hist.snapshot() if hist is not None else {}
                windows = st.get(f"{kind}_windows", 0)
                breach_w = st.get(f"{kind}_breach_windows", 0)
                entry: "dict[str, Any]" = {
                    "count": snap.get("count", 0),
                    "p50_s": snap.get("p50", 0.0),
                    "p99_s": snap.get("p99", 0.0),
                    "slo_ms": int(slo * 1000) if slo is not None
                    else None,
                    "windows": windows,
                    "breach_windows": breach_w,
                    "last_window_p99_s": st.get(f"{kind}_last_p99_s"),
                    "ok": st.get(f"{kind}_ok"),
                }
                if slo is not None and windows:
                    frac = breach_w / windows
                    entry["breach_fraction"] = round(frac, 4)
                    if entry["ok"] is False or frac > 0.5:
                        ok = False
                row[kind] = entry
            row["pass"] = ok
            out[cls_name] = row
        return out

    # ------------------------------------------------------------ bundles

    def bundle(self, breaches: "list[tuple]") -> dict:
        """Assemble the incident document (pure read — the e2e test and
        ``write_incident`` share it). ``breaches`` rows are (metric,
        p99_s) judged against the heartbeat SLO, or (metric, p99_s,
        slo_s) carrying their own — per-class SLOs differ."""
        from tpumr.metrics.locks import lock_table
        m = self.master
        snaps = m.metrics.snapshot()
        jt = snaps.get("jobtracker", {})
        rpc = snaps.get("rpc", {})
        brown = getattr(m, "brownout", None)
        wait_hold = {
            name: val for name, val in jt.items()
            if name.startswith(("jt_lock_wait_seconds|",
                                "jt_lock_hold_seconds|"))}
        phases = {name.split("phase=", 1)[-1]: val
                  for name, val in jt.items()
                  if name.startswith("heartbeat_phase_seconds|")}
        spans = [s.to_dict() for s in m.tracer.pending()[-200:]] \
            if getattr(m, "tracer", None) is not None else []
        return {
            "schema": SCHEMA,
            "ts": time.time(),
            "role": "jobtracker",
            "slo_ms": int(self.slo_s * 1000),
            "reason": [{"metric": b[0], "p99_s": round(b[1], 6),
                        "slo_s": round(b[2] if len(b) > 2
                                       else self.slo_s, 6)}
                       for b in breaches],
            # workload context: WHO was degrading and what the master
            # had already shed when this bundle was cut
            "workload": {
                "scenario": getattr(m, "scenario_name", "") or "",
                "brownout": brown.snapshot() if brown is not None
                else {"level": 0},
                "classes": {
                    cls_name: dict(st)
                    for cls_name, st in self._class_state.items()},
            },
            "folded_stacks": self.sampler.folded(
                max(2 * TICK_S, 5.0)) if self.sampler else "",
            "subsystem_shares": self.sampler.subsystem_shares()
            if self.sampler else {},
            "locks": {"live": lock_table(), "wait_hold": wait_hold},
            "rpc": {k: rpc.get(k) for k in
                    ("rpc_inflight", "rpc_inflight_peak",
                     "rpc_handler_threads") if k in rpc},
            "heartbeat": {
                "seconds": jt.get("heartbeat_seconds", {}),
                "lag": jt.get("heartbeat_lag_seconds", {}),
                "phases": phases,
                "trackers": len(getattr(m, "trackers", ()) or ()),
            },
            "spans": spans,
        }

    def write_incident(self, breaches: "list[tuple]") -> "str | None":
        """Write one bundle; returns its path (None on I/O failure —
        the recorder must outlive a full disk)."""
        doc = self.bundle(breaches)
        try:
            os.makedirs(self.incident_dir, exist_ok=True)
            name = f"incident-{int(doc['ts'] * 1000)}.json"
            path = os.path.join(self.incident_dir, name)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, default=str)
            os.replace(tmp, path)
        except OSError:
            return None
        if self._registry is not None:
            self._registry.incr("incidents_written")
        return path

    # ------------------------------------------------------------ listing

    def list_incidents(self) -> "list[dict]":
        """Newest-first {name, bytes, reason…} rows for /incidents."""
        try:
            names = sorted(
                (n for n in os.listdir(self.incident_dir)
                 if n.startswith("incident-") and n.endswith(".json")),
                reverse=True)
        except OSError:
            return []
        rows = []
        for n in names:
            path = os.path.join(self.incident_dir, n)
            row: "dict[str, Any]" = {"name": n}
            try:
                row["bytes"] = os.path.getsize(path)
                with open(path) as f:
                    doc = json.load(f)
                row["ts"] = doc.get("ts")
                row["reason"] = doc.get("reason", [])
            except (OSError, ValueError):
                row["reason"] = [{"metric": "(unreadable)"}]
            rows.append(row)
        return rows

    def read_incident(self, name: str) -> dict:
        """One bundle by basename — path-traversal-proof (the name must
        be exactly a listing entry)."""
        base = os.path.basename(name)
        if not (base.startswith("incident-") and base.endswith(".json")):
            raise ValueError(f"not an incident bundle name: {name!r}")
        with open(os.path.join(self.incident_dir, base)) as f:
            return json.load(f)


class NNFlightRecorder(FlightRecorder):
    """The NameNode's SLO watchdog — same tick/cooldown/bundle machinery
    as the master's recorder, but the watched distributions are the
    per-RPC-op latencies (``nn_op_seconds{op=}``) judged against
    ``tpumr.nn.incident.slo.ms``. A breach bundle carries the namespace
    lock's live holder/waiter row and wait/hold distributions plus every
    op's cumulative latency — the "which op convoyed the namespace lock"
    postmortem, cut at the breach."""

    @classmethod
    def from_conf(cls, conf: Any, namenode: Any,
                  sampler: Any) -> "NNFlightRecorder | None":
        """None unless ``tpumr.nn.incident.slo.ms`` > 0 (off by
        default). The incident dir falls back to the name dir, which
        always exists."""
        from tpumr.core import confkeys
        slo_ms = confkeys.get_int(conf, "tpumr.nn.incident.slo.ms")
        if slo_ms <= 0:
            return None
        d = conf.get("tpumr.prof.incident.dir") or namenode.ns.name_dir
        return cls(
            namenode, sampler, slo_ms=slo_ms,
            cooldown_ms=confkeys.get_int(
                conf, "tpumr.prof.incident.cooldown.ms"),
            incident_dir=os.path.join(str(d), "incidents"),
            conf=conf)

    def _windowed_p99s(self) -> "list[tuple[str, float]]":
        out = []
        for op, hist in list(getattr(self.master,
                                     "_op_hists", {}).items()):
            metric = f"nn_op_seconds|op={op}"
            cur = hist.typed()
            delta = typed_delta(cur, self._prev.get(metric))
            self._prev[metric] = cur
            if delta and delta.get("count"):
                out.append((metric, typed_p99(delta)))
        return out

    def bundle(self, breaches: "list[tuple]") -> dict:
        from tpumr.metrics.histogram import Histogram
        from tpumr.metrics.locks import lock_table
        nn = self.master
        snaps = nn.metrics.snapshot()
        reg = snaps.get("namenode", {})
        rpc = snaps.get("rpc", {})
        wait_hold = {
            name: val for name, val in reg.items()
            if name.startswith(("nn_lock_wait_seconds|",
                                "nn_lock_hold_seconds|"))}
        ops = {name.split("op=", 1)[-1]: val
               for name, val in reg.items()
               if name.startswith("nn_op_seconds|")}
        # one all-ops distribution (the master bundle's "seconds"
        # slot); the per-op breakdown rides in "phases", mirroring the
        # heartbeat-phase layout so bundle consumers read both roles
        # the same way
        merged = Histogram("nn_op_seconds")
        for h in list(getattr(nn, "_op_hists", {}).values()):
            merged.merge_typed(h.typed())
        return {
            "schema": SCHEMA,
            "ts": time.time(),
            "role": "namenode",
            "slo_ms": int(self.slo_s * 1000),
            "reason": [{"metric": b[0], "p99_s": round(b[1], 6),
                        "slo_s": round(b[2] if len(b) > 2
                                       else self.slo_s, 6)}
                       for b in breaches],
            "workload": {"scenario": "", "brownout": {"level": 0},
                         "classes": {}},
            "folded_stacks": self.sampler.folded(
                max(2 * TICK_S, 5.0)) if self.sampler else "",
            "subsystem_shares": self.sampler.subsystem_shares()
            if self.sampler else {},
            "locks": {"live": lock_table(), "wait_hold": wait_hold},
            "rpc": {k: rpc.get(k) for k in
                    ("rpc_inflight", "rpc_inflight_peak",
                     "rpc_handler_threads") if k in rpc},
            "heartbeat": {"seconds": merged.snapshot(), "phases": ops,
                          "datanodes": len(nn.ns.datanodes)},
            "spans": [],
        }


def validate_incident(doc: Any) -> "list[str]":
    """Schema check for one incident bundle — same stance as the trace
    module's ``validate_chrome_trace``: an empty list means the bundle
    holds everything a postmortem consumer may rely on."""
    errs: "list[str]" = []
    if not isinstance(doc, dict):
        return ["bundle is not an object"]
    if doc.get("schema") != SCHEMA:
        errs.append(f"schema is {doc.get('schema')!r}, want {SCHEMA!r}")
    if not isinstance(doc.get("ts"), (int, float)):
        errs.append("ts missing or non-numeric")
    reason = doc.get("reason")
    if not isinstance(reason, list) or not reason:
        errs.append("reason missing or empty")
    else:
        for i, r in enumerate(reason):
            if not isinstance(r, dict) or "metric" not in r \
                    or not isinstance(r.get("p99_s"), (int, float)):
                errs.append(f"reason[{i}] lacks metric/p99_s")
    if not isinstance(doc.get("slo_ms"), int):
        errs.append("slo_ms missing")
    if not isinstance(doc.get("folded_stacks"), str):
        errs.append("folded_stacks missing (must be a string)")
    locks = doc.get("locks")
    if not isinstance(locks, dict) or not isinstance(
            locks.get("live"), list) \
            or not isinstance(locks.get("wait_hold"), dict):
        errs.append("locks.live / locks.wait_hold missing")
    if not isinstance(doc.get("rpc"), dict):
        errs.append("rpc snapshot missing")
    hb = doc.get("heartbeat")
    if not isinstance(hb, dict) or "seconds" not in hb \
            or "phases" not in hb:
        errs.append("heartbeat snapshot missing seconds/phases")
    if not isinstance(doc.get("spans"), list):
        errs.append("spans missing (must be a list)")
    wl = doc.get("workload")
    if not isinstance(wl, dict) \
            or not isinstance(wl.get("scenario"), str) \
            or not isinstance(wl.get("brownout"), dict) \
            or not isinstance(wl.get("classes"), dict):
        errs.append("workload context missing "
                    "(scenario/brownout/classes)")
    return errs
