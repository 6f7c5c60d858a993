"""The readers of the program's spans (bench/reducers_spans.py), on
hand-built span lists: the arithmetic, and None where the spans are not
there (as on a program that lacks them)."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import reducers_spans as rs  # noqa: E402
from bench import run  # noqa: E402

SHUFFLE = ("shuffle_locate_s", "shuffle_fetch_s", "shuffle_assemble_s",
           "shuffle_pack_s", "shuffle_device_call_s", "shuffle_gather_s",
           "shuffle_write_s", "shuffle_self_s")
OTHERS = ("report_lag_s", "tpu_assign_gap_s", "job_tail_s",
          "tpu_slot_busy_share", "cpu_slot_busy_share", "execute_s_per_map",
          "tpu_task_overhead_s")

_ids = iter(range(1, 1 << 30))


def span(name, start, end, parent=None, job="job_1", backend="", **attrs):
    return {"name": name, "span_id": f"s{next(_ids)}",
            "parent_span_id": parent["span_id"] if parent else "",
            "backend": backend, "start": float(start), "end": float(end),
            "attributes": attrs, "job_id": job}


def gang_reduce(t0, job, locate=(0.5, 2.5), gap=0.25):
    """A ``dshuffle`` span from ``t0`` with every phase under it and
    ``gap`` seconds that no child covers."""
    at = t0 + gap
    kids = []

    def phase(name, seconds, **attrs):
        nonlocal at
        kids.append((name, at, at + seconds, attrs))
        at += seconds

    for m, wait in enumerate(locate):
        phase("dshuffle:locate", wait, map_index=m)
        phase("dshuffle:fetch", 1.0, map_index=m, bytes=100)
    phase("dshuffle:assemble", 2.0, rows=10, bytes=1000)
    phase("dshuffle:pack", 1.5, n_pad=16, bytes_in=192)
    phase("dshuffle:device", 0.5, devices=1, bytes_in=192, bytes_out=64)
    phase("dshuffle:gather", 3.0, rows=10, bytes=1000)
    for r in range(4):
        phase("dshuffle:write", 2.0, range=r, rows=2, bytes=250)
    top = span("dshuffle", t0, at, job=job, rows=10, n_dev=1)
    return [top] + [span(n, a, b, parent=top, job=job, **attrs)
                    for n, a, b, attrs in kids]


def test_the_eight_shuffle_numbers_add_up_to_the_dshuffle_span():
    spans = gang_reduce(100.0, "job_1")
    obs = {"spans": spans}
    got = {name: getattr(rs, name)(obs) for name in SHUFFLE}
    assert got == pytest.approx({
        "shuffle_locate_s": 3.0, "shuffle_fetch_s": 2.0,
        "shuffle_assemble_s": 2.0, "shuffle_pack_s": 1.5,
        "shuffle_device_call_s": 0.5, "shuffle_gather_s": 3.0,
        "shuffle_write_s": 8.0, "shuffle_self_s": 0.25})
    top = spans[0]
    assert sum(got.values()) == pytest.approx(top["end"] - top["start"])


def test_shuffle_numbers_average_over_the_windows_gang_reduces():
    spans = gang_reduce(100.0, "job_1", locate=(0.5, 2.5)) \
        + gang_reduce(200.0, "job_2", locate=(0.0, 1.0), gap=0.75)
    obs = {"spans": spans}
    assert rs.shuffle_locate_s(obs) == pytest.approx((3.0 + 1.0) / 2)
    assert rs.shuffle_self_s(obs) == pytest.approx((0.25 + 0.75) / 2)
    assert rs.shuffle_write_s(obs) == pytest.approx(8.0)
    # an open span (no end yet) is not read
    spans.append(dict(spans[0], span_id="open", end=0.0))
    assert rs.shuffle_self_s(obs) == pytest.approx(0.5)


def test_self_time_counts_overlapping_children_once_and_clips_them():
    top = span("dshuffle", 0.0, 10.0)
    kids = [span("dshuffle:fetch", 1.0, 4.0, parent=top),
            span("dshuffle:fetch", 3.0, 6.0, parent=top),      # overlaps
            span("dshuffle:write", 9.0, 12.0, parent=top)]     # runs over
    assert rs.shuffle_self_s({"spans": [top] + kids}) == pytest.approx(
        10.0 - (5.0 + 1.0))
    # a host fallback's sort is a child like any other: not self time
    kids.append(span("dshuffle:host_sort", 6.0, 9.0, parent=top))
    assert rs.shuffle_self_s({"spans": [top] + kids}) == pytest.approx(1.0)


def a_round(t0, job, tpu_maps=((0.0, 0.4), (1.0, 1.3), (2.0, 2.5)),
            cpu_maps=((0.0, 2.5), (0.0, 3.0), (0.1, 2.9), (3.1, 6.0)),
            lag=0.6, length=10.0, slots=3):
    """One job: ``job`` span, TPU and CPU maps as ``task:launch`` with
    ``tpu:stage`` + ``tpu:execute`` under ``task:run``, and the master's
    ``task:done`` ``lag`` seconds after each launch ends."""
    root = span("job", t0, t0 + length, job=job)
    out = [root]
    for backend, maps, n_slots in (("tpu", tpu_maps, 1),
                                   ("cpu", cpu_maps, slots)):
        for i, (a, b) in enumerate(maps):
            aid = f"attempt_{job}_{backend}_{i}"
            sched = span("schedule", t0 + a, t0 + a, parent=root, job=job,
                         backend=backend, attempt_id=aid)
            attrs = {"device_id": 0} if backend == "tpu" else {}
            launch = span("task:launch", t0 + a, t0 + b, parent=sched,
                          job=job, backend=backend, attempt_id=aid,
                          is_map=True, slots=n_slots, **attrs)
            run_ = span("task:run", t0 + a + 0.01, t0 + b - 0.01,
                        parent=launch, job=job, backend=backend)
            out += [sched, launch, run_,
                    span("task:done", t0 + b + lag, t0 + b + lag,
                         parent=sched, job=job, backend=backend,
                         attempt_id=aid, is_map=True, state="SUCCEEDED")]
            if backend == "tpu":
                out += [span("tpu:stage", t0 + a + 0.02, t0 + a + 0.06,
                             parent=run_, job=job, backend="tpu"),
                        span("tpu:execute", t0 + a + 0.06, t0 + a + 0.16,
                             parent=run_, job=job, backend="tpu")]
    # the reduce: not a map, so no reader of maps may count it
    out.append(span("task:launch", t0 + 0.2, t0 + 9.5, parent=root, job=job,
                    backend="cpu", attempt_id=f"attempt_{job}_r", slots=2,
                    is_map=False))
    out.append(span("task:done", t0 + 9.9, t0 + 9.9, parent=root, job=job,
                    backend="cpu", attempt_id=f"attempt_{job}_r",
                    is_map=False))
    return out


def test_report_lag_joins_done_and_launch_on_the_attempt():
    obs = {"spans": a_round(0.0, "job_1", lag=0.6)
           + a_round(50.0, "job_2", lag=0.2)}
    assert rs.report_lag_s(obs) == pytest.approx(0.4)
    # a done whose launch was never flushed is left out, not guessed
    obs["spans"] = [s for s in obs["spans"] if not (
        s["name"] == "task:launch" and s["job_id"] == "job_2")]
    assert rs.report_lag_s(obs) == pytest.approx(0.6)


def test_the_assign_gap_does_not_bridge_two_jobs():
    obs = {"spans": a_round(0.0, "job_1") + a_round(50.0, "job_2")}
    # inside a job: 0.4 -> 1.0 and 1.3 -> 2.0; the 47.5 s from job_1's
    # last TPU map to job_2's first is not a gap of the slot
    assert rs.tpu_assign_gap_s(obs) == pytest.approx((0.6 + 0.7) / 2)
    one = {"spans": a_round(0.0, "job_1", tpu_maps=((0.0, 0.4),))}
    assert rs.tpu_assign_gap_s(one) is None


def test_the_assign_gap_is_per_device():
    spans = a_round(0.0, "job_1", tpu_maps=((0.0, 1.0), (0.5, 1.5),
                                            (2.0, 3.0), (2.5, 3.5)))
    launches = [s for s in spans if s["name"] == "task:launch"
                and s["backend"] == "tpu"]
    for i, s in enumerate(launches):
        s["attributes"]["device_id"] = i % 2
    assert rs.tpu_assign_gap_s({"spans": spans}) == pytest.approx(1.0)


def test_job_tail_is_the_job_end_minus_the_last_maps_done():
    obs = {"spans": a_round(0.0, "job_1", lag=0.5)}
    assert rs.job_tail_s(obs) == pytest.approx(10.0 - 6.5)


def test_busy_share_divides_by_the_slots_of_the_pool():
    obs = {"spans": a_round(0.0, "job_1", slots=3)}
    assert rs.tpu_slot_busy_share(obs) == pytest.approx(
        100.0 * (0.4 + 0.3 + 0.5) / (1 * 10.0))
    assert rs.cpu_slot_busy_share(obs) == pytest.approx(
        100.0 * (2.5 + 3.0 + 2.8 + 2.9) / (3 * 10.0))
    two = {"spans": obs["spans"] + a_round(50.0, "job_2", length=20.0)}
    assert rs.cpu_slot_busy_share(two) == pytest.approx(
        100.0 * 2 * 11.2 / (3 * 30.0))


def test_stage_execute_and_overhead_add_up_to_the_tpu_launch():
    from bench import reducers
    obs = {"spans": a_round(0.0, "job_1")}
    stage, execute, over = (reducers.stage_s_per_map(obs),
                            rs.execute_s_per_map(obs),
                            rs.tpu_task_overhead_s(obs))
    assert stage == pytest.approx(0.04) and execute == pytest.approx(0.10)
    assert stage + execute + over == pytest.approx((0.4 + 0.3 + 0.5) / 3)


@pytest.mark.parametrize("name", SHUFFLE + OTHERS)
def test_a_reader_that_finds_no_span_returns_none(name):
    """What the parent's program gives: its own spans (``job``,
    ``task:launch`` without ``slots``) or none at all."""
    reader = getattr(rs, name)
    assert reader({"spans": None}) is None
    assert reader({"spans": []}) is None
    old = [span("job", 0.0, 9.0),
           span("task:launch", 1.0, 2.0, backend="cpu", is_map=True,
                attempt_id="a"),
           span("shuffle:fetch", 1.0, 8.0)]
    assert reader({"spans": old}) is None


def test_every_span_metric_of_the_benchmark_names_a_reader_here():
    bm = run.load_benchmark()
    mine = {m["name"]: run._load_json("layer_metrics", m["name"] + ".json")
            for m in bm["per_layer"]
            if (m["name"].startswith(("shuffle.", "master.", "tracker."))
                or m["name"] == "devpath.execute_s_per_map")
            and m["name"] not in ("shuffle.gang_reduce_s",
                                  "master.tpu_map_share",
                                  "tracker.tpu_map_mean_s",
                                  "tracker.cpu_map_mean_s")}
    assert sorted(spec["reducer"] for spec in mine.values()) == sorted(
        SHUFFLE + OTHERS)
    for name, spec in mine.items():
        assert run.find_reducer(spec["reducer"]) is getattr(
            rs, spec["reducer"]), name
        entry = next(m for m in bm["per_layer"] if m["name"] == name)
        assert entry["source"] == "program_span"
        assert entry["moves"] == "rows_per_s"
