"""No CPU stand-in for a TPU slot, one process per chip, and the smoke
script's rehearsal.

- the accelerator-device helper (parallel/jaxruntime.py): CPU devices
  stand in only when the CPU backend was asked for explicitly; otherwise a
  tracker with TPU slots refuses to start, and a slot never shares a device
  by modulo;
- nothing but the tracker touches a JAX backend: clients, the master and
  process-isolated task children;
- ``chip_smoke.py --size tiny`` on the CPU backend runs every phase and
  still ends ``"ok": false``, because no chip is there.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def platforms():
    """Set ``jax_platforms`` for one test (the value only steers the
    helper: the backends of this process are already up)."""
    import jax
    prev = jax.config.jax_platforms

    def set_to(value):
        jax.config.update("jax_platforms", value)

    yield set_to
    jax.config.update("jax_platforms", prev)


# ------------------------------------------------ the accelerator helper


@pytest.mark.parametrize("value,requested", [
    ("cpu", True), ("cpu,tpu", True), (" CPU ", True),
    ("tpu,cpu", False), ("tpu", False), ("", False), (None, False)])
def test_cpu_backend_requested_reads_the_leading_platform(
        platforms, value, requested):
    from tpumr.parallel.jaxruntime import cpu_backend_requested
    platforms(value)
    assert cpu_backend_requested() is requested


def test_explicit_cpu_request_lets_cpu_devices_stand_in():
    import jax

    from tpumr.parallel import jaxruntime
    devices = jaxruntime.accelerator_devices()
    assert devices == list(jax.local_devices())
    assert {d.platform for d in devices} == {"cpu"}
    assert jaxruntime.accelerator_device(-1) is devices[0]
    assert jaxruntime.accelerator_device(3) is devices[3]


def test_slot_past_the_last_device_is_an_error_not_a_modulo():
    from tpumr.mapred.tpu_runner import _select_device
    from tpumr.parallel import jaxruntime
    n = len(jaxruntime.accelerator_devices())
    with pytest.raises(RuntimeError, match=f"TPU slot {n} has no device"):
        _select_device(n)


def test_no_tpu_device_and_no_cpu_request_raises(platforms):
    """JAX fell back to the CPU backend without being asked to: that is
    a chip that failed to initialise, not a place to run TPU tasks."""
    from tpumr.mapred.node_health import default_tpu_probe
    from tpumr.parallel import jaxruntime
    platforms(None)
    with pytest.raises(RuntimeError, match="no TPU device"):
        jaxruntime.accelerator_devices()
    with pytest.raises(RuntimeError, match="no TPU device"):
        default_tpu_probe(0)


def test_split_cache_and_devcache_key_by_device():
    """Four TPU slots are four devices: the HBM split cache is one cache
    per device and the side-input cache holds one image per device."""
    import jax

    from tpumr.mapred.tpu_runner import _select_device, split_cache
    from tpumr.ops import devcache
    devcache.clear_device_cache()
    host = np.arange(16, dtype=np.float32)
    devices = [_select_device(i) for i in range(4)]
    assert len(set(devices)) == 4
    images = []
    for dev in devices:
        with jax.default_device(dev):
            images.append(devcache.device_cached("smoke-test:side", host))
            assert devcache.device_cached("smoke-test:side", host) \
                is images[-1]
    assert [a.devices() for a in images] == [{d} for d in devices]
    assert len({id(split_cache(d, 1 << 20)) for d in devices}) == 4
    devcache.clear_device_cache("smoke-test:")


# ------------------------------------------------------- tracker start()


@pytest.fixture
def master():
    from tpumr.mapred.jobconf import JobConf
    from tpumr.mapred.jobtracker import JobMaster
    jm = JobMaster(JobConf()).start()
    yield jm
    jm.stop()


def _tracker(master, tpu_slots, **kw):
    from tpumr.mapred.jobconf import JobConf
    from tpumr.mapred.tasktracker import NodeRunner
    conf = JobConf()
    conf.set("mapred.tasktracker.map.tpu.tasks.maximum", tpu_slots)
    host, port = master.address
    return NodeRunner(host, port, conf, name=f"tracker_t{tpu_slots}", **kw)


def test_tracker_names_the_devices_behind_its_tpu_slots(master):
    import jax
    tracker = _tracker(master, 2).start()
    try:
        assert tracker.tpu_devices == {
            "platform": "cpu", "kind": jax.local_devices()[0].device_kind,
            "count": len(jax.local_devices()), "slot_device_ids": [0, 1]}
    finally:
        tracker.stop()


def test_tracker_with_tpu_slots_and_no_tpu_device_refuses_to_start(
        master, platforms):
    platforms(None)
    tracker = _tracker(master, 1)
    with pytest.raises(RuntimeError, match="no TPU device"):
        tracker.start()
    assert not tracker._hb_thread.is_alive()   # refused before anything ran


def test_more_tpu_slots_than_devices_refuses_to_start(master):
    import jax
    n = len(jax.local_devices())
    tracker = _tracker(master, n + 1)
    with pytest.raises(RuntimeError,
                       match=f"{n + 1} TPU slot device.*has {n} accel"):
        tracker.start()
    assert not tracker._hb_thread.is_alive()


def test_tracker_without_tpu_slots_never_asks_for_devices(
        master, platforms, monkeypatch):
    from tpumr.parallel import jaxruntime
    platforms(None)
    monkeypatch.setattr(jaxruntime, "accelerator_devices",
                        lambda: pytest.fail("asked for devices"))
    tracker = _tracker(master, 0).start()
    try:
        assert tracker.tpu_devices is None
    finally:
        tracker.stop()


# ------------------------------------------------- one process per chip


def test_task_child_gets_the_cpu_backend_before_anything_imports_jax(
        monkeypatch, tmp_path):
    """Process-isolated CPU attempts must never open the tracker's chip:
    the child pins JAX to the CPU before it runs anything."""
    from tpumr.mapred import child
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(FileNotFoundError):
        child.main([str(tmp_path / "no-such-task-file")])
    assert os.environ["JAX_PLATFORMS"] == "cpu"


def test_clients_and_master_never_initialise_a_jax_backend(tmp_path):
    """What a cluster client runs beside job submission — the kmeans
    driver's seeding and cache clearing, terasort's sampling and
    partition file — and a JobMaster's whole life leave JAX's backends
    uninitialised, so they cannot hold (or fail on) the tracker's chip."""
    prog = r"""
import sys
sys.path.insert(0, %(repo)r)
import numpy as np
from tpumr.cli import main as cli
from tpumr.examples.basic import load_npy_rows, save_npy
from tpumr.examples.terasort import make_terasort_conf
from tpumr.fs import get_filesystem
from tpumr.mapred.jobconf import JobConf
from tpumr.mapred.jobtracker import JobMaster
from tpumr.ops.kmeans import clear_centroid_cache

work = %(work)r
pts = "file://" + work + "/points.npy"
save_npy(get_filesystem(pts), pts, np.ones((64, 16), np.float32))
assert load_npy_rows(get_filesystem(pts), pts, 16).shape == (16, 16)
clear_centroid_cache()
assert cli(["examples", "teragen", "200", "file://" + work + "/gen",
            "-m", "2"]) == 0           # LocalJobRunner, CPU mapper
make_terasort_conf("file://" + work + "/gen", "file://" + work + "/out", 4)
jm = JobMaster(JobConf()).start()
jm.stop()
import jax
from jax._src import xla_bridge
assert not xla_bridge.backends_are_initialized(), "a JAX backend is up"
print("CLEAN")
""" % {"repo": REPO, "work": str(tmp_path)}
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=120, cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("CLEAN")


# ------------------------------------------------------ the smoke script


def _run_smoke(args, cwd=REPO, script=None, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)          # one CPU device, like one chip
    out = subprocess.run(
        [sys.executable, script or os.path.join(REPO, "chip_smoke.py")]
        + args, cwd=cwd, env=env, capture_output=True, text=True,
        timeout=timeout)
    lines = [json.loads(line) for line in out.stdout.splitlines()]
    return out, lines


def test_smoke_rehearsal_runs_every_phase_and_still_fails_without_a_chip():
    out, lines = _run_smoke(["--size", "tiny"])
    assert out.returncode == 1, out.stderr[-3000:]
    last = lines[-1]
    assert last["ok"] is False
    assert last["error"].startswith("no chip: tracker devices are cpu")
    assert last["device"]["platform"] == "cpu"
    phases = {line["phase"]: line for line in lines[:-1]}
    assert list(phases) == ["setup", "cluster_up", "kmeans", "terasort",
                            "pallas", "cluster_down", "done"]
    assert phases["setup"]["native_kits"]["tlz"] is True
    km = phases["kmeans"]
    assert km["maps"] == 8 and len(km["jobs"]) == km["iterations"] == 2
    for job in km["jobs"]:
        assert job["TPU_MAP_TASKS"] > 0 and job["TPU_DEMOTIONS"] == 0
        assert job["TPU_MAP_TASKS"] + job["CPU_MAP_TASKS"] == 8
    assert km["jobs"][0]["TPU_DEVICE_BYTES_STAGED"] > 0
    # XLA:CPU keeps f32: the job agrees with the f32 reference far more
    # closely than with the emulated bf16 pass
    assert km["centroid_max_abs_err_vs_f32_reference"] < 1e-4 \
        < km["centroid_max_abs_err_vs_bf16_emulation"]
    ts = phases["terasort"]
    assert ts["multiset_equal"] and sum(ts["part_rows"]) == ts["rows"]
    assert ts["TPU_SHUFFLE_RECORDS"] == ts["rows"]
    assert ts["SHUFFLE_HOST_FALLBACKS"] == 0
    assert phases["pallas"]["pallas"]["TPU_MAP_TASKS"] > 0
    assert phases["pallas"]["centroid_max_abs_err_pallas_vs_xla"] < 1e-4
    assert phases["cluster_down"]["other_device_holders"] == {}


def test_smoke_at_full_size_fails_at_once_without_a_chip():
    """The driver's first run, in a sandbox with no accelerator: no
    phase runs on a CPU stand-in, the script fails, no result."""
    out, lines = _run_smoke([])
    assert out.returncode == 1, out.stderr[-3000:]
    assert lines[-1]["ok"] is False
    assert "not a chip" in lines[-1]["error"]
    assert [line["phase"] for line in lines[:-1]] == [
        "setup", "cluster_up", "done"]


def test_smoke_script_alone_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo there is no program to run: non-zero exit, no result."""
    alone = shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out, lines = _run_smoke([], cwd=str(tmp_path), script=alone, timeout=60)
    assert out.returncode == 2
    assert lines == []
