"""DFS observability: namenode op/lock attribution, audit log, the
SpaceSaving hot-block pipeline (DN sketch → heartbeat → NN fold →
/hotblocks), datanode read-path metrics, the uniform prom surfaces on
NN + DN, the NN flight-recorder incident e2e, and the ``simulate -dfs``
row contract."""

import json
import logging
import os
import shutil
import time
import urllib.request

import pytest

from tpumr.dfs.hotblocks import HotBlockTable, SpaceSaving
from tpumr.dfs.mini_cluster import MiniDFSCluster
from tpumr.mapred.jobconf import JobConf
from tpumr.metrics.flightrec import validate_incident
from tpumr.metrics.histogram import Histogram
from tpumr.metrics.locks import RANK_NAMESPACE, lock_table
from tpumr.metrics.prometheus import validate_exposition


def fetch(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def small_conf(block_size=1024, replication=2):
    conf = JobConf()
    conf.set("dfs.block.size", block_size)
    conf.set("dfs.replication", replication)
    return conf


# ------------------------------------------------------------ SpaceSaving


class TestSpaceSaving:
    def test_accuracy_on_skewed_stream(self):
        sk = SpaceSaving(k=8)
        # 1 heavy hitter among uniform noise, N >> k
        for i in range(900):
            sk.offer(f"noise_{i % 40}")
            if i % 3 == 0:
                sk.offer("hot")
        assert sk.total == 1200
        rows = sk.topk(1)
        assert rows[0][0] == "hot"
        # the SpaceSaving bound: count - err <= true <= count
        _, count, err = rows[0]
        assert count - err <= 300 <= count

    def test_bounded_memory(self):
        sk = SpaceSaving(k=8)
        for i in range(1000):
            sk.offer(f"k{i}")
        assert len(sk) == 8
        assert sk.total == 1000

    def test_wire_round_trip_and_merge(self):
        a, b = SpaceSaving(k=4), SpaceSaving(k=4)
        for _ in range(10):
            a.offer("x")
        for _ in range(7):
            b.offer("x")
            b.offer("y")
        b2 = SpaceSaving.from_wire(
            json.loads(json.dumps(b.to_wire())))
        a.merge(b2)
        assert a.estimate("x") == 17
        assert a.estimate("y") == 7
        assert a.total == 24
        assert len(a) <= 4

    def test_merge_stays_bounded(self):
        a = SpaceSaving(k=4)
        for i in range(4):
            a.offer(f"a{i}", by=10)
        b = SpaceSaving(k=4)
        for i in range(4):
            b.offer(f"b{i}", by=20)
        a.merge(b)
        assert len(a) == 4
        # the larger stream's keys win the truncation
        assert all(key.startswith("b") for key, _c, _e in a.topk())


class TestHotBlockTable:
    def test_fold_is_idempotent(self):
        t = HotBlockTable(k=8)
        doc = {"total": 30, "top": [["5", 20, 0], ["9", 10, 0]]}
        t.fold("dn1:1", doc)
        t.fold("dn1:1", doc)   # re-delivered heartbeat
        assert t.total_reads() == 30
        top = t.top(2)
        assert top[0]["block"] == "5" and top[0]["reads"] == 20

    def test_merge_across_datanodes_and_drop(self):
        t = HotBlockTable(k=8)
        t.fold("dn1:1", {"total": 12, "top": [["5", 12, 0]]})
        t.fold("dn2:2", {"total": 9, "top": [["5", 6, 0], ["7", 3, 0]]})
        top = t.top(4)
        assert top[0]["block"] == "5" and top[0]["reads"] == 18
        assert sorted(top[0]["datanodes"]) == ["dn1:1", "dn2:2"]
        t.drop("dn1:1")   # dead datanode's reads stop counting
        assert t.total_reads() == 9
        assert t.top(1)[0]["reads"] == 6
        t.fold("dn2:2", None)   # empty piggyback is a no-op
        assert t.total_reads() == 9


# ------------------------------------------------------------ audit log


class TestAuditLog:
    def _ns(self, tmp_path, **conf_kv):
        from tpumr.dfs.namenode import FSNamesystem
        conf = small_conf()
        conf.set("tpumr.nn.audit.enabled", True)
        for k, v in conf_kv.items():
            conf.set(k, v)
        return FSNamesystem(str(tmp_path / "name"), conf)

    def test_create_delete_rename_lines(self, tmp_path, caplog):
        ns = self._ns(tmp_path)
        with caplog.at_level(logging.INFO, logger="tpumr.nn.audit"):
            ns.create("/a.txt", "cli_1", None, None, True)
            ns.rename("/a.txt", "/b.txt")
            ns.delete("/b.txt")
            ns.mkdirs("/d")
        lines = [r.getMessage() for r in caplog.records
                 if r.name == "tpumr.nn.audit"]
        assert any("cmd=create src=/a.txt" in ln for ln in lines)
        assert any("cmd=rename src=/a.txt dst=/b.txt" in ln
                   for ln in lines)
        assert any("cmd=delete src=/b.txt" in ln for ln in lines)
        assert any("cmd=mkdirs src=/d" in ln for ln in lines)
        # every line carries the caller identity field
        assert all("ugi=" in ln for ln in lines)
        assert ns.audit_emitted == 4 and ns.audit_suppressed == 0

    def test_rate_cap_counts_overflow(self, tmp_path, caplog):
        ns = self._ns(tmp_path, **{"tpumr.nn.audit.rate.limit": 5})
        with caplog.at_level(logging.INFO, logger="tpumr.nn.audit"):
            for i in range(40):
                ns.mkdirs(f"/r{i}")
        lines = [r for r in caplog.records if r.name == "tpumr.nn.audit"]
        # one wall-second window admits at most the cap (the loop can
        # straddle a window boundary, hence <= 2 windows' worth)
        assert len(lines) <= 10
        assert ns.audit_emitted + ns.audit_suppressed == 40
        assert ns.audit_suppressed >= 30

    def test_disabled_by_default(self, tmp_path, caplog):
        from tpumr.dfs.namenode import FSNamesystem
        ns = FSNamesystem(str(tmp_path / "name"), small_conf())
        with caplog.at_level(logging.INFO, logger="tpumr.nn.audit"):
            ns.mkdirs("/quiet")
        assert not [r for r in caplog.records
                    if r.name == "tpumr.nn.audit"]


# ------------------------------------------------------------ live cluster


@pytest.fixture(scope="module")
def obs_cluster():
    conf = small_conf()
    conf.set("tdfs.http.port", 0)
    conf.set("tpumr.dn.http.port", 0)
    with MiniDFSCluster(num_datanodes=2, conf=conf) as c:
        yield c


class TestNamespaceLock:
    def test_rank_and_lock_table(self, obs_cluster):
        rows = {r["name"]: r for r in lock_table()}
        assert "namespace" in rows
        assert rows["namespace"]["rank"] == RANK_NAMESPACE == 25

    def test_wait_hold_series_observe(self, obs_cluster):
        client = obs_cluster.client()
        client.mkdirs("/lockwork")
        reg = obs_cluster.namenode.metrics.snapshot()["namenode"]
        hold = reg["nn_lock_hold_seconds|lock=namespace"]
        assert hold["count"] > 0
        assert "nn_lock_wait_seconds|lock=namespace" in reg


class TestOpAndEditlogMetrics:
    def test_per_op_histograms(self, obs_cluster):
        client = obs_cluster.client()
        with client.create("/ops/f.bin") as f:
            f.write(b"z" * 2048)
        with client.open("/ops/f.bin") as f:
            assert len(f.read()) == 2048
        reg = obs_cluster.namenode.metrics.snapshot()["namenode"]
        for op in ("create", "add_block", "complete",
                   "get_block_locations"):
            assert reg[f"nn_op_seconds|op={op}"]["count"] > 0, op
        # heartbeats arrive on their own clock — poll for the first
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            reg = obs_cluster.namenode.metrics.snapshot()["namenode"]
            if reg.get("nn_op_seconds|op=dn_heartbeat", {}).get("count"):
                break
            time.sleep(0.05)
        assert reg["nn_op_seconds|op=dn_heartbeat"]["count"] > 0

    def test_editlog_hists_bound_to_nn(self, obs_cluster):
        client = obs_cluster.client()
        client.mkdirs("/editwork")
        reg = obs_cluster.namenode.metrics.snapshot()["namenode"]
        assert reg["nn_editlog_append_seconds"]["count"] > 0
        assert reg["nn_editlog_sync_seconds"]["count"] > 0
        assert reg["nn_editlog_batch_bytes"]["mean"] > 0

    def test_bare_namesystem_pays_nothing(self, tmp_path):
        # no NameNode, no registry: the editlog keeps its None hists
        from tpumr.dfs.namenode import FSNamesystem
        ns = FSNamesystem(str(tmp_path / "name"), small_conf())
        ns.mkdirs("/x")
        assert ns.edits._append_hist is None


class TestDatanodeReadPath:
    def test_read_metrics_and_sketch(self, obs_cluster):
        client = obs_cluster.client()
        with client.create("/dn/read.bin") as f:
            f.write(b"q" * 4096)
        for _ in range(3):
            with client.open("/dn/read.bin") as f:
                f.read()
        reads = bytes_ = 0
        for dn in obs_cluster.datanodes:
            reg = dn.metrics.snapshot()["datanode"]
            reads += reg.get("dn_read_seconds", {}).get("count", 0)
            bytes_ += reg.get("dn_read_bytes", {}).get("sum", 0)
            assert "dn_readers" in reg   # concurrent-reader gauge
        assert reads > 0 and bytes_ >= 4096
        assert sum(dn._hot.total for dn in obs_cluster.datanodes) > 0


class TestHotBlocksEndToEnd:
    def test_skewed_reads_rank_hot_block_first(self, obs_cluster):
        client = obs_cluster.client()
        with client.create("/hot/a.bin") as f:
            f.write(b"h" * 512)
        with client.create("/hot/b.bin") as f:
            f.write(b"c" * 512)
        for i in range(24):
            with client.open("/hot/a.bin") as f:
                f.read()
            if i % 8 == 0:
                with client.open("/hot/b.bin") as f:
                    f.read()
        # the sketch rides the NEXT heartbeat into the NN fold
        nn = obs_cluster.namenode
        deadline = time.monotonic() + 10.0
        top = []
        while time.monotonic() < deadline:
            top = nn.ns.get_hot_blocks(4)
            # reads land a bit under the raw 24: the locate response
            # shuffles replicas (the 24 reads split across both DNs'
            # sketches) and the per-heartbeat halflife decay ages them
            if top and top[0].get("path") == "/hot/a.bin" \
                    and top[0]["reads"] >= 16:
                break
            time.sleep(0.1)
        assert top and top[0]["path"] == "/hot/a.bin", top
        assert top[0]["reads"] >= 16
        assert top[0]["datanodes"], "no reporting datanode recorded"
        # the HTTP view serves the same ranking
        _, body = fetch(nn.http_url + "/hotblocks?n=4")
        doc = json.loads(body)
        assert doc["top"][0]["path"] == "/hot/a.bin"
        assert doc["total_reads"] >= doc["top"][0]["reads"]


class TestPromSurfaces:
    def test_namenode_exposition_validates(self, obs_cluster):
        client = obs_cluster.client()
        client.mkdirs("/prom")
        _, body = fetch(obs_cluster.namenode.http_url + "/metrics/prom")
        validate_exposition(body)   # raises on violation
        assert "nn_op_seconds" in body
        assert "nn_lock_wait_seconds" in body

    def test_datanode_exposition_and_status(self, obs_cluster):
        dn = obs_cluster.datanodes[0]
        assert dn.http_url, "datanode http did not start"
        _, body = fetch(dn.http_url + "/metrics/prom")
        validate_exposition(body)
        assert "dn_read" in body or "dn_readers" in body
        _, body = fetch(dn.http_url + "/metrics")
        assert "datanode" in json.loads(body)
        _, body = fetch(dn.http_url + "/hotblocks")
        doc = json.loads(body)
        assert set(doc) == {"total", "top"}


# ------------------------------------------------------------ incident e2e


@pytest.fixture(scope="module")
def incident_cluster(tmp_path_factory):
    """Mini-DFS with the NN flight recorder armed and the nn.op.slow
    seam stalling the first ops past the SLO."""
    inc_root = str(tmp_path_factory.mktemp("nn-incidents"))
    conf = small_conf()
    conf.set("tdfs.http.port", 0)
    conf.set("tpumr.prof.enabled", True)
    conf.set("tpumr.prof.incident.dir", inc_root)
    conf.set("tpumr.nn.incident.slo.ms", 250)
    conf.set("tpumr.prof.incident.cooldown.ms", 600_000)
    conf.set("tpumr.fi.nn.op.slow.probability", 1.0)
    conf.set("tpumr.fi.nn.op.slow.max.failures", 3)
    conf.set("tpumr.fi.nn.op.slow.ms", 400)
    with MiniDFSCluster(num_datanodes=1, conf=conf) as c:
        c.incident_dir = os.path.join(inc_root, "incidents")
        yield c


class TestNNIncidentE2E:
    def test_breach_writes_valid_bundle(self, incident_cluster):
        nn = incident_cluster.namenode
        client = incident_cluster.client()
        client.mkdirs("/breach")   # op traffic through the stalled seam
        deadline = time.monotonic() + 15.0
        rows = []
        while time.monotonic() < deadline:
            _, body = fetch(nn.http_url + "/json/incidents")
            rows = json.loads(body)
            if rows:
                break
            time.sleep(0.25)
        assert rows, "no NN incident within deadline"
        assert rows[0]["reason"][0]["metric"].startswith("nn_op_seconds")
        _, body = fetch(nn.http_url + f"/incident?name={rows[0]['name']}")
        doc = json.loads(body)
        assert validate_incident(doc) == [], validate_incident(doc)
        assert doc["role"] == "namenode"
        assert doc["reason"][0]["p99_s"] > doc["slo_ms"] / 1000.0
        # the lock table rides along, namespace lock included
        assert any(r.get("name") == "namespace"
                   for r in doc["locks"]["live"])
        # the merged-op heartbeat section carries real counts
        assert doc["heartbeat"]["seconds"]["count"] > 0
        out = os.environ.get("TPUMR_INCIDENT_E2E_OUT")
        if out:
            os.makedirs(out, exist_ok=True)
            shutil.copy(os.path.join(incident_cluster.incident_dir,
                                     rows[0]["name"]),
                        os.path.join(out, "nn-" + rows[0]["name"]))

    def test_recorder_off_by_default(self, obs_cluster):
        assert obs_cluster.namenode.flightrec is None


# ------------------------------------------------------------ bench contract


REQUIRED_ROW_KEYS = {
    "clients", "wall_s", "ops", "errors", "completed",
    "nn_op_count", "nn_op_p50_s", "nn_op_p99_s", "nn_op_p99_by_op",
    "lock_wait_p99_s", "lock_hold_p99_s", "lock_wait_share",
    "lock_wait_p99_by_lock", "editlog_sync_p99_s",
    "editlog_group_ops_mean", "read_mb_s", "read_rtt_p50_s",
    "read_rtt_p99_s", "meta_rtt_p99_s", "lag_p99_s", "dn_read_p99_s",
    "hot_total_reads", "hot_top", "hot_top1_share",
    "hot_top1_replicas", "hot_top1_boost",
}


class TestBenchRowContract:
    def test_run_dfs_step_row(self, tmp_path):
        from tpumr.scale.simdfs import run_dfs_step
        prom = str(tmp_path / "nn.prom")
        row = run_dfs_step(2, interval_s=0.05, measure_s=1.5,
                           num_datanodes=2, n_files=2,
                           file_bytes=8192, prom_out=prom)
        assert REQUIRED_ROW_KEYS <= set(row)
        assert row["ops"] > 0
        assert row["nn_op_count"] > 0
        assert json.loads(json.dumps(row)) == row   # JSON-safe
        validate_exposition(open(prom).read())

    def test_merged_op_hist_matches_families(self, tmp_path):
        # the merge a DFS rung's row relies on: merging typed per-op hists
        # reproduces the union's count
        a = Histogram("nn_op_seconds")
        b = Histogram("nn_op_seconds")
        for _ in range(10):
            a.observe(0.001)
            b.observe(0.1)
        merged = Histogram("nn_op_seconds")
        merged.merge_typed(a.typed())
        merged.merge_typed(b.typed())
        snap = merged.snapshot()
        assert snap["count"] == 20
        assert snap["p99"] >= 0.05
