"""``tpumr`` — the framework's command-line entry point.

≈ the reference's ``bin/hadoop`` dispatch script (bin/hadoop:66-95): one
command name selects a daemon, a client tool, or a user program. Generic
options (≈ GenericOptionsParser, src/core/.../util/GenericOptionsParser.java)
come before the subcommand's own arguments: ``-D k=v``, ``-fs <uri>``,
``-jt <host:port|local>``.

Daemon commands run in the foreground until SIGINT (process supervision is
the operator's problem, as with the reference's hadoop-daemon.sh).
"""

from __future__ import annotations

import json
import signal
import os
import sys
import threading
import time
from typing import Any

USAGE = """\
Usage: tpumr [generic options] COMMAND [args]
Generic options: -D k=v  -conf FILE  -fs <default-fs-uri>  -jt <host:port|local>
Site config: $TPUMR_CONF_DIR/tpumr-site.{toml,json} loads automatically
(precedence: defaults < site file < -conf files < -D/-fs/-jt)

Daemons:
  namenode -dir DIR [-host H] [-port P]      run the tdfs NameNode
  datanode -nn HOST:PORT -dir DIR            run a tdfs DataNode
  secondarynamenode -nn HOST:PORT -dir DIR   periodic checkpoint daemon
  jobtracker [-host H] [-port P]             run the JobMaster
  tasktracker -jt HOST:PORT                  run a NodeRunner (worker)
  historyserver -dir DIR [-port P]           serve completed-job history

Clients:
  fs -CMD ...          filesystem shell (tpumr fs -help for commands)
  job ...              job control: -list | -status ID | -kill ID | -counters ID
                       offline: -history ID [DIR] | -diagnose ID [DIR] (vaidya)
                       tracing: trace ID [-out FILE] [-dir DIR] (Chrome trace
                       + critical path; needs tpumr.trace.enabled at submit)
  balancer -nn HOST:PORT                     rebalance tdfs blocks
  fsck [PATH]          tdfs health report (missing/under-replicated blocks)
  dfsadmin ...         quotas, decommissioning, safemode, cluster report
  pipes ...            submit an external-binary (pipes) job
  streaming ...        submit a script (streaming) job
  examples NAME ...    run an example program (examples -h lists them)
  distcp SRC DST       distributed copy (any scheme to any scheme)
  archive SRC DEST.tharch | archive -ls ARCH   pack/list archives
  rumen HISTORY_DIR    extract job traces from history
  failmon -collect|-merge   node failure monitoring (collect/upload/merge)
  gridmix [--scale S]  synthetic mixed-workload benchmark
  simulate [-trackers N] [-jobs J] [-maps M] [-reduces R] [-interval MS]
                       [-task-ms MEAN] [-timeout S] [-ff-rate P]
                       control-plane scale harness: a simulated tracker
                       fleet driving real heartbeat/RPC paths against
                       the -jt master (or a self-hosted one)
  keys SUBCMD          credentials: user-key USER | token [-nn] [-renewer R]
                       [-out FILE] | renew FILE | cancel FILE
  fetchdt TOKEN_FILE   fetch a NameNode delegation token (= keys token -nn)
  pipeline ...         DAG-of-jobs pipelines: submit GRAPH.json [-wait] |
                       status ID | -list | -kill ID | trace ID [-out FILE]
  queue ...            queue info: -list | -info Q [-showJobs] | -showacls
  mradmin -refreshQueues|-refreshNodes   live-reload queue ACLs / host lists
  daemonlog ...        -getlevel H:P LOGGER | -setlevel H:P LOGGER LEVEL
  prof HOST:PORT [-seconds N] [-out FILE] [-flame]
                       pull folded stacks (or -flame SVG) off a live
                       daemon's continuous sampler (tpumr.prof.enabled)
  rcc FILE.jr ...      compile Record I/O DDL to record classes (= bin/rcc)
  tdfsproxy -port P    read-only HTTP(S) storage gateway (= hdfsproxy)
  lint [--json FILE] [--rules R,..] [--conf-doc [FILE]] [--list-keys]
                       repo-native static analyzer (lock discipline,
                       config-key registry, clock discipline, docs
                       drift); exit 0 = clean. --conf-doc regenerates
                       docs/CONFIG.md from tpumr/core/confkeys.py
  version              print the version
"""

from tpumr import __version__ as VERSION


def _parse_generic(argv: list[str]) \
        -> tuple[dict[str, Any], list[str], list[str]]:
    """Strip leading generic options; return (overrides, conf_files,
    rest). ``-conf FILE`` ≈ GenericOptionsParser's -conf: an extra
    site-file resource layered below -D overrides."""
    over: dict[str, Any] = {}
    conf_files: list[str] = []
    i = 0
    while i < len(argv):
        a = argv[i]
        if a == "-D" and i + 1 < len(argv):
            k, _, v = argv[i + 1].partition("=")
            over[k.strip()] = v.strip()
            i += 2
        elif a.startswith("-D") and "=" in a:
            k, _, v = a[2:].partition("=")
            over[k.strip()] = v.strip()
            i += 1
        elif a == "-conf" and i + 1 < len(argv):
            conf_files.append(argv[i + 1])
            i += 2
        elif a == "-fs" and i + 1 < len(argv):
            over["fs.default.name"] = argv[i + 1]
            i += 2
        elif a == "-jt" and i + 1 < len(argv):
            over["mapred.job.tracker"] = argv[i + 1]
            i += 2
        else:
            break
    return over, conf_files, argv[i:]


def _site_files(conf_files: list[str]) -> list[str]:
    """Resource files for this invocation, lowest precedence first:
    ``$TPUMR_CONF_DIR/tpumr-site.{toml,json}`` (≈ HADOOP_CONF_DIR's
    *-site.xml auto-loading), then explicit ``-conf`` files in order.
    A configured-but-missing conf dir site file is fine (the reference
    tolerates absent site files); an explicit -conf that is missing is
    an error the Configuration loader raises."""
    out: list[str] = []
    conf_dir = os.environ.get("TPUMR_CONF_DIR")
    if conf_dir:
        for name in ("tpumr-site.toml", "tpumr-site.json"):
            p = os.path.join(conf_dir, name)
            if os.path.exists(p):
                out.append(p)
    out.extend(conf_files)
    return out


def _conf(overrides: dict[str, Any]):
    from tpumr.mapred.jobconf import JobConf
    conf = JobConf()
    for k, v in overrides.items():
        conf.set(k, v)
    return conf


def _serve_forever(stop) -> int:
    """Block until SIGINT/SIGTERM, then stop() the daemon."""
    done = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            signal.signal(sig, lambda *_: done.set())
        except ValueError:  # non-main thread (tests)
            pass
    try:
        while not done.is_set():
            time.sleep(0.5)
    finally:
        stop()
    return 0


def _kv_args(argv: list[str]) -> dict[str, str]:
    """Parse '-name value' pairs of the daemon commands."""
    out: dict[str, str] = {}
    i = 0
    while i < len(argv):
        if argv[i].startswith("-") and i + 1 < len(argv):
            out[argv[i].lstrip("-")] = argv[i + 1]
            i += 2
        else:
            raise SystemExit(f"unexpected argument: {argv[i]}")
    return out


def _host_port(s: str) -> tuple[str, int]:
    host, _, port = s.partition(":")
    return host or "127.0.0.1", int(port)


# ------------------------------------------------------------------ daemons


def cmd_namenode(conf, argv: list[str]) -> int:
    from tpumr.dfs.namenode import NameNode
    a = _kv_args(argv)
    nn = NameNode(a.get("dir", "/tmp/tpumr-name"), conf,
                  host=a.get("host", "127.0.0.1"),
                  port=int(a.get("port", 9000))).start()
    host, port = nn.address
    print(f"NameNode up at tdfs://{host}:{port}/", file=sys.stderr)
    return _serve_forever(nn.stop)


def cmd_datanode(conf, argv: list[str]) -> int:
    from tpumr.dfs.datanode import DataNode
    a = _kv_args(argv)
    host, port = _host_port(a["nn"])
    conf.set("tdfs.datanode.capacity", int(a.get("capacity", 1 << 34)))
    dn = DataNode(host, port, a.get("dir", "/tmp/tpumr-data"),
                  conf).start()
    print(f"DataNode up ({dn.addr}), reporting to {a['nn']}", file=sys.stderr)
    return _serve_forever(dn.stop)


def cmd_secondarynamenode(conf, argv: list[str]) -> int:
    from tpumr.dfs.secondary import SecondaryNameNode
    a = _kv_args(argv)
    host, port = _host_port(a["nn"])
    if "interval" in a:
        conf.set("fs.checkpoint.period", a["interval"])
    snn = SecondaryNameNode(host, port, a.get("dir", "/tmp/tpumr-secondary"),
                            conf=conf).start()
    print(f"SecondaryNameNode up, checkpointing {a['nn']}", file=sys.stderr)
    return _serve_forever(snn.stop)


def cmd_jobtracker(conf, argv: list[str]) -> int:
    from tpumr.mapred.jobtracker import JobMaster
    a = _kv_args(argv)
    jm = JobMaster(conf, host=a.get("host", "127.0.0.1"),
                   port=int(a.get("port", 9001))).start()
    host, port = jm.address
    print(f"JobMaster up at {host}:{port}", file=sys.stderr)
    return _serve_forever(jm.stop)


def cmd_tasktracker(conf, argv: list[str]) -> int:
    from tpumr.mapred.tasktracker import NodeRunner
    a = _kv_args(argv)
    jt = a.get("jt") or conf.get("mapred.job.tracker")
    if not jt or jt == "local" or ":" not in jt:
        print("tasktracker needs -jt HOST:PORT", file=sys.stderr)
        return 255
    host, port = _host_port(jt)
    nr = NodeRunner(host, port, conf).start()
    if nr.tpu_devices is not None:
        print(f"TPU slot devices: {json.dumps(nr.tpu_devices)}",
              file=sys.stderr)
    print(f"NodeRunner up, heartbeating to {host}:{port}", file=sys.stderr)
    return _serve_forever(nr.stop)


def cmd_historyserver(conf, argv: list[str]) -> int:
    from tpumr.mapred.history_server import JobHistoryServer
    a = _kv_args(argv)
    hs = JobHistoryServer(a.get("dir")
                          or conf.get("tpumr.history.dir")
                          or "/tmp/tpumr-history",
                          port=int(a.get("port", 9888)),
                          conf=conf).start()
    print(f"JobHistoryServer up at {hs.url}", file=sys.stderr)
    return _serve_forever(hs.stop)


def cmd_balancer(conf, argv: list[str]) -> int:
    from tpumr.dfs.balancer import Balancer
    a = _kv_args(argv)
    host, port = _host_port(a["nn"])
    moved = Balancer(host, port,
                     threshold=float(a.get("threshold", 0.1)),
                     conf=conf).balance()
    print(f"Balancer moved {moved} blocks")
    return 0


# ------------------------------------------------------------------ clients


def cmd_fs(conf, argv: list[str]) -> int:
    from tpumr.fs.shell import FsShell
    default_fs = conf.get("fs.default.name")
    return FsShell(conf, default_fs=default_fs).run(argv)


def cmd_job(conf, argv: list[str]) -> int:
    """≈ bin/hadoop job: -list, -status, -kill, -counters, -history."""
    from tpumr.ipc.rpc import RpcClient, RpcError
    if argv and argv[0] == "-history":
        # offline: reads the history dir directly (≈ HistoryViewer) — no
        # live master needed
        return _job_history(conf, argv[1:])
    if argv and argv[0] == "-diagnose":
        return _job_diagnose(conf, argv[1:])
    if argv and argv[0] in ("trace", "-trace"):
        return _job_trace(conf, argv[1:])
    if argv and argv[0] in ("stats", "-stats"):
        return _job_stats(conf, argv[1:])
    jt = conf.get("mapred.job.tracker")
    if not jt or jt == "local":
        print("job control needs -jt HOST:PORT", file=sys.stderr)
        return 255
    host, port = _host_port(jt)
    from tpumr.security import client_credentials
    secret, scope = client_credentials(conf, "jobtracker")
    client = RpcClient(host, port, secret=secret, scope=scope)
    usage = ("Usage: tpumr job -list | -status ID | -kill ID | "
             "-set-priority ID PRIO | -kill-task ATTEMPT | "
             "-fail-task ATTEMPT | -list-attempt-ids ID map|reduce "
             "running|completed | -list-active-trackers | "
             "-list-blacklisted-trackers | "
             "-counters ID | -counter ID GROUP NAME | -events ID | "
             "-history ID [HISTORY_DIR] | stats ID [HISTORY_DIR] | "
             "trace ID [-out FILE] [-dir DIR]")
    if not argv:
        print(usage, file=sys.stderr)
        return 255
    cmd, *rest = argv
    if cmd not in ("-list", "-list-active-trackers",
                   "-list-blacklisted-trackers") and not rest:
        print(usage, file=sys.stderr)
        return 255
    try:
        if cmd == "-list":
            for jid in client.call("list_jobs"):
                st = client.call("get_job_status", jid)
                print(f"{jid}\t{st.get('state')}"
                      f"\t{st.get('priority', 'NORMAL')}"
                      f"\tmaps={st.get('map_progress'):.2f}"
                      f"\treduces={st.get('reduce_progress'):.2f}")
            return 0
        if cmd == "-status":
            st = client.call("get_job_status", rest[0])
            if st.get("job_id") and st["job_id"] != rest[0]:
                # the master restarted and recovered this job under a
                # new id (job_recovered alias) — say so, then report
                # the live job (scripts parsing stdout still work)
                print(f"job {rest[0]} was recovered as {st['job_id']} "
                      f"after a master restart", file=sys.stderr)
            print(json.dumps(st, indent=2, default=str))
            return 0
        if cmd == "-counters":
            print(json.dumps(client.call("get_counters", rest[0]), indent=2,
                             default=str))
            return 0
        if cmd == "-counter":
            # ≈ `hadoop job -counter ID GROUP NAME`: one value, bare on
            # stdout (scriptable, the reference's contract)
            if len(rest) < 3:
                print("Usage: tpumr job -counter ID GROUP NAME",
                      file=sys.stderr)
                return 255
            groups = client.call("get_counters", rest[0])
            val = (groups.get(rest[1]) or {}).get(rest[2])
            if val is None:
                print(f"counter {rest[1]}.{rest[2]} not found "
                      f"(groups: {', '.join(sorted(groups))})",
                      file=sys.stderr)
                return 1
            print(val)
            return 0
        if cmd == "-kill":
            from tpumr.security import UserGroupInformation
            ok = client.call("kill_job", rest[0],
                             UserGroupInformation.get_current_user().user)
            print(f"Killed {rest[0]}" if ok
                  else f"{rest[0]} already finished; not killed")
            return 0 if ok else 1
        if cmd == "-events":
            for ev in client.call("get_map_completion_events",
                                  rest[0], 0, 100):
                print(ev)
            return 0
        if cmd in ("-kill-task", "-fail-task"):
            from tpumr.security import UserGroupInformation
            ok = client.call("kill_task", rest[0], cmd == "-fail-task",
                             UserGroupInformation.get_current_user().user)
            verb = "Failed" if cmd == "-fail-task" else "Killed"
            print(f"{verb} task attempt {rest[0]}" if ok else
                  f"{rest[0]} not running; nothing to do")
            return 0 if ok else 1
        if cmd == "-list-attempt-ids":
            if len(rest) < 3:
                print(usage, file=sys.stderr)
                return 255
            for aid in client.call("get_attempt_ids", rest[0], rest[1],
                                   rest[2]):
                print(aid)
            return 0
        if cmd == "-list-active-trackers":
            for name in client.call("get_active_trackers"):
                print(name)
            return 0
        if cmd == "-list-blacklisted-trackers":
            for name in client.call("get_blacklisted_trackers"):
                print(name)
            return 0
        if cmd == "-set-priority":
            if len(rest) < 2:
                print("Usage: tpumr job -set-priority ID "
                      "VERY_HIGH|HIGH|NORMAL|LOW|VERY_LOW",
                      file=sys.stderr)
                return 255
            from tpumr.security import UserGroupInformation
            p = client.call("set_job_priority", rest[0], rest[1],
                            UserGroupInformation.get_current_user().user)
            print(f"Changed job priority of {rest[0]} to {p}")
            return 0
    except RpcError as e:
        print(f"job {cmd}: {e}", file=sys.stderr)
        return 1
    print(f"job: unknown option {cmd}", file=sys.stderr)
    return 255


def cmd_pipeline(conf, argv: list[str]) -> int:
    """DAG-of-jobs pipeline control: submit a JobGraph spec (JSON wire
    form — nodes/edges/loop, see docs/OPERATIONS.md "Running
    pipelines"), poll status, list, kill, or pull the merged
    end-to-end trace."""
    from tpumr.ipc.rpc import RpcError
    usage = ("Usage: tpumr pipeline submit GRAPH.json [-wait] | "
             "status ID | -list | -kill ID | trace ID [-out FILE]")
    if not argv:
        print(usage, file=sys.stderr)
        return 255
    jt = conf.get("mapred.job.tracker")
    if not jt or jt == "local":
        print("pipelines need -jt HOST:PORT (a cluster master)",
              file=sys.stderr)
        return 255
    from tpumr.pipeline import PipelineClient
    client = PipelineClient(conf)
    cmd, *rest = argv
    try:
        if cmd == "submit":
            if not rest:
                print(usage, file=sys.stderr)
                return 255
            with open(rest[0]) as f:
                graph = json.load(f)
            running = client.submit(graph)
            print(running.pipeline_id)
            if "-wait" in rest:
                st = running.wait_for_completion()
                print(json.dumps(st, indent=2, default=str))
                return 0 if st["state"] == "SUCCEEDED" else 1
            return 0
        if cmd in ("status", "-status"):
            if not rest:
                print(usage, file=sys.stderr)
                return 255
            print(json.dumps(client.status(rest[0]), indent=2,
                             default=str))
            return 0
        if cmd == "-list":
            for p in client.list():
                done = sum(1 for n in p["nodes"].values()
                           if n["state"] == "SUCCEEDED")
                print(f"{p['pipeline_id']}\t{p['state']}"
                      f"\t{p.get('name', '')}"
                      f"\tstages={done}/{len(p['nodes'])}")
            return 0
        if cmd == "-kill":
            if not rest:
                print(usage, file=sys.stderr)
                return 255
            ok = client.running(rest[0]).kill()
            print(f"Killed {rest[0]}" if ok
                  else f"{rest[0]} already finished; not killed")
            return 0 if ok else 1
        if cmd in ("trace", "-trace"):
            if not rest:
                print(usage, file=sys.stderr)
                return 255
            from tpumr.core import tracing
            t = client.trace(rest[0])
            if not t["spans"]:
                print(t.get("error") or "no spans", file=sys.stderr)
                return 1
            chrome = tracing.to_chrome_trace(t["spans"])
            out = f"{rest[0]}-trace.json"
            if "-out" in rest:
                i = rest.index("-out") + 1
                if i >= len(rest):
                    print("Usage: tpumr pipeline trace ID -out FILE",
                          file=sys.stderr)
                    return 255
                out = rest[i]
            with open(out, "w") as f:
                json.dump(chrome, f)
            print(f"wrote {len(t['spans'])} spans to {out}")
            return 0
    except (RpcError, OSError, ValueError) as e:
        print(f"pipeline {cmd}: {e}", file=sys.stderr)
        return 1
    print(f"pipeline: unknown option {cmd}", file=sys.stderr)
    return 255


def cmd_fsck(conf, argv: list[str]) -> int:
    """≈ bin/hadoop fsck: namespace health report from the NameNode
    (reference: hdfs/server/namenode/NamenodeFsck.java)."""
    from tpumr.fs import get_filesystem
    from tpumr.fs.shell import FsShell
    target = argv[0] if argv else "/"
    # same resolution rules as the fs shell (relative paths against
    # fs.default.name) — no hand-rolled URI gluing
    uri = FsShell(conf,
                  default_fs=conf.get("fs.default.name"))._resolve(target)
    if "://" not in uri:
        print("fsck: no filesystem given — pass a tdfs:// path or set "
              "fs.default.name (-fs tdfs://HOST:PORT/)", file=sys.stderr)
        return 255
    fs = get_filesystem(uri, conf)
    fsck = getattr(fs, "fsck", None)
    if fsck is None:
        print(f"fsck: only meaningful on tdfs:// (got {uri})",
              file=sys.stderr)
        return 255
    r = fsck(uri)
    print(f"FSCK started for path {target}")
    print(f" Total dirs:\t{r['dirs']}")
    print(f" Total files:\t{r['files']}")
    print(f" Total blocks:\t{r['blocks']} (size {r['size']} B)")
    print(f" Under-replicated blocks:\t{len(r['under_replicated'])}")
    print(f" Over-replicated blocks:\t{len(r['over_replicated'])}")
    print(f" Missing blocks:\t{len(r['missing'])}")
    print(f" Corrupt blocks:\t{len(r['corrupt'])}")
    print(f" Files open for write:\t{len(r['open_files'])}")
    for kind in ("under_replicated", "missing", "corrupt"):
        for ent in r[kind]:
            print(f"  {kind}: block {ent['block_id']} of {ent['path']}")
    print(f"The filesystem under path '{target}' is "
          + ("HEALTHY" if r["healthy"] else "CORRUPT"))
    return 0 if r["healthy"] else 1


def cmd_dfsadmin(conf, argv: list[str]) -> int:
    """≈ bin/hadoop dfsadmin: quotas, decommissioning, cluster report."""
    from tpumr.fs import get_filesystem
    usage = ("Usage: tpumr dfsadmin -setQuota N PATH | -setSpaceQuota N "
             "PATH | -clrQuota PATH | -clrSpaceQuota PATH | "
             "-decommission ADDR start|stop | "
             "-report | -safemode enter|leave|get | -saveNamespace | "
             "-refreshNodes | -refreshServiceAcl")
    if not argv:
        print(usage, file=sys.stderr)
        return 255

    def dfs(path="/"):
        uri = path if "://" in path else \
            (conf.get("fs.default.name") or "") .rstrip("/") + path
        fs = get_filesystem(uri, conf)
        if not hasattr(fs, "client"):
            raise SystemExit(f"dfsadmin: {uri} is not a tdfs:// filesystem")
        return fs, uri

    cmd, *rest = argv
    if cmd == "-refreshNodes" and not rest:
        from tpumr.ipc.rpc import RpcError
        fs, _ = dfs()
        try:
            r = fs.client.nn.call("refresh_nodes")
        except RpcError as e:
            print(f"dfsadmin: {e}", file=sys.stderr)
            return 1
        inc = r["included"]
        print(f"Nodes refreshed: include="
              f"{inc if inc == '*' else ','.join(inc) or '(none)'} "
              f"exclude={','.join(r['excluded']) or '(none)'}")
        for addr, state in sorted(r["changed"].items()):
            print(f"  {addr}: {state}")
        return 0
    if cmd == "-refreshServiceAcl" and not rest:
        from tpumr.ipc.rpc import RpcError
        fs, _ = dfs()
        try:
            for key, spec in fs.client.nn.call(
                    "refresh_service_acl").items():
                print(f"{key} = {spec}")
        except RpcError as e:
            print(f"dfsadmin: {e}", file=sys.stderr)
            return 1
        return 0
    if cmd == "-setQuota" and len(rest) == 2:
        fs, uri = dfs(rest[1])
        fs.client.nn.call("set_quota", fs._p(uri), int(rest[0]), None)
        return 0
    if cmd == "-setSpaceQuota" and len(rest) == 2:
        fs, uri = dfs(rest[1])
        fs.client.nn.call("set_quota", fs._p(uri), None, int(rest[0]))
        return 0
    if cmd == "-clrQuota" and len(rest) == 1:
        fs, uri = dfs(rest[0])
        fs.client.nn.call("set_quota", fs._p(uri), -1, None)
        return 0
    if cmd == "-clrSpaceQuota" and len(rest) == 1:
        fs, uri = dfs(rest[0])
        fs.client.nn.call("set_quota", fs._p(uri), None, -1)
        return 0
    if cmd == "-decommission" and len(rest) == 2:
        fs, _ = dfs("/")
        state = fs.client.nn.call("set_decommission", rest[0], rest[1])
        print(f"{rest[0]}: {state}")
        return 0
    if cmd == "-safemode" and len(rest) == 1:
        fs, _ = dfs("/")
        print(f"Safe mode is {'ON' if fs.client.nn.call('safemode', rest[0]) else 'OFF'}")
        return 0
    if cmd == "-saveNamespace":
        fs, _ = dfs("/")
        fs.client.nn.call("save_namespace")
        return 0
    if cmd == "-report":
        fs, _ = dfs("/")
        for d in fs.client.datanode_report():
            cap = d.get("capacity") or 0
            used = d.get("used", 0)
            pct = f"{100 * used / cap:.1f}%" if cap else "?"
            print(f"{d.get('addr', '?')}\t{d.get('state', '?')}\t"
                  f"blocks={d.get('blocks', '?')}\tused={used} ({pct})")
        return 0
    print(usage, file=sys.stderr)
    return 255


def _job_diagnose(conf, argv: list[str]) -> int:
    """Post-execution diagnosis (≈ contrib/vaidya's
    PostExPerformanceDiagnoser): run the rule set over one job's history
    and print findings + prescriptions. Accepts a JOB_ID (+ history dir
    like -history) or a direct path to a history .jsonl file."""
    import os
    if not argv:
        print("Usage: tpumr job -diagnose JOB_ID [HISTORY_DIR] | "
              "-diagnose PATH.jsonl [-json]", file=sys.stderr)
        return 255
    as_json = "-json" in argv
    argv = [a for a in argv if a != "-json"]
    if not argv:
        print("Usage: tpumr job -diagnose JOB_ID [HISTORY_DIR] | "
              "-diagnose PATH.jsonl [-json]", file=sys.stderr)
        return 255
    from tpumr.tools import vaidya
    target = argv[0]
    if not target.endswith(".jsonl"):
        hist_dir = argv[1] if len(argv) > 1 else conf.get("tpumr.history.dir")
        if not hist_dir:
            print("job -diagnose: pass HISTORY_DIR or set "
                  "tpumr.history.dir", file=sys.stderr)
            return 255
        target = os.path.join(hist_dir, f"{target}.jsonl")
    if "://" not in target and not os.path.exists(target):
        print(f"no history file at {target}", file=sys.stderr)
        return 1
    report = vaidya.diagnose_file(target)
    if as_json:
        import json as _json
        print(_json.dumps(report, indent=2))
    else:
        print(vaidya.format_report(report))
    return 0 if not report["findings"] else 2


def _job_trace(conf, argv: list[str]) -> int:
    """``tpumr job trace JOB_ID [-out FILE] [-dir TRACE_DIR]`` — export
    one traced job's merged distributed trace (Chrome trace-event JSON,
    loadable by chrome://tracing / Perfetto) and print its critical
    path: the submit→schedule→launch→run chain that determined the
    makespan, with per-span contribution percentages. Live mode pulls
    the merge from the JobTracker (get_job_trace); offline mode
    (``-dir``, or no jobtracker configured) merges the span files the
    daemons flushed next to the job history."""
    from tpumr.core import tracing
    usage = "Usage: tpumr job trace JOB_ID [-out FILE] [-dir TRACE_DIR]"
    if not argv:
        print(usage, file=sys.stderr)
        return 255
    job_id, out, trace_dir = argv[0], None, None
    it = iter(argv[1:])
    for a in it:
        if a == "-out":
            out = next(it, None)
        elif a == "-dir":
            trace_dir = next(it, None)
        else:
            print(usage, file=sys.stderr)
            return 255
    spans: "list[dict]" = []
    jt = conf.get("mapred.job.tracker")
    if trace_dir is None and jt and jt != "local":
        client = _jt_client(conf)
        if client is None:
            return 255
        from tpumr.ipc.rpc import RpcError
        try:
            t = client.call("get_job_trace", job_id)
        except RpcError as e:
            print(f"job trace: {e}", file=sys.stderr)
            return 1
        if t.get("error"):
            print(f"job trace: {t['error']}", file=sys.stderr)
            return 1
        spans = t["spans"]
    else:
        trace_dir = trace_dir or tracing.trace_dir_from_conf(conf)
        if not trace_dir:
            print("job trace: pass -dir TRACE_DIR or set "
                  "tpumr.trace.dir / tpumr.history.dir", file=sys.stderr)
            return 255
        # the trace id IS the job id (jobtracker.submit_job)
        spans = tracing.read_trace_files(str(trace_dir), job_id)
    if not spans:
        print(f"job trace: no spans found for {job_id} (was the job "
              f"submitted with tpumr.trace.enabled=true?)",
              file=sys.stderr)
        return 1
    chrome = tracing.to_chrome_trace(spans)
    out = out or f"{job_id}-trace.json"
    with open(out, "w") as f:
        json.dump(chrome, f, indent=1)
    roles = sorted({s.get("role", "?") for s in spans})
    cp = tracing.critical_path(spans)
    print(f"Trace: {len(spans)} spans across roles "
          f"{', '.join(roles)}")
    print(f"Makespan: {cp['makespan_s']:.3f}s — Chrome trace written to "
          f"{out} (load in chrome://tracing or ui.perfetto.dev)")
    print(f"Critical path ({len(cp['path'])} spans, "
          f"{cp['total_s']:.3f}s summed, "
          f"{cp['self_total_s']:.3f}s self time):")
    print(f"  {'span':<28} {'role':<12} {'backend':<8} "
          f"{'duration':>10} {'self':>10} {'contrib':>8}")
    for p in cp["path"]:
        print(f"  {p['name']:<28} {p['role']:<12} "
              f"{p['backend'] or '—':<8} {p['duration_s']:>9.4f}s "
              f"{p['self_s']:>9.4f}s {p['contribution_pct']:>7.1f}%")
    return 0


def _fmt_latency(label: str, pct: dict) -> str:
    if not pct:
        return f"{label}: (no finished tasks)"
    return (f"{label}: n={pct['count']}  mean={pct['mean']:.3f}s  "
            f"p50={pct['p50']:.3f}s  p95={pct['p95']:.3f}s  "
            f"p99={pct['p99']:.3f}s  max={pct['max']:.3f}s")


def _job_stats(conf, argv: list[str]) -> int:
    """`tpumr job stats JOB_ID [HISTORY_DIR] [-json]`: print the per-job
    stats rollup (metrics-<jobid>.json, written next to job history at
    finalization) — latency percentiles, the TPU/CPU task-time split,
    and acceleration factors. Offline like -history: reads the rollup
    file, no live master needed."""
    import os
    as_json = "-json" in argv
    argv = [a for a in argv if a != "-json"]
    if not argv:
        print("Usage: tpumr job stats JOB_ID [HISTORY_DIR] [-json]",
              file=sys.stderr)
        return 255
    job_id = argv[0]
    hist_dir = argv[1] if len(argv) > 1 else conf.get("tpumr.history.dir")
    if not hist_dir:
        print("job stats: pass HISTORY_DIR or set tpumr.history.dir",
              file=sys.stderr)
        return 255
    path = os.path.join(hist_dir, f"metrics-{job_id}.json")
    if not os.path.exists(path):
        known = [f[len("metrics-"):-len(".json")]
                 for f in sorted(os.listdir(hist_dir))
                 if f.startswith("metrics-") and f.endswith(".json")] \
            if os.path.isdir(hist_dir) else []
        print(f"no stats rollup for {job_id} in {hist_dir} (written at "
              f"job finalization); known: {', '.join(known) or '(none)'}",
              file=sys.stderr)
        return 1
    with open(path) as f:
        r = json.load(f)
    if as_json:
        print(json.dumps(r, indent=2))
        return 0
    print(f"Job: {r.get('job_id', job_id)}"
          + (f"  ({r['job_name']})" if r.get("job_name") else ""))
    print(f"State: {r.get('state', '?')}   wall time: "
          f"{r.get('wall_time', 0):.2f}s   maps: {r.get('num_maps', 0)} "
          f"({r.get('finished_tpu_maps', 0)} tpu / "
          f"{r.get('finished_cpu_maps', 0)} cpu)   reduces: "
          f"{r.get('num_reduces', 0)}")
    print(_fmt_latency("map latency   ", r.get("map_latency") or {}))
    if r.get("map_latency_tpu"):
        print(_fmt_latency("  tpu maps    ", r["map_latency_tpu"]))
    if r.get("map_latency_cpu"):
        print(_fmt_latency("  cpu maps    ", r["map_latency_cpu"]))
    print(_fmt_latency("reduce latency", r.get("reduce_latency") or {}))
    split = r.get("task_time_split") or {}
    print(f"task time     : tpu {split.get('tpu_map_s', 0):.3f}s / "
          f"cpu {split.get('cpu_map_s', 0):.3f}s map "
          f"(tpu {split.get('tpu_fraction_of_map_time', 0):.0%} of map "
          f"task-time), reduce {split.get('reduce_s', 0):.3f}s")
    prof = r.get("acceleration_factor_profiled") or 0
    obs = r.get("acceleration_factor_observed") or 0
    if prof or obs:
        print(f"acceleration  : profiled {prof:.2f}x, observed "
              f"{obs:.2f}x")
    dropped = r.get("runtime_samples_dropped", 0)
    if dropped:
        print(f"(percentiles computed over a capped sample; "
              f"{dropped} runtimes dropped)")
    counters = r.get("counters") or {}
    n = sum(len(v) for v in counters.values())
    print(f"counters      : {n} across {len(counters)} groups "
          f"(full dump: tpumr job stats {job_id} -json)")
    return 0


def _job_history(conf, argv: list[str]) -> int:
    """Human summary of one job's history file (≈ HistoryViewer, the
    engine behind `hadoop job -history`)."""
    import os
    if not argv:
        print("Usage: tpumr job -history JOB_ID [HISTORY_DIR]",
              file=sys.stderr)
        return 255
    job_id = argv[0]
    hist_dir = argv[1] if len(argv) > 1 else \
        conf.get("tpumr.history.dir")
    if not hist_dir:
        print("job -history: pass HISTORY_DIR or set tpumr.history.dir",
              file=sys.stderr)
        return 255
    path = os.path.join(hist_dir, f"{job_id}.jsonl")
    if not os.path.exists(path):
        known = [f[:-6] for f in sorted(os.listdir(hist_dir))
                 if f.endswith(".jsonl")] if os.path.isdir(hist_dir) else []
        print(f"no history for {job_id} in {hist_dir}; known: "
              f"{', '.join(known) or '(none)'}", file=sys.stderr)
        return 1
    from tpumr.mapred.history import JobHistory
    from tpumr.mapred.history_server import job_summary
    events = JobHistory.read(path)
    s = job_summary(events)
    print(f"Job: {s.get('job_id', job_id)}")
    print(f"Name: {s.get('name', '')}")
    print(f"State: {s.get('state', 'INCOMPLETE')}")
    if s.get("wall_time") is not None:
        print(f"Wall time: {s['wall_time']:.2f}s")
    print(f"Maps: {s.get('num_maps', '?')}  Reduces: "
          f"{s.get('num_reduces', '?')}")
    print(f"TPU maps: {s.get('finished_tpu_maps', 0) or 0}  CPU maps: "
          f"{s.get('finished_cpu_maps', 0) or 0}")
    if s.get("acceleration_factor"):
        print(f"Acceleration factor: {s['acceleration_factor']:.2f}")
    if s.get("error"):
        print(f"Error: {s['error']}")
    kinds: dict = {}
    for ev in events:
        kinds[ev.get("event", "?")] = kinds.get(ev.get("event", "?"), 0) + 1
    print("Events: " + ", ".join(f"{k}={v}"
                                 for k, v in sorted(kinds.items())))
    # per-task failure diagnostics ≈ HistoryViewer's FAILED task listing
    for ev in events:
        if ev.get("event") == "TASK_FAILED":
            where = "tpu" if ev.get("run_on_tpu") else "cpu"
            print(f"  failed: {ev.get('attempt_id', '?')} ({where} on "
                  f"{ev.get('tracker', '?')}, "
                  f"{ev.get('runtime', 0):.2f}s)")
    return 0


def cmd_failmon(conf, argv: list[str]) -> int:
    """≈ contrib/failmon RunOnce + the HDFS merge step."""
    from tpumr.tools import failmon
    usage = ("Usage: tpumr failmon -collect [-store DIR] [-upload URL] "
             "[-anonymize] | -merge URL DEST")
    if not argv:
        print(usage, file=sys.stderr)
        return 255
    if argv[0] == "-merge":
        if len(argv) != 3:
            print(usage, file=sys.stderr)
            return 255
        n = failmon.merge(argv[1], argv[2])
        print(f"merged {n} events -> {argv[2]}")
        return 0
    if argv[0] != "-collect":
        print(usage, file=sys.stderr)
        return 255
    rest = argv[1:]
    anonymize = "-anonymize" in rest
    rest = [a for a in rest if a != "-anonymize"]
    opts: dict[str, str] = {}
    i = 0
    while i < len(rest):
        flag = rest[i]
        if flag not in ("-store", "-upload") or i + 1 >= len(rest):
            print(f"failmon: bad or valueless option {flag!r}\n{usage}",
                  file=sys.stderr)
            return 255
        opts[flag] = rest[i + 1]
        i += 2
    store_dir = opts.get("-store") or conf.get("failmon.store.dir") \
        or "/tmp/tpumr-failmon"
    store = failmon.LocalStore(store_dir, anonymize=anonymize)
    n = failmon.run_once(store, failmon.default_monitors(conf))
    print(f"collected {n} events -> {store_dir}")
    url = opts.get("-upload") or conf.get("failmon.upload.url")
    if url:
        dest = store.upload(url)
        print(f"uploaded -> {dest}" if dest else "nothing to upload")
    return 0


def cmd_gridmix(conf, argv: list[str]) -> int:
    from tpumr.benchmarks.gridmix import main as gridmix_main
    return gridmix_main(argv)


def cmd_simulate(conf, argv: list[str]) -> int:
    """Control-plane scale harness (tpumr/scale/): N simulated trackers
    speaking the real heartbeat protocol plus a synthetic multi-job
    workload, against the configured master (``-jt HOST:PORT``) or a
    self-hosted in-process one. With a self-hosted master the report
    includes the master-side saturation series (heartbeat p50/p99, lag
    p99, lock-wait p99, assign p99, RPC inflight peak); against a live
    master read those off its /metrics/prom. See docs/OPERATIONS.md
    "Sizing the master". ``-dfs N`` runs the storage twin instead: one
    DFS saturation rung against a fresh in-process mini-DFS (see
    "Monitoring the DFS")."""
    from tpumr.scale import ScaleDriver, SimFleet
    from tpumr.security import rpc_secret
    a = _kv_args(argv)
    if "scenario" in a:
        return _simulate_scenario(conf, a)
    if "dfs" in a:
        return _simulate_dfs(conf, a)
    n = int(a.get("trackers", 25))
    n_jobs = int(a.get("jobs", 4))
    maps = int(a.get("maps", 64))
    reduces = int(a.get("reduces", 2))
    interval_s = float(a.get("interval", 200)) / 1000.0
    task_mean_s = float(a.get("task-ms", 500)) / 1000.0
    timeout_s = float(a.get("timeout", 120))
    ff_rate = float(a.get("ff-rate", 0.0))
    jt = conf.get("mapred.job.tracker")
    master = None
    if jt and jt != "local" and ":" in str(jt):
        host, port = _host_port(str(jt))
    else:
        from tpumr.mapred.jobtracker import JobMaster
        conf.set("tpumr.heartbeat.interval.ms", int(interval_s * 1000))
        conf.set_if_unset("tpumr.tracker.expiry.ms", 60_000)
        master = JobMaster(conf).start()
        host, port = master.address
        print(f"self-hosted JobMaster at {host}:{port}", file=sys.stderr)
    secret = rpc_secret(conf)
    fleet = SimFleet(host, port, n, secret=secret, interval_s=interval_s,
                     task_time_mean_s=task_mean_s,
                     fetch_failure_rate=ff_rate).start()
    driver = ScaleDriver(host, port, secret=secret)
    try:
        print(f"simulate: {n} trackers @ {interval_s * 1000:.0f}ms "
              f"heartbeats, {n_jobs} jobs x {maps} maps / {reduces} "
              f"reduces, task mean {task_mean_s * 1000:.0f}ms",
              file=sys.stderr)
        result = driver.run_workload(n_jobs, maps, reduces,
                                     timeout_s=timeout_s)
        fl = fleet.stats()
        report = {
            "trackers": n,
            "jobs_succeeded": len(result["succeeded"]),
            "jobs_failed": len(result["failed"]),
            "jobs_unfinished": len(result["unfinished"]),
            "heartbeats": fl["heartbeats"],
            "tasks_completed": fl["tasks_completed"],
            "hb_errors": fl["hb_errors"],
            "client_rtt_p50_s": fl["hb_rtt"].get("p50", 0.0),
            "client_rtt_p99_s": fl["hb_rtt"].get("p99", 0.0),
            "client_lag_p99_s": fl["hb_lag"].get("p99", 0.0),
        }
        if master is not None:
            snap = master.metrics.snapshot()
            jt_m = snap.get("jobtracker", {})
            report.update({
                "heartbeat_p50_s": jt_m.get("heartbeat_seconds",
                                            {}).get("p50", 0.0),
                "heartbeat_p99_s": jt_m.get("heartbeat_seconds",
                                            {}).get("p99", 0.0),
                "heartbeat_lag_p99_s": jt_m.get("heartbeat_lag_seconds",
                                                {}).get("p99", 0.0),
                # one wait series per decomposed master lock class;
                # the unsuffixed row is the GLOBAL lock's
                "lock_wait_p99_s": jt_m.get(
                    "jt_lock_wait_seconds|lock=global",
                    {}).get("p99", 0.0),
                "lock_wait_trackers_p99_s": jt_m.get(
                    "jt_lock_wait_seconds|lock=trackers",
                    {}).get("p99", 0.0),
                "lock_wait_scheduler_p99_s": jt_m.get(
                    "jt_lock_wait_seconds|lock=scheduler",
                    {}).get("p99", 0.0),
                "interval_instructed_ms": jt_m.get(
                    "heartbeat_interval_instructed_ms", 0),
                "assign_p99_s": snap.get("scheduler", {}).get(
                    "assign_seconds", {}).get("p99", 0.0),
                "completion_event_lag_p99": jt_m.get(
                    "completion_event_lag", {}).get("p99", 0.0),
                "rpc_inflight_peak": master._server.inflight_peak(),
            })
        print(json.dumps(report, indent=2, sort_keys=True))
        return 0 if not result["failed"] and not result["unfinished"] \
            else 1
    finally:
        fleet.stop()
        driver.close()
        if master is not None:
            master.stop()


def _simulate_dfs(conf, a: "dict[str, str]") -> int:
    """``simulate -dfs N`` — one DFS saturation rung: a fresh
    in-process MiniDFSCluster under a fleet of N real DFSClients on a
    fixed op cadence (``tpumr/scale/simdfs.py``), reported as one
    joined row — NameNode op/lock/editlog attribution plus client-side
    round trips and hot-block skew.
    ``-seconds S`` measurement window, ``-interval MS`` per-client op
    cadence, ``-datanodes N``, ``-files N`` working-set size,
    ``-hot-p P`` hot-file read probability, ``-prom PATH`` scrapes the
    live NameNode /metrics/prom into PATH. The row is judged against
    the dual SLO (``tpumr.dfs.bench.op.slo.ms`` / ``.read.slo.ms``);
    exit 1 when it fails."""
    from tpumr.core import confkeys
    from tpumr.scale.simdfs import run_dfs_step
    row = run_dfs_step(
        int(a["dfs"]), conf=conf,
        interval_s=float(a.get("interval", 50)) / 1000.0,
        measure_s=float(a.get("seconds", 6)),
        num_datanodes=int(a.get("datanodes", 3)),
        n_files=int(a.get("files", 8)),
        hot_read_p=float(a.get("hot-p", 0.5)),
        read_bytes=int(a.get("read-bytes", 1 << 16)),
        seed=int(a.get("seed", 0)),
        prom_out=a.get("prom"))
    op_slo_s = confkeys.get_int(conf, "tpumr.dfs.bench.op.slo.ms") / 1e3
    read_slo_s = confkeys.get_int(conf,
                                  "tpumr.dfs.bench.read.slo.ms") / 1e3
    row["slo"] = {
        "op_slo_s": op_slo_s, "read_slo_s": read_slo_s,
        "pass": row["completed"] and row["nn_op_p99_s"] <= op_slo_s
                and row["read_rtt_p99_s"] <= read_slo_s}
    print(json.dumps(row, indent=2, sort_keys=True))
    return 0 if row["slo"]["pass"] else 1


def _simulate_scenario(conf, a: "dict[str, str]") -> int:
    """``simulate -scenario NAME`` — replay one scenario-lab mix
    (tpumr/scale/scenario.py) and gate on its per-class SLO verdicts.
    ``-seed S`` overrides the spec's seed, ``-report PATH`` writes the
    full machine-readable report there (stdout then carries a short
    verdict summary instead), ``-incidents DIR`` keeps history +
    incident bundles under DIR even on success."""
    from tpumr.core import confkeys
    from tpumr.scale.scenario import ScenarioError, run_named
    seed = int(a["seed"]) if "seed" in a else None
    scenario_dir = a.get("dir") \
        or confkeys.get(conf, "tpumr.scenario.dir")
    try:
        rep = run_named(a["scenario"], seed=seed,
                        scenario_dir=scenario_dir,
                        artifacts_dir=a.get("incidents"))
    except ScenarioError as e:
        print(f"scenario error: {e}", file=sys.stderr)
        return 2
    doc = json.dumps(rep, indent=2, sort_keys=True)
    if "report" in a:
        with open(a["report"], "w") as f:
            f.write(doc + "\n")
        jobs = rep["jobs"]
        print(f"scenario {rep['scenario']} seed {rep['seed']}: "
              f"{jobs['succeeded']}/{jobs['submitted']} jobs, "
              f"{jobs['failed']} failed, {jobs['unfinished']} "
              f"unfinished, wall {rep['wall_s']}s -> {a['report']}")
        for cls_name, row in sorted(rep["verdicts"].items()):
            print(f"  class {cls_name}: "
                  f"{'PASS' if row.get('pass') else 'FAIL'}")
        if rep.get("dfs"):
            d = rep["dfs"]
            heal = d.get("heal") or {}
            print(f"  dfs: {'PASS' if d['pass'] else 'FAIL'} "
                  f"({d['ops']} ops, {d['errors']} errors, "
                  f"{d['corrupt_reads']} corrupt reads, "
                  f"{d['safemode_refusals']} safemode refusals, "
                  f"heal {heal.get('heal_s')}s)")
        print(f"  overall: {'PASS' if rep['pass'] else 'FAIL'}")
    else:
        print(doc)
    return 0 if rep["pass"] else 1


def cmd_scenario(conf, argv: list[str]) -> int:
    """Scenario-lab catalog / runner:

    - ``scenario -list`` — the built-in mixes plus any ``*.toml`` specs
      under ``tpumr.scenario.dir`` (or ``-dir DIR``).
    - ``scenario NAME [-seed S] [-report PATH] [-incidents DIR]`` —
      replay one (same as ``simulate -scenario NAME``).
    """
    from tpumr.core import confkeys
    if argv and argv[0].lstrip("-") == "list":
        from tpumr.scale.scenario import list_scenarios
        a = _kv_args(argv[1:])
        scenario_dir = a.get("dir") \
            or confkeys.get(conf, "tpumr.scenario.dir")
        for row in list_scenarios(scenario_dir):
            if "error" in row:
                print(f"{row['name']}  [{row['origin']}]  "
                      f"ERROR: {row['error']}")
                continue
            chaos = ",".join(row["chaos"]) or "none"
            print(f"{row['name']}  [{row['origin']}]  "
                  f"jobs={row['jobs']} classes="
                  f"{','.join(row['classes'])} chaos={chaos} "
                  f"trace={row['trace_s']:.1f}s")
        return 0
    if argv and not argv[0].startswith("-"):
        a = _kv_args(argv[1:])
        a["scenario"] = argv[0]
        return _simulate_scenario(conf, a)
    print("usage: tpumr scenario -list | "
          "tpumr scenario NAME [-seed S] [-report PATH]",
          file=sys.stderr)
    return 2


def cmd_distcp(conf, argv: list[str]) -> int:
    from tpumr.tools.distcp import main as distcp_main
    return distcp_main(argv)


def cmd_archive(conf, argv: list[str]) -> int:
    from tpumr.tools.archive import main as archive_main
    return archive_main(argv)


def cmd_rumen(conf, argv: list[str]) -> int:
    from tpumr.tools.rumen import main as rumen_main
    return rumen_main(argv)


def cmd_pipes(conf, argv: list[str]) -> int:
    from tpumr.pipes.submitter import main as pipes_main
    return pipes_main(argv)


def cmd_streaming(conf, argv: list[str]) -> int:
    from tpumr.streaming.stream_job import main as stream_main
    return stream_main(argv)


def cmd_examples(conf, argv: list[str]) -> int:
    from tpumr.examples import main as ex_main
    return ex_main(argv)


def cmd_keys(conf, argv: list[str]) -> int:
    """Credential provisioning (tpumr/security/tokens.py):

    - ``keys user-key USER`` — derive USER's personal signing key from
      the cluster secret (operator-side; hand the hex to the user, who
      sets ``tpumr.rpc.user.key``). ≈ provisioning a service keytab.
    - ``keys token [-renewer R] [-out FILE]`` — obtain a delegation
      token from the JobTracker for the CALLER's identity and write the
      credential file (``tpumr.rpc.token.file``).
    - ``keys renew FILE`` / ``keys cancel FILE``.
    """
    usage = ("Usage: tpumr keys user-key USER | "
             "token [-renewer R] [-out FILE] | renew FILE | cancel FILE")
    if not argv:
        print(usage, file=sys.stderr)
        return 255
    sub, *rest = argv
    if sub == "user-key":
        from tpumr.security import rpc_secret
        from tpumr.security.tokens import derive_user_key
        secret = rpc_secret(conf)
        if secret is None or not rest:
            print("user-key needs USER and the cluster secret "
                  "(tpumr.rpc.secret[.file])", file=sys.stderr)
            return 1
        print(derive_user_key(secret, rest[0]).hex())
        return 0
    if sub in ("token", "renew", "cancel"):
        from tpumr.ipc.rpc import RpcClient, RpcError
        from tpumr.security import client_credentials
        # -nn targets the NameNode (tokens are per-issuing-service,
        # like the reference's NN vs JT delegation tokens)
        service = "namenode" if "-nn" in rest else "jobtracker"
        rest = [a for a in rest if a != "-nn"]
        if service == "namenode":
            default = str(conf.get("fs.default.name") or "")
            if not default.startswith("tdfs://"):
                print("-nn needs fs.default.name=tdfs://HOST:PORT",
                      file=sys.stderr)
                return 255
            host, port = _host_port(default[len("tdfs://"):].rstrip("/"))
        else:
            jt = conf.get("mapred.job.tracker")
            if not jt or jt == "local":
                print("token ops need -jt HOST:PORT", file=sys.stderr)
                return 255
            host, port = _host_port(jt)
        secret, scope = client_credentials(conf, service)
        client = RpcClient(host, port, secret=secret, scope=scope)
        try:
            if sub == "token":
                renewer, out = "", None
                it = iter(rest)
                for a in it:
                    if a == "-renewer":
                        renewer = next(it, "")
                    elif a == "-out":
                        out = next(it, None)
                wire = client.call("get_delegation_token", renewer)
                if out:
                    # merge under the service key so one credential file
                    # can hold both the JT and NN tokens
                    merged: dict = {}
                    if os.path.exists(out):
                        with open(out) as f:
                            prev = json.load(f)
                        if isinstance(prev, dict):
                            if "ident" in prev:
                                # flat single-service file: preserve the
                                # existing credential under the OTHER
                                # service key rather than discarding it
                                other = ("namenode"
                                         if service == "jobtracker"
                                         else "jobtracker")
                                merged = {other: prev}
                            else:
                                merged = prev
                    merged[service] = wire
                    fd = os.open(out, os.O_WRONLY | os.O_CREAT
                                 | os.O_TRUNC, 0o600)  # credential file
                    with os.fdopen(fd, "w") as f:
                        json.dump(merged, f, indent=2)
                        f.write("\n")
                    print(f"{service} token written to {out}")
                else:
                    print(json.dumps(wire, indent=2))
                return 0
            with open(rest[0]) as f:
                data = json.load(f)
            wire = data if "ident" in data else data[service]
            if sub == "renew":
                exp = client.call("renew_delegation_token", wire)
                print(f"renewed until {exp}")
            else:
                client.call("cancel_delegation_token", wire)
                print("canceled")
            return 0
        except (RpcError, OSError, IndexError, ValueError, KeyError) as e:
            print(f"keys {sub}: {e}", file=sys.stderr)
            return 1
    print(usage, file=sys.stderr)
    return 255


def _jt_client(conf):
    """An RPC client for the configured JobTracker, or None (with the
    error already printed) when mapred.job.tracker is unset/local."""
    from tpumr.ipc.rpc import RpcClient
    from tpumr.security import client_credentials
    jt = conf.get("mapred.job.tracker")
    if not jt or jt == "local" or ":" not in str(jt):
        print("this command needs -jt HOST:PORT "
              "(or mapred.job.tracker)", file=sys.stderr)
        return None
    host, port = _host_port(str(jt))
    secret, scope = client_credentials(conf, "jobtracker")
    return RpcClient(host, port, secret=secret, scope=scope)


def cmd_queue(conf, argv: list[str]) -> int:
    """≈ bin/hadoop queue: -list | -info QUEUE [-showJobs] | -showacls
    (reference CLI: JobQueueClient over JobClient.getQueues/
    getJobsFromQueue/getQueueAclsForCurrentUser)."""
    from tpumr.ipc.rpc import RpcError
    usage = "Usage: tpumr queue -list | -info QUEUE [-showJobs] | -showacls"
    if not argv or argv[0] not in ("-list", "-info", "-showacls"):
        print(usage, file=sys.stderr)
        return 255
    client = _jt_client(conf)
    if client is None:
        return 255
    cmd, *rest = argv
    try:
        if cmd == "-list":
            for q in client.call("get_queue_info"):
                print(f"Queue: {q['queue']}")
                print(f"  acl-submit-job: {q['acl_submit_job']}"
                      + ("" if q["acls_enabled"] else " (acls disabled)"))
                print(f"  acl-administer-jobs: {q['acl_administer_jobs']}")
                print(f"  jobs: {q['running_jobs']} running / "
                      f"{q['total_jobs']} total")
            return 0
        if cmd == "-info":
            if not rest:
                print(usage, file=sys.stderr)
                return 255
            queue, *flags = rest
            info = next((q for q in client.call("get_queue_info")
                         if q["queue"] == queue), None)
            if info is None:
                print(f"queue {queue!r} is not defined", file=sys.stderr)
                return 1
            print(json.dumps(info, indent=2))
            if "-showJobs" in flags:
                for jid in client.call("get_queue_jobs", queue):
                    # per-job view ACLs may hide a status from this
                    # caller; the queue listing itself must still
                    # complete (the id is queue metadata, not job data)
                    try:
                        state = client.call("get_job_status",
                                            jid).get("state")
                    except RpcError:
                        state = "(not viewable)"
                    print(f"{jid}\t{state}")
            return 0
        if cmd == "-showacls":
            from tpumr.security import UserGroupInformation
            me = UserGroupInformation.get_current_user().user
            print(f"Queue acls for user: {me}")
            for row in client.call("get_queue_acls", me):
                ops = ",".join(row["operations"]) or "(none)"
                print(f"  {row['queue']}: {ops}")
            return 0
    except RpcError as e:
        print(f"queue: {e}", file=sys.stderr)
        return 1
    print(usage, file=sys.stderr)
    return 255


def cmd_mradmin(conf, argv: list[str]) -> int:
    """≈ bin/hadoop mradmin (AdminOperationsProtocol), admin-gated when
    ACLs are enforced:

    - ``-refreshQueues``: re-read queue names + ACLs
      (mapred.queue.acls.file) on the live JobTracker, no restart.
    - ``-refreshNodes``: re-read mapred.hosts / mapred.hosts.exclude;
      trackers on newly excluded hosts are evicted (their work
      re-queues like a lost tracker's).
    """
    from tpumr.ipc.rpc import RpcError
    usage = ("Usage: tpumr mradmin -refreshQueues | -refreshNodes | "
             "-refreshServiceAcl")
    if argv not in (["-refreshQueues"], ["-refreshNodes"],
                    ["-refreshServiceAcl"]):
        # strict: silently ignoring a trailing flag would report an
        # operation as done that never ran
        print(usage, file=sys.stderr)
        return 255
    client = _jt_client(conf)
    if client is None:
        return 255
    from tpumr.security import UserGroupInformation
    me = UserGroupInformation.get_current_user().user
    try:
        if argv == ["-refreshQueues"]:
            queues = client.call("refresh_queues", me)
            print(f"Queues refreshed: {', '.join(queues)}")
        elif argv == ["-refreshServiceAcl"]:
            for key, spec in client.call("refresh_service_acl").items():
                print(f"{key} = {spec}")
        else:
            r = client.call("refresh_nodes", me)
            inc = r["included"]
            print(f"Nodes refreshed: include="
                  f"{inc if inc == '*' else ','.join(inc) or '(none)'} "
                  f"exclude={','.join(r['excluded']) or '(none)'}")
            for name in r["evicted_trackers"]:
                print(f"  evicted: {name}")
    except RpcError as e:
        print(f"mradmin: {e}", file=sys.stderr)
        return 1
    return 0


def cmd_daemonlog(conf, argv: list[str]) -> int:
    """≈ bin/hadoop daemonlog: get/set a live daemon's logger level
    through its status HTTP server (/json/logLevel ≈ the LogLevel
    servlet). Works against ANY tpumr daemon's HTTP port."""
    import urllib.error
    import urllib.parse
    import urllib.request
    usage = ("Usage: tpumr daemonlog -getlevel HOST:PORT LOGGER | "
             "-setlevel HOST:PORT LOGGER LEVEL")
    if len(argv) < 3 or argv[0] not in ("-getlevel", "-setlevel") \
            or (argv[0] == "-setlevel" and len(argv) < 4):
        print(usage, file=sys.stderr)
        return 255
    hostport, logger = argv[1], argv[2]
    params = {"log": "" if logger == "root" else logger}
    if argv[0] == "-setlevel":
        params["level"] = argv[3]
    url = (f"http://{hostport}/json/logLevel?"
           f"{urllib.parse.urlencode(params)}")
    try:
        # level mutation must travel as POST (the server rejects GET
        # sets so drive-by GETs can't silence a daemon's logging)
        req = urllib.request.Request(
            url, method="POST" if "level" in params else "GET")
        with urllib.request.urlopen(req, timeout=10) as resp:
            body = json.loads(resp.read().decode("utf-8"))
    except urllib.error.HTTPError as e:
        # the server reports rejected levels/loggers as a JSON error
        # body — surface its message, not a bare "HTTP Error 500"
        try:
            detail = json.loads(e.read().decode("utf-8")).get("error", e)
        except ValueError:
            detail = e
        print(f"daemonlog: {detail}", file=sys.stderr)
        return 1
    except OSError as e:
        print(f"daemonlog: {hostport}: {e}", file=sys.stderr)
        return 1
    if "error" in body:
        print(f"daemonlog: {body['error']}", file=sys.stderr)
        return 1
    print(f"{body['log']}: level={body['level']} "
          f"effective={body['effective']}")
    return 0


def cmd_prof(conf, argv: list[str]) -> int:
    """Pull a profiling window off a live daemon's continuous sampler:
    ``tpumr prof HOST:PORT [-seconds N] [-out FILE] [-flame]``. Default
    output is the collapsed folded-stack text (one ``thread;frames
    count`` line per unique stack — pipe into any flamegraph tool);
    ``-flame`` asks the daemon for the self-contained SVG instead.
    Needs ``tpumr.prof.enabled`` on the target daemon."""
    import urllib.error
    import urllib.request
    usage = ("Usage: tpumr prof HOST:PORT [-seconds N] [-out FILE] "
             "[-flame]")
    if not argv or ":" not in argv[0]:
        print(usage, file=sys.stderr)
        return 255
    hostport, rest = argv[0], argv[1:]
    a = _kv_args([x for x in rest if x != "-flame"])
    flame = "-flame" in rest
    path = "flame" if flame else "stacks"
    url = f"http://{hostport}/{path}"
    if a.get("seconds"):
        url += f"?seconds={float(a['seconds'])}"
    try:
        with urllib.request.urlopen(url, timeout=30) as resp:
            body = resp.read().decode("utf-8")
    except urllib.error.HTTPError as e:
        # a daemon without the sampler 404s — say what to enable
        detail = (f"{e} — is tpumr.prof.enabled set on the daemon?"
                  if e.code == 404 else e)
        print(f"prof: {hostport}: {detail}", file=sys.stderr)
        return 1
    except OSError as e:
        print(f"prof: {hostport}: {e}", file=sys.stderr)
        return 1
    out = a.get("out")
    if out:
        with open(out, "w") as f:
            f.write(body)
        print(f"wrote {len(body)} bytes to {out}", file=sys.stderr)
    else:
        sys.stdout.write(body)
    return 0


def cmd_fetchdt(conf, argv: list[str]) -> int:
    """≈ bin/hadoop fetchdt TOKEN_FILE: fetch a NameNode delegation
    token into a credential file — an alias for
    ``tpumr keys token -nn -out FILE``."""
    if len(argv) != 1:
        print("Usage: tpumr fetchdt TOKEN_FILE", file=sys.stderr)
        return 255
    return cmd_keys(conf, ["token", "-nn", "-out", argv[0]])


def cmd_rcc(conf, argv: list[str]) -> int:
    """≈ bin/rcc: compile Record I/O DDL to record classes."""
    from tpumr.recordio.rcc import main as rcc_main
    return rcc_main(argv)


def cmd_tdfsproxy(conf, argv: list[str]) -> int:
    """≈ contrib/hdfsproxy: read-only HTTP(S) storage gateway."""
    from tpumr.tools.tdfsproxy import main as proxy_main
    return proxy_main(argv, conf)


def cmd_lint(conf, argv: list[str]) -> int:
    """Repo-native static analyzer (tpumr/tools/tpulint): proves the
    master's lock-rank discipline, the config-key registry, monotonic-
    clock deadline arithmetic, and docs/code drift — the invariants the
    runtime only spot-checks on exercised paths."""
    from tpumr.tools.tpulint.cli import main as lint_main
    return lint_main(argv)


def cmd_version(conf, argv: list[str]) -> int:
    print(f"tpumr {VERSION}")
    return 0


COMMANDS = {
    "namenode": cmd_namenode,
    "datanode": cmd_datanode,
    "secondarynamenode": cmd_secondarynamenode,
    "jobtracker": cmd_jobtracker,
    "tasktracker": cmd_tasktracker,
    "historyserver": cmd_historyserver,
    "balancer": cmd_balancer,
    "fsck": cmd_fsck,
    "dfsadmin": cmd_dfsadmin,
    "fs": cmd_fs,
    "job": cmd_job,
    "pipeline": cmd_pipeline,
    "pipes": cmd_pipes,
    "streaming": cmd_streaming,
    "distcp": cmd_distcp,
    "failmon": cmd_failmon,
    "gridmix": cmd_gridmix,
    "simulate": cmd_simulate,
    "scenario": cmd_scenario,
    "archive": cmd_archive,
    "rumen": cmd_rumen,
    "examples": cmd_examples,
    "keys": cmd_keys,
    "queue": cmd_queue,
    "mradmin": cmd_mradmin,
    "daemonlog": cmd_daemonlog,
    "prof": cmd_prof,
    "fetchdt": cmd_fetchdt,
    "rcc": cmd_rcc,
    "tdfsproxy": cmd_tdfsproxy,
    "lint": cmd_lint,
    "version": cmd_version,
}


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    overrides, conf_files, rest = _parse_generic(argv)
    if not rest:
        sys.stderr.write(USAGE)
        return 255
    cmd, *args = rest
    fn = COMMANDS.get(cmd)
    if fn is None:
        sys.stderr.write(f"Unknown command: {cmd}\n\n" + USAGE)
        return 255
    # resource layers for this invocation, lowest first: conf-dir site
    # file(s), -conf files, then -D/-fs/-jt overrides on top. Installed
    # as default resources ≈ GenericOptionsParser merging into the job
    # conf so they also reach confs the subcommand builds itself
    # (examples/pipes/streaming); removed afterwards so repeated
    # in-process invocations (tests, embedding) don't accumulate layers
    from tpumr.core.configuration import Configuration
    layers: "list[dict | str]" = list(_site_files(conf_files))
    if overrides:
        layers.append(overrides)
    if not layers:
        return fn(_conf(overrides), args)
    installed = 0
    try:
        for layer in layers:
            # a broken -conf file raises here, before dispatch — the
            # command never runs against partial configuration
            Configuration.add_default_resource(layer)
            installed += 1
        return fn(_conf(overrides), args)
    finally:
        if installed:
            del Configuration._default_resources[-installed:]


if __name__ == "__main__":
    sys.exit(main())
