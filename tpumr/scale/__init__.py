"""Control-plane scale harness — simulated trackers, real wire protocol.

The JobTracker is one process absorbing every heartbeat, completion-
event poll, and fetch-failure report. This package loads it (and a
NameNode) by hand, behind ``tpumr simulate``:

- :mod:`tpumr.scale.simtracker` — ``SimTracker``/``SimFleet``: N
  lightweight fake trackers speaking the REAL heartbeat protocol over
  the REAL RPC transport (``RpcClient`` → ``ipc/rpc.py`` → the live
  ``JobMaster.heartbeat``), executing assigned tasks as timed no-ops
  drawn from a configurable duration distribution. Everything the wire
  carries is authentic — response-id replay, metrics piggybacks,
  completion-event polls, fetch-failure reports — only task execution
  is faked, because task bytes are the data plane and this harness
  measures the control plane.
- :mod:`tpumr.scale.simdfs` — ``SimDFSClient``/``SimDFSFleet``: the
  storage twin — N real ``DFSClient`` instances generating a skewed
  read-dominant op mix against a live NameNode + DataNodes, the load
  side of ``tpumr simulate -dfs``.
- :mod:`tpumr.scale.driver` — ``ScaleDriver``: submits synthetic
  multi-job workloads over the client RPC surface and waits for them.
- :mod:`tpumr.scale.scenario` — the scenario lab: named,
  seed-deterministic traffic mixes (interactive bursts, wide batch,
  iterative pipelines) replayed against a real master with chaos
  (tracker churn, master kill/restart, fi seams) and judged by
  per-traffic-class SLO verdicts from the flight recorder.

The read side is the master's own saturation series (heartbeat
latency/lag/phases, ``jt_lock_wait_seconds``, ``rpc_inflight``,
completion-event lag), which ``tpumr simulate`` prints when it hosts
the master itself. These are host-clock numbers of simulated trackers:
the system's speed is what ``bench/run.py`` measures on the chip.
"""

from tpumr.scale.driver import ScaleDriver
from tpumr.scale.scenario import (BUILTIN_SCENARIOS, ScenarioError,
                                  ScenarioRunner, list_scenarios,
                                  load_spec, plan, run_named,
                                  validate_spec)
from tpumr.scale.simdfs import SimDFSClient, SimDFSFleet
from tpumr.scale.simtracker import SimFleet, SimTracker

__all__ = ["BUILTIN_SCENARIOS", "ScaleDriver", "ScenarioError",
           "ScenarioRunner", "SimDFSClient", "SimDFSFleet", "SimFleet",
           "SimTracker", "list_scenarios", "load_spec", "plan",
           "run_named", "validate_spec"]
