"""Multi-host (DCN) bring-up executed for real: TWO separate processes
join one jax.distributed job through parallel/multihost.ensure_initialized
and run a cross-process collective over the global mesh (SURVEY.md §5
distributed-comm TPU-native equivalent — here on CPU devices, both
processes on one machine, which exercises the identical code path the
DCN deployment uses)."""

import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = """
import os, sys
sys.path.insert(0, {repo!r})
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
rank = int(sys.argv[1]); coord = sys.argv[2]
import jax
jax.config.update("jax_platforms", "cpu")
from tpumr.mapred.jobconf import JobConf
from tpumr.parallel import multihost
conf = JobConf()
conf.set("tpumr.distributed.coordinator", coord)
conf.set("tpumr.distributed.num.processes", 2)
conf.set("tpumr.distributed.process.id", rank)
assert multihost.ensure_initialized(conf) is True
pi, pc = multihost.process_info()
assert (pi, pc) == (rank, 2), (pi, pc)
mesh = multihost.global_mesh(conf)
assert len(mesh.devices.flatten()) == 4, mesh
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from tpumr.parallel import collectives
local = np.array([rank * 2 + 0.0, rank * 2 + 1.0], dtype=np.float32)
garr = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("data")), local, (4,))
out = jax.jit(jax.shard_map(lambda x: collectives.psum(x, "data"),
                        mesh=mesh, in_specs=P("data"), out_specs=P()))(garr)
total = float(np.asarray(jax.device_get(out))[0])
assert total == 6.0, total
print("RANK%d OK" % rank, flush=True)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_distributed_bringup():
    """ensure_initialized + global_mesh + a psum spanning two OS
    processes: the full DCN code path (jax.distributed coordinator,
    cross-process collective) actually executes."""
    prog = WORKER.format(repo=REPO)
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)   # workers set their own device count
    procs = [subprocess.Popen([sys.executable, "-c", prog, str(r), coord],
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
             for r in (0, 1)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=200)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail(f"distributed bring-up hung; partial: {outs}")
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank{r} failed:\n{out[-2000:]}"
        assert f"RANK{r} OK" in out
