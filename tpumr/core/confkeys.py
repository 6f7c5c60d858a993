"""The configuration-key registry — single source of truth.

Every dotted config key the tree reads is declared here once: key,
type, default, one doc line. ``tpumr lint`` (tpumr/tools/tpulint)
enforces the contract repo-wide: reads of unregistered ``tpumr.*`` /
``mapred.*`` / ``io.*`` keys fail the build, literal call-site
defaults that contradict this file fail the build, and registered keys
nothing reads fail the build. ``tpumr lint --conf-doc`` generates
``docs/CONFIG.md`` from this table, so the operator reference can
never drift from the code.

Keys read through f-strings (``f"tpumr.fi.{point}.probability"``)
register as PATTERN entries whose ``*`` spans any characters
(including dots).

The typed readers at the bottom (:func:`get_int` et al.) read a key
with its registered type and default — the adoption surface for
modules that used to carry their own fallback literals. A call site
may still pass a literal default, but the linter insists it equals the
registered one.
"""

from __future__ import annotations

from dataclasses import dataclass
from fnmatch import fnmatchcase
from typing import Any


@dataclass(frozen=True)
class ConfKey:
    key: str
    type: str            # str | int | float | bool | strings | size | class
    default: Any
    doc: str
    pattern: bool = False


def _K(key: str, type: str, default: Any, doc: str,
       pattern: bool = False) -> ConfKey:
    return ConfKey(key, type, default, doc, pattern)


_ENTRIES: "tuple[ConfKey, ...]" = (
    _K('datajoin.maxNumOfValuesPerGroup', 'int', 100,
        "contrib/datajoin: max values buffered per join group."),
    _K('dfs.block.size', 'int', 8388608,
        "tdfs block size, bytes."),
    _K('dfs.hosts', 'str', None,
        "Datanode include file (empty = all may join)."),
    _K('dfs.hosts.exclude', 'str', None,
        "Datanode exclude/decommission file."),
    _K('dfs.permissions', 'bool', True,
        "Enforce tdfs permission checks."),
    _K('dfs.permissions.supergroup', 'str', 'supergroup',
        "Group granted tdfs superuser rights."),
    _K('dfs.replication', 'int', 3,
        "Default tdfs replication factor."),
    _K('dfs.safemode.threshold.pct', 'float', 0.999,
        "Fraction of blocks that must report before the NameNode leaves "
        "safemode."),
    _K('failmon.disk.paths', 'strings', None,
        "Disks failmon monitors."),
    _K('failmon.log.files', 'strings', None,
        "Log files failmon scrapes."),
    _K('failmon.store.dir', 'str', None,
        "failmon local event store directory."),
    _K('failmon.upload.url', 'str', None,
        "failmon upload destination."),
    _K('fs.checkpoint.period', 'int', 3600,
        "SecondaryNameNode checkpoint interval, seconds."),
    _K('fs.default.name', 'str', 'file:///',
        "Default filesystem URI for relative paths (tdfs://HOST:PORT/ "
        "or file:///)."),
    _K('fs.gs.auth.token', 'str', None,
        "Static bearer token for the gs:// object-store client."),
    _K('fs.gs.emulation.dir', 'str', None,
        "Local directory backing the gs:// emulation filesystem."),
    _K('fs.gs.endpoint', 'str', None,
        "Override endpoint URL for gs:// (emulators, proxies)."),
    _K('fs.trash.checkpoint.interval.s', 'str', None,
        "NameNode-side trash checkpoint sweep period, seconds."),
    _K('fs.trash.interval', 'int', 0,
        "Minutes between trash checkpoints; 0 disables the trash "
        "(deletes are immediate)."),
    _K('fs.trash.root', 'str', None,
        "Override for the per-user trash root directory."),
    _K('hadoop.security.groups.cache.secs', 'int', 300,
        "User->groups resolution cache TTL, seconds."),
    _K('io.sort.factor', 'int', 10,
        "Maximum segments merged per merge pass (map spills and reduce "
        "merges)."),
    _K('io.sort.mb', 'int', 100,
        "Map-side sort buffer size, MiB (spills past it)."),
    _K('io.sort.spill.percent', 'float', 0.8,
        "Sort-buffer fill fraction that triggers a background spill."),
    _K('key.value.separator.in.input.line', 'str', '\t',
        "KeyValueTextInputFormat separator between key and value."),
    _K('map.output.key.field.separator', 'str', '\t',
        "KeyFieldBasedPartitioner/Comparator field separator."),
    _K('mapred.acls.enabled', 'bool', False,
        "Enforce queue/job ACLs."),
    _K('mapred.cache.files', 'str', '',
        "Distributed-cache file URIs shipped to tasks."),
    _K('mapred.cluster.administrators', 'str', '',
        "Cluster admin ACL (user/group list)."),
    _K('mapred.combiner.class', 'class', None,
        "Combiner class (dotted name)."),
    _K('mapred.compress.map.output', 'bool', False,
        "Compress intermediate map output."),
    _K('mapred.data.field.separator', 'str', '\t',
        "FieldSelection mapper/reducer field separator."),
    _K('mapred.fairscheduler.pool', 'str', None,
        "Fair-scheduler pool this job lands in."),
    _K('mapred.healthChecker.interval.ms', 'int', 10000,
        "Node-health script period, ms."),
    _K('mapred.healthChecker.script.path', 'str', None,
        "Node-health script path (unset = health checks off)."),
    _K('mapred.hosts', 'str', None,
        "Tracker include file (empty = all may join); live-reloadable "
        "via mradmin -refreshNodes."),
    _K('mapred.hosts.exclude', 'str', None,
        "Tracker exclude file; excluded trackers are evicted on "
        "refresh."),
    _K('mapred.input.dir', 'strings', None,
        "Comma-separated input paths."),
    _K('mapred.input.format.class', 'class', None,
        "InputFormat class (dotted name)."),
    _K('mapred.job.map.memory.mb', 'int', 0,
        "Per-map memory demand for the memory-aware scheduler gate, "
        "MiB."),
    _K('mapred.job.name', 'str', '',
        "Human-readable job name (history, status pages)."),
    _K('mapred.job.priority', 'str', 'NORMAL',
        "Initial job priority (VERY_HIGH..VERY_LOW)."),
    _K('mapred.job.queue.name', 'str', None,
        "Queue the job is submitted to."),
    _K('mapred.job.reduce.memory.mb', 'int', 0,
        "Per-reduce memory demand for the memory-aware scheduler gate, "
        "MiB."),
    _K('mapred.job.shuffle.input.buffer.percent', 'float', 0.7,
        "Fraction of the RAM budget map outputs may fill."),
    _K('mapred.job.shuffle.merge.percent', 'float', 0.66,
        "Fill fraction that triggers an in-memory merge."),
    _K('mapred.job.tracker', 'str', None,
        "JobTracker address HOST:PORT, or 'local' for the in-process "
        "runner."),
    _K('mapred.job.tracker.http.port', 'int', -1,
        "JobTracker status HTTP port (-1 = auto)."),
    _K('mapred.jobtracker.restart.recover', 'bool', False,
        "Replay completed work from the history log on master restart."),
    _K('mapred.jobtracker.restart.recovery.grace.ms', 'int', 3000,
        "Hold a recovered job's scheduling until its trackers re-join, "
        "ms."),
    _K('mapred.jobtracker.taskScheduler', 'class', None,
        "TaskScheduler class the master loads."),
    _K('mapred.line.input.format.linespermap', 'int', 1,
        "NLineInputFormat: lines per split."),
    _K('mapred.local.dir', 'str', None,
        "Tracker-local scratch directory."),
    _K('mapred.local.map.tasks.maximum', 'int', 1,
        "Local-runner parallel map width."),
    _K('mapred.map.max.attempts', 'int', 4,
        "Attempts per map task before the job fails."),
    _K('mapred.map.multithreadedrunner.threads', 'int', 10,
        "MultithreadedMapRunner thread count."),
    _K('mapred.map.output.compression.codec', 'str', 'zlib',
        "Map-output (shuffle/spill) codec; native tlz is the hot-path "
        "choice."),
    _K('mapred.map.runner.class', 'class', None,
        "MapRunner class driving the map loop on CPU."),
    _K('mapred.map.runner.tpu.class', 'class', None,
        "MapRunner class driving the map loop on the TPU pass."),
    _K('mapred.map.tasks', 'int', 1,
        "Requested number of map tasks (input splits may override)."),
    _K('mapred.mapper.class', 'class', None,
        "Mapper class (dotted name)."),
    _K('mapred.mapper.regex', 'str', '',
        "Regex for the built-in grep mapper."),
    _K('mapred.mapper.regex.group', 'int', 0,
        "Capture group the grep mapper emits."),
    _K('mapred.max.fetch.failures.per.map', 'int', 3,
        "Distinct reducers reporting fetch failure before a map "
        "re-executes."),
    _K('mapred.max.split.size', 'int', 2**63 - 1,
        "Upper bound on input split size, bytes (2**63-1 = uncapped; "
        "CombineFileInputFormat treats it as its pack-target sentinel)."),
    _K('mapred.min.split.size', 'int', 1,
        "Lower bound on input split size, bytes."),
    _K('mapred.output.compress', 'bool', False,
        "Compress job output files."),
    _K('mapred.output.compression.codec', 'str', 'none',
        "Job output compression codec (none/zlib/tlz)."),
    _K('mapred.output.dir', 'str', None,
        "Job output directory."),
    _K('mapred.output.format.class', 'class', None,
        "OutputFormat class (dotted name)."),
    _K('mapred.output.key.comparator.class', 'class', None,
        "Sort comparator for map output keys."),
    _K('mapred.output.value.groupfn.class', 'class', None,
        "Grouping comparator for the reduce phase."),
    _K('mapred.partitioner.class', 'class', None,
        "Partitioner class (dotted name)."),
    _K('mapred.queue.acls.file', 'str', None,
        "Queue ACLs file, live-reloadable via mradmin -refreshQueues."),
    _K('mapred.queue.names', 'str', None,
        "Configured queue names (unset = single 'default')."),
    _K('mapred.reduce.max.attempts', 'int', 4,
        "Attempts per reduce task before the job fails."),
    _K('mapred.reduce.slowstart.completed.maps', 'float', 0.05,
        "Map completion fraction before reduces schedule."),
    _K('mapred.reduce.speculative.execution', 'bool', None,
        "Reduce-side speculation override (unset = master switch)."),
    _K('mapred.reduce.tasks', 'int', 1,
        "Number of reduce tasks (0 = map-only job)."),
    _K('mapred.reducer.class', 'class', None,
        "Reducer class (dotted name)."),
    _K('mapred.speculative.execution', 'bool', True,
        "Speculative execution master switch."),
    _K('mapred.speculative.lag.factor', 'float', 1.5,
        "How far behind the mean a task must run to speculate."),
    _K('mapred.speculative.min.runtime.s', 'float', 10.0,
        "Minimum runtime before a task may be speculated, seconds."),
    _K('mapred.task.limit.maxrss.mb', 'int', 0,
        "Process-isolation RSS kill limit, MiB (0 = off)."),
    _K('mapred.task.profile', 'bool', False,
        "Enable the per-task cProfile profiler."),
    _K('mapred.task.timeout', 'int', 600000,
        "Ms without task progress before the tracker reaps the attempt."),
    _K('mapred.task.tracker.http.port', 'int', -1,
        "Tracker status/shuffle HTTP port (-1 = auto)."),
    _K('mapred.task.tracker.task-controller', 'str', None,
        "Task controller: thread/process isolation backend."),
    _K('mapred.tasktracker.map.cpu.tasks.maximum', 'int', 3,
        "CPU map slots per tracker (the Shirahata hybrid split)."),
    _K('mapred.tasktracker.map.tpu.tasks.maximum', 'int', 1,
        "TPU map slots per tracker (one chip = one slot)."),
    _K('mapred.tasktracker.memory.mb', 'int', -1,
        "Tracker-advertised memory for the scheduler gate, MiB (-1 = "
        "unadvertised)."),
    _K('mapred.tasktracker.reduce.tasks.maximum', 'int', 2,
        "Reduce slots per tracker."),
    _K('mapred.text.key.comparator.options', 'str', '',
        "KeyFieldBasedComparator sort options (-k, -n, -r)."),
    _K('mapred.text.key.value.fields.spec', 'str', '0:1-',
        "FieldSelection key:value field spec."),
    _K('mapred.textoutputformat.separator', 'str', '\t',
        "TextOutputFormat key/value separator."),
    _K('mapred.userlog.retain.hours', 'float', 24.0,
        "Hours task userlogs are retained."),
    _K('mapreduce.mapper.multithreadedmapper.class', 'class', None,
        "New-API multithreaded mapper delegate class."),
    _K('mapreduce.mapper.multithreadedmapper.threads', 'int', 10,
        "New-API multithreaded mapper thread count."),
    _K('mapreduce.mapper.regex', 'str', None,
        "New-API alias of mapred.mapper.regex."),
    _K('mapreduce.mapper.regex.group', 'str', None,
        "New-API alias of mapred.mapper.regex.group."),
    _K('mapreduce.output.lazyoutputformat.outputformat', 'class', None,
        "LazyOutputFormat delegate class."),
    _K('stream.combine.command', 'str', None,
        "Streaming combiner command line."),
    _K('stream.map.command', 'str', None,
        "Streaming map command line."),
    _K('stream.map.input', 'str', 'text',
        "Streaming map input serialization (text/typedbytes)."),
    _K('stream.map.input.ignoreKey', 'bool', False,
        "Feed only values to the map command."),
    _K('stream.map.output', 'str', 'text',
        "Streaming map output serialization."),
    _K('stream.map.output.field.separator', 'str', '\t',
        "Streaming map output field separator."),
    _K('stream.reduce.command', 'str', None,
        "Streaming reduce command line."),
    _K('stream.reduce.input', 'str', 'text',
        "Streaming reduce input serialization."),
    _K('stream.reduce.output', 'str', 'text',
        "Streaming reduce output serialization."),
    _K('tdfs.client.dn.conns', 'int', 2,
        "Pooled connections per datanode in the client's shared "
        "RPC pool."),
    _K('tdfs.client.dn.idle.s', 'float', 60.0,
        "Seconds an idle pooled datanode connection survives before "
        "the pool closes it."),
    _K('tdfs.client.nn.backoff.ms', 'float', 200.0,
        "Base backoff between NameNode RPC transport retries, ms "
        "(jittered exponential)."),
    _K('tdfs.client.nn.retries', 'int', 1,
        "NameNode RPC transport retries per call — what carries a "
        "client across a NameNode restart (resends replay from the "
        "server response cache, never re-execute)."),
    _K('tdfs.client.read.acquire.retries', 'int', 3,
        "Block-location refetches a reader attempts when every cached "
        "replica fails or the location list is empty (a restarted "
        "NameNode re-learning its datanodes) before giving up — "
        "HDFS's dfs.client.max.block.acquire.failures."),
    _K('tdfs.client.read.acquire.backoff.ms', 'float', 300.0,
        "Pause before each block-location refetch, giving datanodes "
        "a heartbeat window to re-register with a restarted "
        "NameNode."),
    _K('tdfs.client.read.chunk.bytes', 'str', None,
        "Client read chunk size, bytes."),
    _K('tdfs.client.read.pipeline.depth', 'int', 4,
        "Chunk reads kept in flight per replica connection "
        "(pipelined read window)."),
    _K('tdfs.client.write.chunk.bytes', 'str', None,
        "Client write chunk size, bytes."),
    _K('tdfs.client.write.pipeline.depth', 'int', 4,
        "Chunk writes kept in flight while shipping a block "
        "(pipelined write window)."),
    _K('tdfs.datanode.capacity', 'int', 1099511627776,
        "Advertised datanode capacity, bytes."),
    _K('tdfs.datanode.fdcache.capacity', 'int', 64,
        "Open block-file descriptors the datanode read path caches "
        "(pinned LRU)."),
    _K('tdfs.datanode.expiry.s', 'int', 10,
        "Seconds without a heartbeat before a datanode is declared "
        "dead."),
    _K('tdfs.datanode.heartbeat.s', 'float', 1.0,
        "Datanode -> NameNode heartbeat period, seconds."),
    _K('tdfs.datanode.scan.period.s', 'str', None,
        "Block-scanner (checksum verification) full-cycle period, "
        "seconds."),
    _K('tdfs.edits.auto.checkpoint.mb', 'int', 256,
        "Edit-log volume that triggers a self-checkpoint, MiB."),
    _K('tdfs.edits.segment.mb', 'int', 16,
        "Edit-log segment roll size, MiB."),
    _K('tdfs.hotblocks.cool.s', 'float', 15.0,
        "Seconds a block must stay below the hot threshold before "
        "its replica boost expires (cool-down)."),
    _K('tdfs.hotblocks.replicate.cap', 'int', 4,
        "Max replicas the hot-block policy will boost a block to "
        "(bounded by live datanodes)."),
    _K('tdfs.hotblocks.replicate.min.reads', 'int', 200,
        "Minimum sketched reads a block needs before the hot-block "
        "policy considers boosting it."),
    _K('tdfs.hotblocks.replicate.share', 'float', 0.3,
        "Share of all sketched reads at which a block is declared "
        "hot and gets extra replicas."),
    _K('tdfs.http.port', 'int', -1,
        "NameNode status HTTP port (-1 = auto)."),
    _K('tdfs.lease.hard.limit.s', 'int', 60,
        "Write-lease hard expiry, seconds (lease recovery fences dead "
        "writers)."),
    _K('tdfs.namenode.lock.stripe.depth', 'int', 2,
        "Path components that pick a namespace lock stripe; shorter "
        "paths use the structural lock."),
    _K('tdfs.namenode.lock.stripes', 'int', 8,
        "Namespace lock stripes (per-subtree locks); cross-stripe "
        "ops take the structural lock."),
    _K('tdfs.read.wire.codec', 'str', 'tlz',
        "Wire compression codec for chunked block reads "
        "('none' disables)."),
    _K('tdfs.replication.interval.s', 'float', 1.0,
        "NameNode re-replication monitor period, seconds."),
    _K('tdfs.superuser', 'str', '',
        "Extra tdfs superuser principal."),
    _K('tdfs.upload.stale.s', 'int', 600,
        "Seconds before a half-uploaded block replica is "
        "garbage-collected."),
    _K('tdfsproxy.permissions.file', 'str', None,
        "tdfsproxy per-path permissions file."),
    _K('tdfsproxy.ssl.cert', 'str', None,
        "tdfsproxy TLS certificate file."),
    _K('tdfsproxy.ssl.key', 'str', None,
        "tdfsproxy TLS key file."),
    _K('topology.script.file.name', 'str', None,
        "Executable resolving host -> rack for topology-aware "
        "placement."),
    _K('total.order.partitioner.path', 'str', None,
        "Partition-boundary keys file for the total-order partitioner."),
    _K('tpumr.acls.require.verified', 'bool', False,
        "Reject unsigned callers once ACLs are on."),
    _K('tpumr.block.access.lifetime.s', 'float', 3600.0,
        "NameNode-minted block access stamp lifetime, seconds."),
    _K('tpumr.brownout.cadence.factor', 'float', 3.0,
        "Brownout heartbeat-cadence stretch multiplier while the "
        "'cadence' shed step is active (capped at the instructed max)."),
    _K('tpumr.brownout.dwell.ms', 'int', 3000,
        "Min ms between brownout level transitions — one step per "
        "dwell, so shedding ramps instead of slamming."),
    _K('tpumr.brownout.enabled', 'bool', False,
        "Master brownout mode: under sustained SLO pressure the master "
        "sheds deferrable load in ranked steps (trace sampling -> "
        "heartbeat cadence -> speculation + history I/O)."),
    _K('tpumr.brownout.engage.ticks', 'int', 3,
        "Consecutive breached flight-recorder windows before the "
        "brownout steps up one level."),
    _K('tpumr.brownout.release.ticks', 'int', 3,
        "Consecutive clear flight-recorder windows before the brownout "
        "steps back down one level."),
    _K('tpumr.cache.dir', 'str', None,
        "Distributed-cache local materialization root."),
    _K('tpumr.cache.executables', 'str', '',
        "Distributed-cache entries to mark executable."),
    _K('tpumr.capacity.queues', 'str', 'default',
        "Capacity scheduler: configured queues."),
    _K('tpumr.capacity.supports-priority', 'bool', False,
        "Capacity scheduler: honor job priority."),
    _K('tpumr.chain.reduce.mappers', 'str', None,
        "ChainReducer: post-reduce mapper chain."),
    _K('tpumr.chain.reducer', 'str', None,
        "ChainReducer: the wrapped reducer."),
    _K('tpumr.cpu.batch.map', 'bool', True,
        "Vectorized CPU batch path for kernel maps."),
    _K('tpumr.datajoin.mappers', 'str', None,
        "datajoin: per-source mapper class list."),
    _K('tpumr.db.connect', 'str', None,
        "DB input/output: connection string."),
    _K('tpumr.db.input.count.query', 'str', None,
        "DB input: row-count query."),
    _K('tpumr.db.input.fields', 'str', None,
        "DB input: selected fields."),
    _K('tpumr.db.input.order.by', 'str', None,
        "DB input: split ordering column."),
    _K('tpumr.db.input.query', 'str', None,
        "DB input: explicit query."),
    _K('tpumr.db.input.table', 'str', None,
        "DB input: table name."),
    _K('tpumr.db.module', 'str', 'sqlite3',
        "DB input/output: DB-API module name."),
    _K('tpumr.db.output.fields', 'str', None,
        "DB output: inserted fields."),
    _K('tpumr.db.output.table', 'str', None,
        "DB output: table name."),
    _K('tpumr.dense.split.rows', 'int', 0,
        "Dense-tensor input format: rows per split (0 = one split)."),
    _K('tpumr.devcache.heartbeat.tags', 'int', 32,
        "Max device-cache tags a tracker piggybacks per heartbeat for "
        "affinity placement (0 = don't advertise)."),
    _K('tpumr.devcache.required.tags', 'str', '',
        "Comma list of device-cache tags this job's tasks want warm "
        "(empty = derived from the job's known side inputs)."),
    _K('tpumr.dfs.bench.op.slo.ms', 'int', 100,
        "simulate -dfs: NameNode op-latency p99 SLO (merged "
        "nn_op_seconds) a rung must hold to pass, ms."),
    _K('tpumr.dfs.bench.read.slo.ms', 'int', 250,
        "simulate -dfs: client-side end-to-end read round-trip p99 SLO "
        "a rung must hold to pass, ms."),
    _K('tpumr.distcp.preserve', 'bool', False,
        "distcp: preserve file attributes."),
    _K('tpumr.distcp.update', 'bool', False,
        "distcp: skip up-to-date targets."),
    _K('tpumr.distcp.work', 'str', None,
        "distcp work/staging directory."),
    _K('tpumr.dn.hotblocks.halflife.s', 'float', 60.0,
        "Half-life of the datanode read sketch's per-heartbeat "
        "exponential decay, seconds (0 disables; keeps the hot-block "
        "view current so replica boosts can cool down)."),
    _K('tpumr.dn.hotblocks.k', 'int', 64,
        "SpaceSaving counters per datanode read sketch (bounds hot-"
        "block memory; any block read more than total/k times is "
        "guaranteed tracked)."),
    _K('tpumr.dn.hotblocks.top', 'int', 16,
        "Top sketch entries a datanode piggybacks per heartbeat into "
        "the namenode's cluster hot-block table."),
    _K('tpumr.dn.http.port', 'int', -1,
        "DataNode status/metrics HTTP port (0 = ephemeral, -1 = off)."),
    _K('tpumr.fairscheduler.preemption', 'bool', False,
        "Fair scheduler: enable preemption."),
    _K('tpumr.fairscheduler.preemption.interval.ms', 'int', 1000,
        "Fair scheduler: preemption check period, ms."),
    _K('tpumr.fairscheduler.preemption.timeout.ms', 'int', 15000,
        "Fair scheduler: starvation window before preempting, ms."),
    _K('tpumr.fi.dn.partition.ms', 'int', 3000,
        "Ms the dn.partition fault seam silences a DataNode's "
        "heartbeats (reads keep serving; NN expiry + rejoin follow)."),
    _K('tpumr.fi.jt.heartbeat.slow.ms', 'int', 400,
        "Ms the jt.heartbeat.slow fault seam stalls master heartbeat "
        "handling (drives the flight-recorder incident e2e)."),
    _K('tpumr.fi.nn.op.slow.ms', 'int', 400,
        "Ms the nn.op.slow fault seam stalls NameNode op handling "
        "(drives the NN flight-recorder incident e2e)."),
    _K('tpumr.fi.rpc.delay.ms', 'int', 100,
        "Ms the rpc.delay fault seam stalls a call."),
    _K('tpumr.fi.seed', 'str', None,
        "Fault-injection RNG seed (per-(seed,point) streams; chaos runs "
        "replay deterministically)."),
    _K('tpumr.fi.task.slow.ms', 'int', 2000,
        "Ms the task.slow fault seam crawls before the real work runs."),
    _K('tpumr.grep.group', 'int', 0,
        "Grep example: capture group."),
    _K('tpumr.grep.pattern', 'str', None,
        "Grep example: regex."),
    _K('tpumr.heartbeat.batch', 'int', 0,
        "Max co-located tracker beats coalesced into one heartbeat_batch "
        "RPC by the scale fleet (0/1 = one pipelined RPC per beat). "
        "Replay semantics hold per member — a resent batch never "
        "double-folds a tracker."),
    _K('tpumr.heartbeat.beats.per.second', 'int', 0,
        "Target master-wide beat rate for adaptive cadence (0 = fixed "
        "cadence)."),
    _K('tpumr.heartbeat.delta', 'bool', True,
        "Delta-encode heartbeats (only changed statuses ride the wire)."),
    _K('tpumr.heartbeat.interval.max.ms', 'int', 0,
        "Adaptive-cadence staleness cap, ms (0 = uncapped)."),
    _K('tpumr.heartbeat.interval.ms', 'int', 1000,
        "Tracker heartbeat cadence floor, ms."),
    _K('tpumr.heartbeat.lostmaster.backoff.max.ms', 'int', 15000,
        "Cap on the tracker's lost-master heartbeat backoff, ms."),
    _K('tpumr.history.async', 'bool', True,
        "Write job-history events from a bounded background queue "
        "instead of on the heartbeat's deferred phase (readers flush "
        "first, so recovery and retired-status reads stay exact)."),
    _K('tpumr.history.dir', 'str', None,
        "Job history directory (events, per-job metrics rollups, "
        "traces)."),
    _K('tpumr.history.queue.max', 'int', 10000,
        "Bound on queued history events before new ones are dropped and "
        "counted in history_writes_dropped (must stay 0 in bench runs)."),
    _K('tpumr.jax.cache.dir', 'str', None,
        "JAX persistent compilation cache directory; 'none' disables. "
        "Unset: <checkout>/.jax_cache. Ignored where "
        "JAX_COMPILATION_CACHE_DIR is set in the environment, which JAX "
        "reads itself."),
    _K('tpumr.jax.cache.min.compile.secs', 'float', 0.5,
        "Min compile time before an executable is persisted, seconds."),
    _K('tpumr.job.id', 'str', '',
        "This job's id (framework-set, task-side)."),
    _K('tpumr.jobclient.rpc.retries', 'int', 3,
        "Transport retries for the job submit/poll client channel "
        "(wider than the daemon default: wait_for_completion must "
        "survive master restarts)."),
    _K('tpumr.jobtracker.rpc.reactor', 'bool', True,
        "Serve master RPC on the shared reactor (vs "
        "thread-per-connection)."),
    _K('tpumr.kmeans.centroids', 'str', None,
        "KMeans op: serialized centroids."),
    _K('tpumr.kmeans.centroids.out', 'str', None,
        "KMeans iterative driver: where the centroid-update reducer "
        "writes the NEXT round's centroid .npy (round-templated in "
        "pipelines, so rounds never rewrite one path)."),
    _K('tpumr.kmeans.use.pallas', 'bool', False,
        "KMeans op: use the Pallas kernel."),
    _K('tpumr.local.run.on.tpu', 'bool', False,
        "Local runner executes the TPU pass too."),
    _K('tpumr.map.kernel', 'str', None,
        "Registered TPU map kernel name (ops registry)."),
    _K('tpumr.mapreduce.mapper.class', 'class', None,
        "New-API mapper class bridge key."),
    _K('tpumr.mapreduce.partitioner.class', 'class', None,
        "New-API partitioner class bridge key."),
    _K('tpumr.matmul.b', 'str', None,
        "Matmul op: serialized B operand."),
    _K('tpumr.matmul.bf16', 'bool', True,
        "Matmul op: compute in bf16."),
    _K('tpumr.metrics.file', 'str', None,
        "File sink path for metrics records."),
    _K('tpumr.metrics.period.ms', 'int', 10000,
        "Metrics publish period, ms."),
    _K('tpumr.metrics.piggyback.interval.ms', 'int', 0,
        "Min ms between tracker metrics piggybacks on heartbeats (0 = "
        "every beat)."),
    _K('tpumr.metrics.udp', 'str', None,
        "UDP sink HOST:PORT for metrics records."),
    _K('tpumr.nn.audit.enabled', 'bool', False,
        "NameNode audit log (logger 'tpumr.nn.audit'): one line per "
        "mutating/metadata op with caller, cmd, src, dst, perm."),
    _K('tpumr.nn.audit.rate.limit', 'int', 200,
        "Max audit lines per second; the overflow is counted "
        "(audit_suppressed) instead of written, so an op storm can't "
        "turn the audit log into the bottleneck."),
    _K('tpumr.nn.incident.slo.ms', 'int', 0,
        "NameNode flight-recorder SLO: a windowed nn_op_seconds p99 "
        "over this arms an incident snapshot (0 = recorder off)."),
    _K('tpumr.ops.device.cache.mb', 'int', 1024,
        "Ops-level device cache budget, MiB."),
    _K('tpumr.pipeline.conf.hooks.allowed', 'strings', 'tpumr.',
        "Dotted-prefix allowlist for pipeline conf_hook callables — "
        "hooks run IN THE MASTER PROCESS, so only operator-vetted "
        "module prefixes may execute (default: the tpumr tree)."),
    _K('tpumr.pipeline.handoff.dir', 'str', None,
        "Tracker-local root for streamed-handoff reduce spills (set by "
        "the tracker; outlives job cleanup until the pipeline ends)."),
    _K('tpumr.pipeline.handoff.poll.ms', 'int', 200,
        "Downstream handoff reader poll period, ms (event feed + DFS "
        "fallback probes)."),
    _K('tpumr.pipeline.handoff.source', 'str', None,
        "INTERNAL in-process seam: the tracker's handoff stream-source "
        "factory object, stashed in the stage conf for thread-isolated "
        "maps (never serialized; absent = DFS fallback only)."),
    _K('tpumr.pipeline.handoff.timeout.ms', 'int', 600000,
        "Bound on a downstream map waiting for one upstream partition "
        "(stream or committed fallback) before the attempt fails."),
    _K('tpumr.pipeline.handoff.upstream', 'str', None,
        "Stage conf: JSON list of upstream job ids a streamed stage "
        "fetches from (stamped by the pipeline engine)."),
    _K('tpumr.pipeline.id', 'str', None,
        "Stage conf: the owning pipeline id (stamped by the engine; "
        "anchors scheduler ordering and trace parenting)."),
    _K('tpumr.pipeline.node', 'str', None,
        "Stage conf: the owning graph node id (stamped by the engine)."),
    _K('tpumr.pipeline.round', 'int', 0,
        "Stage conf: loop-node round number (stamped by the engine)."),
    _K('tpumr.pipeline.stream.handoff', 'bool', False,
        "Stage conf: tee this stage's reduce output into map-output "
        "(IFile) framing served over the shuffle wire for downstream "
        "stages (set by the engine on stream out-edges)."),
    _K('tpumr.pipes.executable', 'str', None,
        "Pipes binary URI."),
    _K('tpumr.pipes.piped.input', 'bool', True,
        "Feed pipes input over stdin (vs the application pulling)."),
    _K('tpumr.pipes.tpu.executable', 'str', None,
        "Pipes binary for the TPU pass."),
    _K('tpumr.policy.file', 'str', None,
        "Service-level authorization policy file."),
    _K('tpumr.prof.enabled', 'bool', False,
        "Continuous profiler master switch: stack sampling, cpu_share "
        "subsystem attribution, gil_delay_seconds, /stacks + /flame."),
    _K('tpumr.prof.hz', 'int', 19,
        "Profiler sampling rate (Hz); co-prime with common timer grids "
        "so periodic work cannot hide between samples."),
    _K('tpumr.prof.incident.cooldown.ms', 'int', 60000,
        "Min ms between flight-recorder incident bundles — a sustained "
        "breach writes one bundle per window, not a stream."),
    _K('tpumr.prof.incident.dir', 'str', None,
        "Flight-recorder bundle directory (default: an incidents/ dir "
        "next to the job history)."),
    _K('tpumr.prof.incident.slo.ms', 'int', 250,
        "Windowed heartbeat p99 (handling or lag) above this arms the "
        "flight recorder (the master's dual-p99 SLO)."),
    _K('tpumr.prof.trie.max.nodes', 'int', 20000,
        "Profiler stack-trie node budget; overflow folds into (other) "
        "so profiler memory stays bounded."),
    _K('tpumr.prof.window.s', 'float', 120.0,
        "Profiler sample-retention window for /stacks?seconds= queries "
        "and the cpu_share gauges."),
    _K('tpumr.profile.ewma', 'float', 0.0,
        "EWMA weight for the job's TPU acceleration profile (0 = plain "
        "mean)."),
    _K('tpumr.randomwriter.max.key', 'int', 100,
        "RandomWriter: max key size, bytes."),
    _K('tpumr.randomwriter.max.value', 'int', 1000,
        "RandomWriter: max value size, bytes."),
    _K('tpumr.randomwriter.min.key', 'int', 10,
        "RandomWriter: min key size, bytes."),
    _K('tpumr.randomwriter.min.value', 'int', 0,
        "RandomWriter: min value size, bytes."),
    _K('tpumr.reduce.kernel', 'str', None,
        "Registered reduce kernel name (ops registry): the reducer of a "
        "device-shuffled job, run on the device where the rows were "
        "sorted."),
    _K('tpumr.rpc.client.backoff.ms', 'int', 200,
        "Base jittered backoff between RPC transport retries, ms."),
    _K('tpumr.rpc.client.retries', 'int', 1,
        "Transport retries per daemon RPC call (trackers lean on the "
        "lost-master backoff instead)."),
    _K('tpumr.rpc.secret', 'str', None,
        "Cluster RPC secret (inline; prefer the .file form)."),
    _K('tpumr.rpc.secret.file', 'str', None,
        "File holding the cluster RPC secret."),
    _K('tpumr.rpc.token.file', 'str', None,
        "Delegation-token credential file."),
    _K('tpumr.rpc.user.key', 'str', None,
        "Per-user signing key (hex) for personal-credential RPC."),
    _K('tpumr.rpc.user.key.file', 'str', None,
        "File holding the per-user signing key."),
    _K('tpumr.scheduler.affinity', 'bool', True,
        "Prefer TPU slots on trackers whose device cache already holds "
        "the job's side-input tags."),
    _K('tpumr.scheduler.affinity.defer.passes', 'int', 3,
        "Heartbeats a job's TPU assignment may be deferred waiting for "
        "a tag-warm tracker before placing cold (0 = never defer)."),
    _K('tpumr.scenario.class', 'str', None,
        "Traffic class tag on a submitted job (scenario lab): keys the "
        "per-class latency percentiles and SLO verdicts."),
    _K('tpumr.scenario.dir', 'str', None,
        "Directory of operator-authored *.toml scenario specs for "
        "'tpumr scenario -list' / 'tpumr simulate -scenario'."),
    _K('tpumr.scenario.name', 'str', None,
        "Active scenario name on the master; stamped into flight-"
        "recorder incident bundles as workload context."),
    _K('tpumr.security.authorization', 'bool', False,
        "Service-level authorization (policy file) master switch."),
    _K('tpumr.shuffle.batch.bytes', 'int', 8 << 20,
        "Total payload budget of one batched multi-segment fetch "
        "response, bytes."),
    _K('tpumr.shuffle.batch.segments', 'int', 8,
        "Max map outputs coalesced into one get_map_outputs_batch RPC "
        "(1 = per-segment fetches)."),
    _K('tpumr.shuffle.chunk.bytes', 'int', 1 << 20,
        "Serve-side chunking of map output reads, bytes."),
    _K('tpumr.shuffle.conns.per.target', 'int', 2,
        "Pooled shuffle connections per source tracker; fetchers "
        "multiplex over them instead of one socket each."),
    _K('tpumr.shuffle.copy.backoff.max.ms', 'float', 10000.0,
        "Penalty-box backoff cap, ms."),
    _K('tpumr.shuffle.copy.backoff.ms', 'float', 200.0,
        "Base per-source penalty-box backoff, ms (jittered, "
        "exponential)."),
    _K('tpumr.shuffle.copy.retries', 'int', 3,
        "Transport retries per fetch round."),
    _K('tpumr.shuffle.device', 'bool', False,
        "Stage shuffle through device memory (TPU-side partition/sort)."),
    _K('tpumr.shuffle.device.capacity', 'int', 0,
        "Device shuffle cache capacity, bytes (0 = auto)."),
    _K('tpumr.shuffle.device.key.bytes', 'int', 0,
        "Fixed key width for device shuffle records, bytes."),
    _K('tpumr.shuffle.device.ranges', 'int', 1,
        "Partition ranges per device sort pass."),
    _K('tpumr.shuffle.device.value.bytes', 'int', 0,
        "Fixed value width for device shuffle records, bytes."),
    _K('tpumr.shuffle.fd.cache.size', 'int', 64,
        "Open spill file descriptors the serving tracker caches (LRU) "
        "so chunk reads pread instead of open+seek per chunk."),
    _K('tpumr.shuffle.fetch.max.failures', 'int', 50,
        "Total fetch failures before the reduce attempt aborts."),
    _K('tpumr.shuffle.fetch.pipeline.depth', 'int', 4,
        "Chunk requests kept in flight per connection while streaming "
        "one segment (1 = one chunk per round trip)."),
    _K('tpumr.shuffle.fetch.retries.per.source', 'int', 3,
        "Fetch failures per map location before a report goes up the "
        "umbilical."),
    _K('tpumr.shuffle.merge.enabled', 'bool', True,
        "Background merge engine on the reduce side."),
    _K('tpumr.shuffle.merge.reserve.wait.ms', 'float', 2000.0,
        "Ms a fetch waits for merge headroom before spilling straight "
        "to disk."),
    _K('tpumr.shuffle.parallel.copies', 'int', 5,
        "Concurrent fetch streams per reduce."),
    _K('tpumr.shuffle.poll.ms', 'int', 200,
        "Completion-event poll period while the reduce waits for maps, "
        "ms."),
    _K('tpumr.shuffle.ram.mb', 'float', 128.0,
        "In-memory shuffle budget per reduce, MiB."),
    _K('tpumr.shuffle.size.priority', 'bool', True,
        "Order pending shuffle fetches largest-advertised-output first "
        "(completion events carry map output sizes)."),
    _K('tpumr.shuffle.timeout.ms', 'int', 600000,
        "Shuffle phase overall deadline, ms."),
    _K('tpumr.shuffle.wire.codec', 'str', 'tlz',
        "Wire compression for chunks of UNCOMPRESSED spills ('none' "
        "disables); decompressed copier-side inside the RAM budget."),
    _K('tpumr.sleep.hang.attempts', 'int', 1,
        "Sleep example: attempts that hang before succeeding."),
    _K('tpumr.sleep.hang.map', 'int', -1,
        "Sleep example: map index that hangs (-1 = none)."),
    _K('tpumr.sleep.map.ms', 'int', 100,
        "Sleep example: per-map sleep, ms."),
    _K('tpumr.sleep.reduce.ms', 'int', 100,
        "Sleep example: per-reduce sleep, ms."),
    _K('tpumr.speculative.cap', 'int', 2,
        "Max speculative attempts in flight per job (targeted mode)."),
    _K('tpumr.speculative.critical.fraction', 'float', 0.75,
        "A straggler is speculated only when its remaining time is "
        "within this fraction of the job's longest remaining path."),
    _K('tpumr.speculative.rate.ewma', 'float', 0.4,
        "Smoothing factor for per-task progress-rate EWMAs (the "
        "remaining-work estimator's input)."),
    _K('tpumr.speculative.targeted', 'bool', True,
        "LATE-style targeted speculation (estimated-finish stragglers "
        "on the critical path, capped) instead of blanket twins."),
    _K('tpumr.task.attempt.id', 'str', '',
        "This attempt's id (framework-set, task-side)."),
    _K('tpumr.task.input.path', 'str', None,
        "Current input path (framework-set, task-side)."),
    _K('tpumr.task.isolation', 'str', 'thread',
        "Task isolation mode: 'thread' (default) or 'process' (child "
        "per CPU attempt)."),
    _K('tpumr.task.local.dir', 'str', None,
        "Per-task scratch dir (framework-set)."),
    _K('tpumr.task.partition', 'int', -1,
        "This task's partition number (framework-set; -1 = unset)."),
    _K('tpumr.task.profile.sort', 'str', 'cumulative',
        "Profiler report sort column."),
    _K('tpumr.task.status.report.interval.ms', 'int', 1000,
        "Min ms between unchanged RUNNING status re-ships on delta "
        "beats (0 = every beat)."),
    _K('tpumr.task.strip.cluster.secret', 'bool', False,
        "Strip the cluster RPC secret from process-isolated task "
        "children."),
    _K('tpumr.task.user', 'str', None,
        "User a process-isolated task child runs as."),
    _K('tpumr.task.userlogs.dir', 'str', None,
        "Override for task userlog directory."),
    _K('tpumr.task.work.dir', 'str', None,
        "Task working directory (framework-set)."),
    _K('tpumr.tasktracker.reactor', 'bool', True,
        "Serve the tracker RPC surface (umbilical + shuffle) through "
        "the selector reactor instead of thread-per-connection."),
    _K('tpumr.topology.map', 'str', None,
        "Inline host->rack map (JSON/dict), the script-less topology "
        "source."),
    _K('tpumr.tpu.attempt.retries', 'int', 1,
        "Device/compile-classed failures before a TIP is pinned "
        "CPU-only."),
    _K('tpumr.tpu.device.probe.interval.ms', 'int', 10000,
        "Quarantined-device probe cadence, ms."),
    _K('tpumr.tpu.device.probe.max.interval.ms', 'int', 300000,
        "Probe cadence backoff cap, ms."),
    _K('tpumr.tpu.device.quarantine.failures', 'int', 3,
        "Consecutive device-classed failures before a device is "
        "quarantined (0 = off)."),
    _K('tpumr.tpu.job.quarantine.tips', 'int', 3,
        "Distinct device-failing TIPs before the job's TPU pass is "
        "disabled."),
    _K('tpumr.tpu.output.cache', 'bool', True,
        "Keep map output device-resident for the device shuffle."),
    _K('tpumr.tpu.pipeline.window', 'int', 32,
        "Cold-dispatch pipeline window, records."),
    _K('tpumr.tpu.pipeline.window.mb', 'int', 2048,
        "Pipeline window byte budget, MiB."),
    _K('tpumr.tpu.split.cache', 'bool', True,
        "Cache staged input splits in device memory (HBM)."),
    _K('tpumr.tpu.split.cache.mb', 'int', 2048,
        "Split-cache HBM budget, MiB."),
    _K('tpumr.trace.dir', 'str', None,
        "Span-file directory (default: next to job history)."),
    _K('tpumr.trace.enabled', 'bool', False,
        "Distributed tracing master switch (set at submit)."),
    _K('tpumr.trace.id', 'str', '',
        "Trace id (framework-set; the job id)."),
    _K('tpumr.trace.sample', 'str', None,
        "Per-job head-sampling rate in [0,1]."),
    _K('tpumr.tracker.expiry.ms', 'int', 10000,
        "Ms without a heartbeat before a tracker's lease expires "
        "(monotonic deadline)."),
    _K('tpumr.tracker.max.faults', 'int', 4,
        "Fault charges before a tracker is blacklisted."),
    _K('tpumr.tracker.registry.shards', 'int', 16,
        "Stripe count of the tracker-registry lock (rank 30)."),
    _K('tpumr.wordcount.vectorized', 'bool', True,
        "Wordcount op: vectorized kernel path."),
    _K('user.name', 'str', '',
        "Caller identity override (tests/tools); normally derived from "
        "the process owner."),
    _K('hadoop.proxyuser.*', 'str', None,
        "Proxy-user (doas) host/group allowlists.", pattern=True),
    _K('mapred.queue.*', 'str', None,
        "Per-queue ACL keys: "
        "mapred.queue.<name>.acl-{submit-job,administer-jobs}.", pattern=True),
    _K('mapreduce.job.acl-*', 'str', None,
        "Per-job ACLs: acl-view-job / acl-modify-job.", pattern=True),
    _K('tpumr.capacity.*', 'str', None,
        "Capacity scheduler per-queue knobs: "
        "tpumr.capacity.<queue>.{guaranteed-capacity,...}.", pattern=True),
    _K('tpumr.fairscheduler.pool.*', 'str', None,
        "Fair scheduler per-pool knobs.", pattern=True),
    _K('tpumr.fi.*', 'str', None,
        "Per-seam fault-injection knobs: tpumr.fi.<point>.probability / "
        ".max.failures (docs/OPERATIONS.md lists the seams).", pattern=True),
    _K('tpumr.scenario.slo.*', 'str', None,
        "Per-traffic-class latency SLOs (scenario lab): "
        "tpumr.scenario.slo.<class>.{assign,complete}.ms.", pattern=True),
    _K('tpumr.user.groups.*', 'str', None,
        "Static user->groups mapping entries.", pattern=True),
)


REGISTRY: "dict[str, ConfKey]" = {e.key: e for e in _ENTRIES}

_PATTERNS: "tuple[ConfKey, ...]" = tuple(
    e for e in _ENTRIES if e.pattern)


def lookup(key: str) -> "ConfKey | None":
    """Exact entry, else the first pattern entry matching ``key``."""
    e = REGISTRY.get(key)
    if e is not None:
        return e
    for p in _PATTERNS:
        if fnmatchcase(key, p.key):
            return p
    return None


def pattern_matches(pattern_key: str, key: str) -> bool:
    return fnmatchcase(key, pattern_key)


def pattern_covers(pattern_key: str, read_prefix: str) -> bool:
    """Could a dynamic read with this literal prefix produce keys the
    pattern matches? True when the prefixes agree up to the pattern's
    first wildcard."""
    head = pattern_key.split("*", 1)[0]
    return head.startswith(read_prefix) or read_prefix.startswith(head)


def suggest(key: str, n: int = 3, cutoff: int = 4) -> "list[str]":
    """Closest registered keys by edit distance — typo'd dotted keys
    silently read defaults forever, so the finding names the likely
    intent."""
    scored = sorted(
        ((_distance(key, k, cutoff + 1), k) for k in REGISTRY),
        key=lambda t: (t[0], t[1]))
    return [k for d, k in scored[:n] if d <= cutoff]


def _distance(a: str, b: str, cap: int) -> int:
    """Levenshtein with an early-out cap (band optimization is not
    worth it at registry scale)."""
    if abs(len(a) - len(b)) >= cap:
        return cap
    prev = list(range(len(b) + 1))
    for i, ca in enumerate(a, start=1):
        cur = [i]
        best = i
        for j, cb in enumerate(b, start=1):
            cur.append(min(prev[j] + 1, cur[-1] + 1,
                           prev[j - 1] + (ca != cb)))
            best = min(best, cur[-1])
        if best >= cap:
            return cap
        prev = cur
    return prev[-1]


# ------------------------------------------------- typed, registry-backed


def _entry(key: str) -> ConfKey:
    e = lookup(key)
    if e is None:
        raise KeyError(f"config key {key!r} is not registered in "
                       f"tpumr/core/confkeys.py")
    return e


def default_of(key: str) -> Any:
    return _entry(key).default


_TRUE = {"true", "yes", "on", "1"}
_FALSE = {"false", "no", "off", "0"}


def get(conf: Any, key: str) -> Any:
    """Registry-defaulted read; works on Configuration objects AND the
    plain dict confs jobs ship over the wire."""
    v = conf.get(key)
    return _entry(key).default if v in (None, "") else v


def get_int(conf: Any, key: str) -> "int | None":
    e = _entry(key)
    if hasattr(conf, "get_int"):
        return conf.get_int(key, e.default)
    v = conf.get(key)
    if v in (None, ""):
        return e.default
    return int(v)


def get_float(conf: Any, key: str) -> "float | None":
    e = _entry(key)
    if hasattr(conf, "get_float"):
        return conf.get_float(key, e.default)
    v = conf.get(key)
    if v in (None, ""):
        return e.default
    return float(v)


def get_boolean(conf: Any, key: str) -> "bool | None":
    e = _entry(key)
    if hasattr(conf, "get_boolean"):
        return conf.get_boolean(key, e.default)
    v = conf.get(key)
    if v in (None, ""):
        return e.default
    if isinstance(v, bool):
        return v
    s = str(v).strip().lower()
    if s in _TRUE:
        return True
    if s in _FALSE:
        return False
    return e.default
