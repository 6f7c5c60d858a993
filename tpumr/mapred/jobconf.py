"""JobConf — the per-job configuration facade.

≈ ``org.apache.hadoop.mapred.JobConf`` (reference: src/mapred/org/apache/
hadoop/mapred/JobConf.java, ~2100 LoC): a Configuration plus typed accessors
for the MapReduce job contract. Key names keep the reference's spelling where
a direct equivalent exists (so its GPU keys map 1:1 to TPU keys):

- ``mapred.tasktracker.map.cpu.tasks.maximum``  (TaskTracker.java:1427)
- ``mapred.tasktracker.map.tpu.tasks.maximum``  (≈ ...map.gpu.tasks.maximum, :1429)
- ``tpumr.map.kernel``                          (≈ hadoop.pipes.gpu.executable,
  Submitter.java:110 — here it names a registered Pallas kernel mapper
  instead of a CUDA binary)
- ``mapred.map.runner.tpu.class``               (≈ mapred.map.runnner.gpu.class,
  JobConf.java:978 — the reference's getter/setter key typo is documented and
  intentionally NOT reproduced)

The reference's optional-scheduling switch
(JobQueueTaskScheduler.java:78) has no key here: how many of a hybrid
job's maps the CPU slots get is decided by what the master measures a CPU
map and a TPU slot's turn to cost (mapred/map_cost.py), not by a switch.
"""

from __future__ import annotations

from typing import Any

from tpumr.core.configuration import Configuration
from tpumr.core import confkeys

#: keys whose job-layer baseline IS the registry default — seeded from
#: tpumr/core/confkeys.py so the generated reference (docs/CONFIG.md)
#: and the runtime defaults can never diverge (tpumr lint guards
#: call-site literals; this guards the resource layer). Per-key docs
#: live in the registry. Dual slot pools ≈ reference
#: conf/mapred-site.xml:23-33 (3 CPU + 1 GPU map slots).
_REGISTRY_SEEDED = (
    "mapred.reduce.tasks",
    "mapred.map.max.attempts",
    "mapred.reduce.max.attempts",
    "mapred.task.timeout",
    "io.sort.mb",
    "io.sort.spill.percent",
    "io.sort.factor",
    "mapred.compress.map.output",
    "mapred.map.output.compression.codec",
    "mapred.min.split.size",
    "mapred.max.split.size",
    "mapred.tasktracker.map.cpu.tasks.maximum",
    "mapred.tasktracker.map.tpu.tasks.maximum",
    "mapred.tasktracker.reduce.tasks.maximum",
    "mapred.reduce.slowstart.completed.maps",
    "mapred.speculative.execution",
    "mapred.job.shuffle.input.buffer.percent",
    "mapred.job.shuffle.merge.percent",
    "tpumr.shuffle.merge.enabled",
    "tpumr.shuffle.parallel.copies",
    "tpumr.tpu.attempt.retries",
    "tpumr.tpu.job.quarantine.tips",
    "tpumr.tpu.device.quarantine.failures",
    "tpumr.tpu.device.probe.interval.ms",
    "tpumr.tpu.device.probe.max.interval.ms",
)

DEFAULTS: dict[str, Any] = {
    **{k: confkeys.default_of(k) for k in _REGISTRY_SEEDED},
    # job-layer-only parameters consumed through this layer (no
    # conf-getter read sites, hence no registry entry)
    "io.file.buffer.size": 65536,
    "fs.local.block.size": 32 * 1024 * 1024,
}


class JobConf(Configuration):
    def __init__(self, other: Configuration | None = None) -> None:
        super().__init__(other=other, load_defaults=other is None)
        if other is None or not isinstance(other, JobConf):
            # DEFAULTS as lowest layer
            self._resources.insert(0, dict(DEFAULTS))

    # ------------------------------------------------------------ identity

    @property
    def job_name(self) -> str:
        return self.get("mapred.job.name", "")

    def set_job_name(self, name: str) -> None:
        self.set("mapred.job.name", name)

    # ------------------------------------------------------------ io paths

    def set_input_paths(self, *paths: str) -> None:
        self.set("mapred.input.dir", ",".join(paths))

    def get_input_paths(self) -> list[str]:
        return self.get_strings("mapred.input.dir")

    def add_input_path(self, path: str) -> None:
        cur = self.get_strings("mapred.input.dir")
        self.set("mapred.input.dir", ",".join(cur + [path]))

    def set_output_path(self, path: str) -> None:
        self.set("mapred.output.dir", path)

    def get_output_path(self) -> str | None:
        return self.get("mapred.output.dir")

    # ------------------------------------------------------------ task counts

    @property
    def num_reduce_tasks(self) -> int:
        return confkeys.get_int(self, "mapred.reduce.tasks")

    def set_num_reduce_tasks(self, n: int) -> None:
        self.set("mapred.reduce.tasks", n)

    @property
    def num_map_tasks_hint(self) -> int:
        return confkeys.get_int(self, "mapred.map.tasks")

    def set_num_map_tasks_hint(self, n: int) -> None:
        self.set("mapred.map.tasks", n)

    # ------------------------------------------------------------ classes

    def set_mapper_class(self, cls: type) -> None:
        self.set_class("mapred.mapper.class", cls)

    def get_mapper_class(self) -> type | None:
        return self.get_class("mapred.mapper.class")

    def set_reducer_class(self, cls: type) -> None:
        self.set_class("mapred.reducer.class", cls)

    def get_reducer_class(self) -> type | None:
        return self.get_class("mapred.reducer.class")

    def set_combiner_class(self, cls: type) -> None:
        self.set_class("mapred.combiner.class", cls)

    def get_combiner_class(self) -> type | None:
        return self.get_class("mapred.combiner.class")

    def set_partitioner_class(self, cls: type) -> None:
        self.set_class("mapred.partitioner.class", cls)

    def get_partitioner_class(self) -> type:
        from tpumr.mapred.api import HashPartitioner
        return self.get_class("mapred.partitioner.class", HashPartitioner)

    def set_input_format(self, cls: type) -> None:
        self.set_class("mapred.input.format.class", cls)

    def get_input_format(self) -> type:
        from tpumr.mapred.input_formats import TextInputFormat
        return self.get_class("mapred.input.format.class", TextInputFormat)

    def set_output_format(self, cls: type) -> None:
        self.set_class("mapred.output.format.class", cls)

    def get_output_format(self) -> type:
        from tpumr.mapred.output_formats import TextOutputFormat
        return self.get_class("mapred.output.format.class", TextOutputFormat)

    def set_output_key_comparator_class(self, cls: type) -> None:
        self.set_class("mapred.output.key.comparator.class", cls)

    def get_output_key_comparator(self) -> Any:
        from tpumr.mapred.api import DeserializingComparator
        from tpumr.utils.reflection import new_instance
        cls = self.get_class("mapred.output.key.comparator.class",
                             DeserializingComparator)
        # configured comparators (lib.KeyFieldBasedComparator reads its
        # -k options from conf) get the conf; plain ones ignore it
        return new_instance(cls, self)

    def set_output_value_grouping_comparator(self, cls: type) -> None:
        """≈ JobConf.setOutputValueGroupingComparator — the secondary-sort
        seam: reduce groups run under this comparator while the merge order
        stays the output-key comparator's."""
        self.set_class("mapred.output.value.groupfn.class", cls)

    def get_output_value_grouping_comparator(self) -> Any:
        from tpumr.utils.reflection import new_instance
        cls = self.get_class("mapred.output.value.groupfn.class")
        # conf-configured comparators (lib.KeyFieldBasedComparator) need
        # their options here too, same as get_output_key_comparator
        return new_instance(cls, self) if cls is not None else None

    def set_map_runner_class(self, cls: type) -> None:
        """≈ JobConf.setMapRunnerClass (CPU path)."""
        self.set_class("mapred.map.runner.class", cls)

    def get_map_runner_class(self) -> type:
        from tpumr.mapred.api import MapRunner
        return self.get_class("mapred.map.runner.class", MapRunner)

    def set_tpu_map_runner_class(self, cls: type) -> None:
        """≈ JobConf.setGPUMapRunnerClass (JobConf.java:977-1001; the
        reference's mapred.map.runnner.gpu.class getter typo is fixed here,
        divergence documented)."""
        self.set_class("mapred.map.runner.tpu.class", cls)

    def get_tpu_map_runner_class(self) -> type:
        from tpumr.mapred.tpu_runner import TpuMapRunner
        return self.get_class("mapred.map.runner.tpu.class", TpuMapRunner)

    # ------------------------------------------------------------ TPU kernel

    def set_map_kernel(self, name: str) -> None:
        """Name a registered device kernel mapper (tpumr.ops registry) —
        the TPU analog of hadoop.pipes.gpu.executable: without it a job is
        CPU-only in the hybrid scheduler (JobQueueTaskScheduler.java:342-347
        semantics preserved)."""
        self.set("tpumr.map.kernel", name)

    def get_map_kernel(self) -> str | None:
        return self.get("tpumr.map.kernel")

    def set_reduce_kernel(self, name: str) -> None:
        """Name a registered reduce kernel (tpumr.ops registry) as the
        job's reducer: behind the device shuffle it runs on the device
        where the rows were sorted, and only its groups come back."""
        self.set("tpumr.reduce.kernel", name)

    def get_reduce_kernel(self) -> str | None:
        return self.get("tpumr.reduce.kernel")

    def set_device_shuffle(self, key_bytes: int, value_bytes: int) -> None:
        """Opt this job into the device-shuffled reduce (ICI all_to_all +
        per-device sort — tpumr.mapred.device_shuffle): map outputs must be
        fixed-width ``bytes`` keys/values of exactly these lengths."""
        self.set("tpumr.shuffle.device", True)
        self.set("tpumr.shuffle.device.key.bytes", key_bytes)
        self.set("tpumr.shuffle.device.value.bytes", value_bytes)

    # ------------------------------------------------------------ slot pools

    @property
    def max_cpu_map_slots(self) -> int:
        return confkeys.get_int(
            self, "mapred.tasktracker.map.cpu.tasks.maximum")

    @property
    def max_tpu_map_slots(self) -> int:
        return confkeys.get_int(
            self, "mapred.tasktracker.map.tpu.tasks.maximum")

    @property
    def max_reduce_slots(self) -> int:
        return confkeys.get_int(
            self, "mapred.tasktracker.reduce.tasks.maximum")

    # ------------------------------------------------------------ sort/spill

    @property
    def sort_mb(self) -> int:
        return confkeys.get_int(self, "io.sort.mb")

    @property
    def spill_percent(self) -> float:
        return confkeys.get_float(self, "io.sort.spill.percent")

    @property
    def sort_factor(self) -> int:
        return confkeys.get_int(self, "io.sort.factor")

    @property
    def compress_map_output(self) -> str:
        if confkeys.get_boolean(self, "mapred.compress.map.output"):
            return self.get("mapred.map.output.compression.codec", "zlib")
        return "none"
