"""Kernel registry: device mappers and device reducers.

≈ the role of DistributedCache executable slots in the reference
(mapred/pipes/Submitter.java:349-379: CPU binary → cache[0], GPU binary →
cache[1]): jobs name their accelerator mapper; the node runner resolves it at
launch. Names are strings in job conf (``tpumr.map.kernel``) so submission
stays wire-serializable. A job whose reduce runs behind the device shuffle
names its reducer the same way (``tpumr.reduce.kernel``,
:class:`ReduceKernel`).
"""

from __future__ import annotations

from typing import Any, Callable, Iterable


class KernelMapper:
    """A whole-batch device mapper.

    Contract: ``map_batch(batch, conf, task)`` consumes a staged
    :class:`~tpumr.io.recordbatch.DenseBatch` or
    :class:`~tpumr.io.recordbatch.RecordBatch` and returns an iterable of
    (key, value) records — typically FEW records, because the kernel
    aggregates on device (per-split partial sums, counts, blocks). This is
    the designed-in advantage over the reference's per-record socket protocol
    (BinaryProtocol MAP_ITEM hot loop, PipesGPUMapRunner.java:97-107): output
    leaves the device pre-combined.

    ``batch.values`` (and dense batch arrays generally) may be READ-ONLY
    numpy views over the input file's buffer (DenseInputFormat stages
    splits zero-copy via ``np.frombuffer``). Kernels must not mutate
    batch arrays in place — copy first (``np.array(batch.values)``) if a
    writable array is needed; ``jnp.asarray`` staging is unaffected.
    """

    #: registry name
    name: str = ""

    def map_batch(self, batch: Any, conf: Any, task: Any) -> Iterable[tuple]:
        """Synchronous batch map. Kernels that implement the two-phase
        launch/drain protocol get this for free (one host transfer per
        task); others override it directly."""
        state = self.map_batch_launch(batch, conf, task)
        if state is None:
            # a kernel that declines batches at runtime must also override
            # map_batch with its own fallback path
            raise NotImplementedError(
                f"kernel {self.name!r}: map_batch_launch declined this "
                "batch (returned None) and map_batch is not overridden")
        import jax
        return self.map_batch_drain(jax.device_get(state), conf, task)

    # ---------------------------------------------- two-phase device protocol
    #
    # Dispatch is asynchronous; a host transfer of a computed array is
    # where the host blocks on the device. Kernels that split into
    #   launch: dispatch device work, return a pytree of jax.Arrays
    #           (plain-python leaves pass through untouched), and
    #   drain:  turn the fetched host pytree into (key, value) records
    # let the runner batch MANY tasks' fetches into ONE jax.device_get —
    # one host sync per pipeline window instead of per output array
    # (TpuMapRunner single-task path + LocalJobRunner windowed prelaunch).

    def map_batch_launch(self, batch: Any, conf: Any, task: Any) -> Any:
        """Dispatch the device computation for one staged batch; return a
        pytree whose jax.Array leaves the runner will fetch, or None if
        this kernel does not support the two-phase protocol. Must not
        block on device results. Receives the job-level conf when called
        from the prelaunch window (task-localized conf otherwise)."""
        return None

    def map_batch_drain(self, fetched: Any, conf: Any, task: Any
                        ) -> Iterable[tuple]:
        """Convert the fetched (host) pytree returned by
        :meth:`map_batch_launch` into the task's (key, value) records."""
        raise NotImplementedError

    @classmethod
    def supports_launch(cls) -> bool:
        return cls.map_batch_launch is not KernelMapper.map_batch_launch

    # optional output-chaining hook: the device array whose host image
    # the task's output file will contain (same shape/dtype as the rows
    # the drain writes). Jobs writing through DenseNpyOutputFormat get
    # their output published into the HBM cache so a chained consumer
    # (DenseInputFormat) skips the storage read AND the re-upload —
    # see tpumr/mapred/device_output.py.
    # def device_output_rows(self, state) -> "jax.Array | None"

    # optional: kernels can advertise a CPU mapper class for the hybrid
    # scheduler's CPU slots (same job, both backends)
    cpu_mapper_class: type | None = None

    #: optional vectorized host implementation with the same
    #: ``(batch, conf, task) -> iterable of (key, value)`` contract —
    #: when present, CPU slots run the whole staged split through it
    #: (CpuBatchMapRunner) instead of per-record Python, keeping the
    #: hybrid scheduler's acceleration factor an honest batch-vs-batch
    #: measurement
    map_batch_cpu: Any = None


_REGISTRY: dict[str, KernelMapper] = {}


def register_kernel(kernel: KernelMapper) -> KernelMapper:
    if not kernel.name:
        raise ValueError("kernel needs a name")
    _REGISTRY[kernel.name] = kernel
    return kernel


def get_kernel(name: str) -> KernelMapper:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"no kernel mapper {name!r}; registered: "
                       f"{sorted(_REGISTRY)}") from None


def kernels() -> list[str]:
    return sorted(_REGISTRY)


class ReduceKernel:
    """A whole-range device reducer behind the device shuffle.

    Where a map kernel consumes a staged split, a reduce kernel consumes
    the job's rows once the device has ORDERED them by key: the value
    column goes up beside the key words, the kernel's program runs on the
    device right after the sort, and only its output rows (one a group)
    come back (``tpumr.parallel.device_sort.device_partition_sort``).
    Input and output rows are fixed-width: the job's key bytes, then
    ``value_bytes`` of value, the output's key being the group's.

    ``device_program(key_cols)`` returns the jitted program ``(words,
    n_live) -> (table, groups)``: ``words`` is ``[key_cols + value words,
    n]`` uint32, one COLUMN a row of the array, ordered by the key
    columns with the ``n_live`` real rows first; ``table`` holds the
    output rows the same way, the live ones first, and ``groups`` is how
    many those are. ``reduce_host(rows, klen)`` is its numpy twin
    over key-sorted ``[n, klen + value_bytes]`` uint8 rows: what an
    overflow, a host fallback or a mesh the kernel has no program for
    reduces with, to the same rows.
    """

    #: registry name
    name: str = ""
    #: width of the value a map emits and of the value a group gets
    value_bytes: int = 0

    def device_program(self, key_cols: int) -> Callable:
        raise NotImplementedError

    def reduce_host(self, rows: Any, klen: int) -> Any:
        raise NotImplementedError


_REDUCE_REGISTRY: dict[str, ReduceKernel] = {}


def register_reduce_kernel(kernel: ReduceKernel) -> ReduceKernel:
    if not kernel.name:
        raise ValueError("reduce kernel needs a name")
    _REDUCE_REGISTRY[kernel.name] = kernel
    return kernel


def get_reduce_kernel(name: str) -> ReduceKernel:
    try:
        return _REDUCE_REGISTRY[name]
    except KeyError:
        raise KeyError(f"no reduce kernel {name!r}; registered: "
                       f"{sorted(_REDUCE_REGISTRY)}") from None
