"""Device map kernels — the TPU replacement for user CUDA map binaries.

In the reference, accelerator map tasks are user-supplied CUDA executables
launched through pipes (mapred/pipes/Application.java:162-181 picks
localCacheFiles[1] and passes GPUDeviceId as argv[1]); there is no GPU code
in-tree. Here the equivalent is a registry of :class:`KernelMapper`s — named
device programs a job selects with ``JobConf.set_map_kernel(name)`` — each
consuming a whole staged batch (MXU-friendly arrays) instead of a per-record
socket stream.

Importing this package registers the built-in kernels.
"""

from tpumr.ops.registry import (KernelMapper, ReduceKernel, get_kernel,
                                get_reduce_kernel, kernels, register_kernel,
                                register_reduce_kernel)

# built-ins register on import
import tpumr.ops.kmeans    # noqa: F401,E402
import tpumr.ops.matmul    # noqa: F401,E402
import tpumr.ops.pi        # noqa: F401,E402
import tpumr.ops.wordcount  # noqa: F401,E402
import tpumr.ops.grep      # noqa: F401,E402
import tpumr.ops.segment_sum  # noqa: F401,E402

__all__ = ["KernelMapper", "ReduceKernel", "get_kernel", "get_reduce_kernel",
           "kernels", "register_kernel", "register_reduce_kernel"]
