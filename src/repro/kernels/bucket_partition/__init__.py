from repro.kernels.bucket_partition.ops import (bucket_partition,  # noqa: F401
                                                bucket_scatter)
from repro.kernels.bucket_partition.ref import (bucket_partition_ref,  # noqa: F401
                                                bucket_scatter_ref)
