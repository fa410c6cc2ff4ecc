from .mesh import (  # noqa: F401
    BUCKET_AXIS,
    force_virtual_cpu,
    make_mesh,
    mesh_row_quantum,
    quantize_cap,
    quantized_rows,
    replicated,
    row_sharding,
)
from .distributed import (  # noqa: F401
    distributed_bucketed_join_counts,
    distributed_bucketize,
)
from .table_ops import (  # noqa: F401
    distributed_bucketed_join_pairs,
    distributed_bucketize_perm,
    distributed_bucketize_table,
    distributed_exchange_table,
)
