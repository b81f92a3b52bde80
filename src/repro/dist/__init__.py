"""repro.dist — the distributed-performance layer.

The paper's final specialization level configures how data moves between
chips; this package is that level's runtime library:

* :mod:`repro.dist.collectives` — int8 block-quantized gradient
  compression with error feedback, and ``compressed_psum`` for use under
  ``jax.shard_map`` (the template's ``special.compress`` function);
* :mod:`repro.dist.sharding`    — PartitionSpec resolution with the same
  divisibility repair the data-organization pass applies to the IR;
* :mod:`repro.dist.flash_decode`— shard_map flash-decode over a
  seq-sharded KV cache (local append + 3-term online-softmax combine).

Everything here is plan-driven: the passes decide *whether* these paths
run; this package only implements *how*.
"""

from __future__ import annotations

from repro.dist.collectives import (  # noqa: F401
    compressed_psum,
    compressed_slice_sum,
    dequantize_int8,
    ef_compress,
    ef_state,
    quantize_int8,
)
from repro.dist.sharding import (  # noqa: F401
    cache_pspecs,
    mesh_sizes,
    resolve_pspec,
    tree_shardings,
)
