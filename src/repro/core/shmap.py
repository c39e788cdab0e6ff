"""`jax.shard_map` with replication checking off (single shim for every SPMD
module): every shard_map body in this repo uses manual collectives with
unannotated replication, so ``check_vma`` is disabled."""
from __future__ import annotations

import jax


def shmap(fn, mesh, in_specs, out_specs):
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False,
    )
