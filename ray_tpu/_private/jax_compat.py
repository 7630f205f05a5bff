"""One spelling of `shard_map` and `axis_size` for the package, over the
installed jax (0.9): `jax.shard_map` with the replication check spelled
`check_vma` (None leaves jax's default), and `jax.lax.axis_size`.
"""

from __future__ import annotations

import jax


def axis_size(axis_name) -> int:
    return jax.lax.axis_size(axis_name)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None, **kwargs):
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs
    )
