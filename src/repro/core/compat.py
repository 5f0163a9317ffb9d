"""The one ``shard_map`` entry point.

Every call site goes through :func:`shard_map` so the replication check is
set in one place.
"""

from __future__ import annotations

from jax import shard_map as _shard_map


def shard_map(f, *, mesh, in_specs, out_specs):
    # The replication check stays off: check_vma is stricter than these specs
    # are annotated for. With the check off, grad-of-shard_map additionally
    # requires scan carries to be non-scalar (see train/pipeline.py) — scalar
    # residuals can't be spec'd per-device.
    return _shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=False)
