"""The two JAX surface calls every mesh entry point shares.

Written for the one installation there is (jax 0.9.0): `jax.shard_map`
and the `jax_num_cpu_devices` config option.  Kept as helpers so the
`check_vma` default and the "too late to resize" answer live in one
place.
"""

from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def set_cpu_devices(n: int) -> bool:
    """Request n XLA:CPU virtual devices.  Returns False when the
    backend already exists (jax refuses the update) — callers treat
    that as "whatever device count exists is what you get"."""
    try:
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:
        return False
    return True
