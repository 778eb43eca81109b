"""Two small JAX helpers shared by the mesh and shard_map code.

``make_mesh``
    ``jax.make_mesh`` with every axis ``AxisType.Auto`` (sharding by
    propagation, which the shard_map engines expect).

``pvary_like``
    Varying-manual-axes promotion for shard_map loop carries.
"""

from __future__ import annotations

import jax

__all__ = ["make_mesh", "pvary_like"]


def pvary_like(val, like):
    """Promote ``val``'s varying-manual-axes to match ``like`` (shard_map).

    Loop carries must have stable types under shard_map: a ``jnp.zeros``
    init is unvarying while permuted/sharded data is varying, so the init
    must be pcast before entering a ``fori_loop``/``while_loop``/``scan``.
    Outside a manual-axes context both sets are empty and this is the
    identity.
    """
    need = set(jax.typeof(like).vma) - set(jax.typeof(val).vma)
    if need:
        val = jax.lax.pcast(val, tuple(sorted(need)), to="varying")
    return val


def make_mesh(shape, names):
    auto = (jax.sharding.AxisType.Auto,) * len(names)
    return jax.make_mesh(shape, names, axis_types=auto)
