"""The repo's one spelling of jax's ``shard_map`` and abstract-mesh APIs
(jax 0.9).  ``models/moe_ep.py`` and ``fl/batched.py`` both build on this."""

from __future__ import annotations

import jax

shard_map = jax.shard_map

# Splat into every shard_map call to disable the replication check.
SHARD_MAP_NO_CHECK_KW = {"check_vma": False}


def abstract_client_mesh(width: int, axis: str = "clients"):
    """``jax.sharding.AbstractMesh`` with one ``width``-sized axis.

    An abstract mesh lets one traced ``shard_map`` program serve every
    concrete mesh of the same shape — the submesh bindings in
    ``fl/batched.py`` use it to share a single trace across equal-width
    submeshes (the concrete devices come in through the inputs'
    ``NamedSharding``)."""
    return jax.sharding.AbstractMesh((int(width),), (axis,))
