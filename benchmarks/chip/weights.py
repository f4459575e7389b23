"""Random weights from the seed, made on the device in one jitted call, and
placed on the cell's chips as the program places them.

The benchmark owns the weights: the program under test is handed them in its
own parameter layout (the family's ``to_program``), and the plain reference
makes them again from the same seed after the program's state is freed.
Each family (``families/<family>.py``) lays out and draws its own leaves,
directly in the configuration's dtype; this module holds what every family
shares.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec


def key_from_seed(seed: int):
    """A JAX key from a seed of any size (run seeds exceed 32 bits)."""
    words = np.random.SeedSequence([int(seed), 0]).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0] >> 1)),
                              int(words[1] >> 1))


def shardings(abstract, to_program, mesh):
    """The ``NamedSharding`` on ``mesh`` of each leaf of ``abstract`` (a
    family's layout): the spec that the program's partition rules
    (``repro.dist.sharding``) give the same array in the program's layout
    (``to_program``)."""
    from repro.dist import sharding

    leaves, treedef = jax.tree_util.tree_flatten(abstract)
    ids = to_program(jax.tree_util.tree_unflatten(treedef,
                                                  list(range(len(leaves)))))
    specs = sharding.partition_params(to_program(abstract))
    by_id = dict(zip(jax.tree_util.tree_leaves(ids),
                     jax.tree_util.tree_leaves(
                         specs, is_leaf=lambda s: isinstance(s,
                                                             PartitionSpec))))
    return jax.tree_util.tree_unflatten(
        treedef, [NamedSharding(mesh, by_id[i]) for i in range(len(leaves))])


def placed(build, key, to_program, mesh):
    """``jax.jit(build)(key)`` with every leaf made where the program keeps
    it (``shardings``), so no chip ever holds the whole model.  The values
    do not depend on the placement (JAX's partitionable threefry).  On a
    mesh of one chip every leaf goes to the default device, where the
    one-chip engine's own arrays are, so its executables see one
    placement."""
    if mesh.size == 1:
        return jax.jit(build)(key)
    out = shardings(jax.eval_shape(build, key), to_program, mesh)
    return jax.jit(build, out_shardings=out)(key)
