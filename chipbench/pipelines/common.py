"""What every pipeline shares: the seed's keys, mesh and shardings, leaf
norms, and the checks of delivery and staging that need no model."""
from __future__ import annotations

import numpy as np


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31 (a plain
    ``PRNGKey`` takes 32 signed bits)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def mesh_and_shardings(devices):
    """One ``("data",)`` mesh over the cell's chips -> (mesh, batch
    sharding, replicated sharding)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devices).reshape(len(devices)), ("data",))
    return mesh, NamedSharding(mesh, P("data")), NamedSharding(mesh, P())


def leaf_names(tree) -> list:
    import jax
    return [jax.tree_util.keystr(path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree) -> dict:
    """``{leaf name: l2 norm}`` as host floats (one small jitted reduction;
    nothing the size of the state is copied)."""
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree.leaves(t)])(tree)
    return dict(zip(leaf_names(tree), (float(n) for n in norms)))


def staged_layout_faults(batch, n_devices: int) -> int:
    """Staged arrays that do not sit as one equal shard per device on
    distinct devices (host-side metadata, no device sync)."""
    import jax
    bad = 0
    for arr in jax.tree.leaves(batch):
        shards = arr.addressable_shards
        rows = {s.data.shape[0] for s in shards}
        if (len(shards) != n_devices
                or len({s.device for s in shards}) != n_devices
                or rows != {arr.shape[0] // n_devices}):
            bad += 1
    return bad


def delivery_numbers(groups: np.ndarray, n_groups: int) -> dict:
    """The store's guarantees read off the order in which its row groups
    were delivered (``groups``: one entry per delivered row group, in
    order). With ``sample_order='free'`` row groups of neighbouring epochs
    may interleave at the boundary by as many as are in flight, so the
    counts allow a difference of two and an epoch three quarters of its
    groups; a dropped or echoed group passes both bounds within a few
    epochs."""
    counts = np.bincount(groups, minlength=n_groups)
    epochs = [groups[s:s + n_groups]
              for s in range(0, len(groups) - n_groups + 1, n_groups)]
    return {
        "groups_imbalance": int(max(0, counts.max() - counts.min() - 2)),
        "epochs_short": sum(len(set(e.tolist())) < 0.75 * n_groups
                            for e in epochs),
        "epochs_unshuffled": sum(bool(np.all(np.diff(e) > 0))
                                 for e in epochs),
    }
