"""ZeRO-style optimizer-state sharding: flatten/pad/scatter layout.

The ZeRO-1 data-parallel exchange (``ParallelWrapper(zero_optimizer=
True)``) partitions every gradient/param/moment tensor FLAT across the
``data`` axis: leaf ``i`` (size ``s_i``) is padded to ``n * m_i``
(``m_i = ceil(s_i / n)``) and shard ``k`` owns elements
``[k*m_i, (k+1)*m_i)``. Updaters and regularization are elementwise, so
applying them to the local slice of the reduce-scattered gradient with
the local slice of params/moments reproduces the all-reduce path's
update BITWISE on each element — only the optimizer state (and the
update compute) divides by ``n``.

:class:`ZeroSpec` is the static layout: built host-side once per
(tree structure, shard count), it provides the in-graph slice/assemble
helpers the wrapper's ZeRO step composes with
``compression.bucketed_psum_scatter`` / ``bucketed_all_gather``.
"""

from __future__ import annotations

from typing import List

import numpy as np


class ZeroSpec:
    """Flatten/pad/scatter layout for one pytree over ``n`` shards.

    All metadata is static (shapes from the host tree's avals); the
    ``local_*`` helpers are pure jnp and run inside the compiled step.
    """

    def __init__(self, tree, n: int):
        import jax

        leaves, self.treedef = jax.tree_util.tree_flatten(tree)
        self.n = int(n)
        self.shapes = [tuple(l.shape) for l in leaves]
        self.dtypes = [np.dtype(l.dtype) for l in leaves]
        self.sizes = [int(np.prod(s)) if s else 1 for s in self.shapes]
        self.slice_sizes = [-(-s // self.n) for s in self.sizes]   # m_i
        self.padded_sizes = [m * self.n for m in self.slice_sizes]

    # --- staging ------------------------------------------------------------
    def scatter(self, tree, mesh, axis: str):
        """Stage ``tree`` into the scattered flat layout, choosing the
        data path by residency: device-resident trees (a restored
        checkpoint's arrays, a live training state) re-cut through
        ``comms.reshard``'s slice-intersection exchange — no host
        round-trip — while host/numpy trees take :meth:`scatter_host`.
        Identical values either way (the restore-across-mesh-shapes
        bit-identity is pinned by test_comms)."""
        import jax

        leaves = jax.tree_util.tree_flatten(tree)[0]
        if jax.process_count() > 1 or not all(
                isinstance(l, jax.Array) for l in leaves):
            return self.scatter_host(tree, mesh, axis)
        try:
            return self.scatter_device(tree, mesh, axis)
        except Exception:
            # residency probe passed but the exchange could not decompose
            # the layout — the host path is always correct
            return self.scatter_host(tree, mesh, axis)

    def scatter_device(self, tree, mesh, axis: str):
        """Device tree -> scattered flat layout via
        ``comms.reshard.reshard_flat`` (flatten/pad stays in jax;
        shard k's slice lands on shard k's devices by slice
        intersection, not via a numpy mirror)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from deeplearning4j_tpu.comms.reshard import reshard_flat

        sh = NamedSharding(mesh, P(axis))
        leaves = jax.tree_util.tree_flatten(tree)[0]
        out = []
        for leaf, size, padded in zip(leaves, self.sizes,
                                      self.padded_sizes):
            flat = jnp.reshape(leaf, (-1,))
            out.append(reshard_flat(flat, size, padded, sh))
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def exchange_plans(self, axis: str, bucket_bytes=None):
        """The (reduce_scatter, all_gather) CollectivePlans of one ZeRO
        step over this layout — digest source for the AOT step key, and
        exactly the plans the compiled exchange resolves at trace time
        (same leaf sizes/dtypes → same plan cache entry)."""
        import jax

        from deeplearning4j_tpu.comms import scheduler

        flat = [jax.ShapeDtypeStruct((p,), dt)
                for p, dt in zip(self.padded_sizes, self.dtypes)]
        rs = scheduler.plan_for(flat, "reduce_scatter", axis, bucket_bytes)
        slices = [jax.ShapeDtypeStruct((m,), dt)
                  for m, dt in zip(self.slice_sizes, self.dtypes)]
        ag = scheduler.plan_for(slices, "all_gather", axis, bucket_bytes,
                                full_sizes=self.padded_sizes)
        return rs, ag

    # --- host side ----------------------------------------------------------
    def scatter_host(self, tree, mesh, axis: str):
        """Host tree -> tree of flat ``[n*m_i]`` arrays committed with
        their leading axis sharded over ``axis`` (shard k's slice lives
        on shard k's devices — the 1/n-per-device memory footprint).
        Multi-process-safe: ``mesh_mod.stage_host`` routes through
        ``jax.make_array_from_callback``, so each pod host stages only
        its OWN addressable slices of every flat vector — no process
        ever materializes or addresses a remote host's shard (bitwise
        the old ``device_put`` path at ``process_count == 1``, pinned
        by test_sharding's parity suite)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from deeplearning4j_tpu.parallel import mesh as mesh_mod

        leaves = jax.tree_util.tree_flatten(tree)[0]
        sh = NamedSharding(mesh, P(axis))
        out = []
        for leaf, padded, dt in zip(leaves, self.padded_sizes, self.dtypes):
            flat = np.zeros((padded,), dt)
            flat[:leaf.size] = np.asarray(leaf).reshape(-1)
            out.append(mesh_mod.stage_host(flat, sh))
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def gather_host(self, scattered):
        """Inverse of :meth:`scatter_host`: device tree of flat padded
        arrays -> host numpy tree with the original shapes.
        Multi-process-safe: ``mesh_mod.host_gather`` replicates
        process-spanning slices through a compiled identity (the
        cross-host all-gather) before reading; single-process arrays
        keep the direct ``np.asarray`` route bitwise."""
        import jax

        from deeplearning4j_tpu.parallel import mesh as mesh_mod

        leaves = jax.tree_util.tree_flatten(scattered)[0]
        out = []
        for leaf, shape, size in zip(leaves, self.shapes, self.sizes):
            flat = mesh_mod.host_gather(leaf)
            out.append(flat[:size].reshape(shape))
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def bytes_per_device(self) -> int:
        """Per-device bytes of the scattered tree (each device holds one
        ``m_i`` slice per leaf)."""
        return sum(m * dt.itemsize
                   for m, dt in zip(self.slice_sizes, self.dtypes))

    def total_bytes(self) -> int:
        return sum(s * dt.itemsize
                   for s, dt in zip(self.sizes, self.dtypes))

    # --- in-graph (inside shard_map) ---------------------------------------
    def flat_padded(self, tree):
        """Full-shape tree -> tree of flat ``[n*m_i]`` vectors (reshape
        + zero-pad; the ``bucketed_psum_scatter`` input contract)."""
        import jax
        import jax.numpy as jnp

        leaves = jax.tree_util.tree_flatten(tree)[0]
        out = []
        for leaf, size, padded in zip(leaves, self.sizes,
                                      self.padded_sizes):
            flat = jnp.reshape(leaf, (-1,))
            if padded != size:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((padded - size,), flat.dtype)])
            out.append(flat)
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def local_slices(self, tree, index):
        """Full-shape tree -> tree of this shard's flat ``[m_i]``
        slices (``index`` may be a traced ``axis_index``)."""
        import jax

        flat = jax.tree_util.tree_flatten(self.flat_padded(tree))[0]
        out = [jax.lax.dynamic_slice_in_dim(f, index * m, m)
               for f, m in zip(flat, self.slice_sizes)]
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def assemble(self, slices, axis: str, bucket_bytes=None):
        """Per-shard slice tree -> full-shape tree replicated on every
        shard (the ZeRO all-gather), via
        ``compression.bucketed_all_gather`` on this layout's bucket
        sizes."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.parallel.compression import (
            bucketed_all_gather,
        )

        full_flat = bucketed_all_gather(slices, axis, self.padded_sizes,
                                        bucket_bytes)
        leaves = jax.tree_util.tree_flatten(full_flat)[0]
        out = [jnp.reshape(f[:size], shape)
               for f, size, shape in zip(leaves, self.sizes, self.shapes)]
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def layout_bytes(self, bucket_bytes=None) -> List[int]:
        """Per-bucket payload bytes of one scatter/gather schedule over
        this layout (telemetry's bucket-layout histogram — same
        ``bucket_partition`` the scheduler's compiled exchange uses)."""
        from deeplearning4j_tpu.comms.scheduler import bucket_partition

        sizes = [p * dt.itemsize
                 for p, dt in zip(self.padded_sizes, self.dtypes)]
        if not sizes:
            return []
        if bucket_bytes is None or len(sizes) <= 1:
            return [sum(sizes)]
        return [sum(sizes[i] for i in bucket)
                for bucket in bucket_partition(sizes, int(bucket_bytes))]
