"""Mesh-wide point aggregation: shard_map + explicit XOR-butterfly.

Why not `jax.jit(in_shardings=...)` over the halving tree: the GSPMD
partitioner has to propagate shardings through the strided slices and
95-step carry scans of the limb arithmetic, and on the wide Fp2 forms
that is pathological — observed on the 8-device CPU mesh as an
XLA-compiler segfault for the inlined 3-level G2 tree and a >40-minute
compile for even ONE sharded G2 add level. `shard_map` sidesteps the
partitioner entirely: each device compiles a small LOCAL program (its
shard's reduction tree) and the cross-device combine is an explicit
`lax.ppermute` butterfly — the collective rides ICI, exactly the
SURVEY §2.3 design, and the compile cost is log2 small adds.

The butterfly requires power-of-two axis sizes (every practical mesh
here; parallel/mesh.py builds 2^k axes). After log2(size) rounds of
`x += ppermute(x, i ^ step)` every shard holds the full sum, so the
result is read from shard 0.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

_CACHE: dict = {}


def aggregate_sharded(points, mesh, add_fn, identity, trailing_shape):
    """Sum [B, *trailing_shape] int32 points over the mesh -> one point.

    add_fn must be a batched complete point addition; identity the
    numpy identity point of shape trailing_shape."""
    n = int(mesh.devices.size)
    for ax in mesh.axis_names:
        size = int(mesh.shape[ax])
        assert size & (size - 1) == 0, (
            f"butterfly all-reduce needs power-of-two axes, got "
            f"{ax}={size}"
        )
    b = points.shape[0]
    per = max(1, -(-b // n))
    per = 1 << (per - 1).bit_length()
    nb = per * n
    pts = np.asarray(points)
    if nb != b:
        pad = np.broadcast_to(
            identity, (nb - b, *trailing_shape)
        ).astype(pts.dtype)
        pts = np.concatenate([pts, pad], axis=0)

    spec = P(mesh.axis_names)
    key = (mesh, nb, add_fn)
    fn = _CACHE.get(key)
    if fn is None:

        def local(p):
            # p: [per, *trailing] — this shard's slice
            while p.shape[0] > 1:
                p = add_fn(p[0::2], p[1::2])
            x = p
            for ax in mesh.axis_names:
                size = int(mesh.shape[ax])
                step = 1
                while step < size:
                    perm = [(i, i ^ step) for i in range(size)]
                    x = add_fn(x, jax.lax.ppermute(x, ax, perm))
                    step *= 2
            return x

        fn = jax.jit(
            shard_map(
                local,
                mesh=mesh,
                in_specs=spec,
                out_specs=spec,
                check_vma=False,
            )
        )
        _CACHE[key] = fn
    out = fn(jax.device_put(pts, NamedSharding(mesh, spec)))
    return jnp.asarray(np.asarray(out)[0])
