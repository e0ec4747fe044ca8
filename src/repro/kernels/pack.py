"""Fused gather/pack kernel — coalesce per-leaf device→host checkpoint copies.

``snapshot_to_host`` used to issue one ``device_get`` per pytree leaf; for
the sharded layouts in ``sharding/specs.py`` that is dozens of small DMA
transfers, each paying latency.  ``pack_leaves_pallas`` gathers all
same-dtype leaves into ONE contiguous device buffer (a single Pallas grid
sweep over output blocks), so the host side becomes one large transfer per
dtype group.  ``packed_snapshot_to_host`` is the drop-in
``snapshot_to_host`` replacement built on it (``fused=True`` there routes
here); the fig5 slow-lane microbench quantifies the win.

Kernel shape: every leaf is flattened to 1-D, padded to a
``block_rows × lane`` tile multiple, and viewed as ``(n_i·block_rows,
lane)``.  The grid runs over the *output* blocks, leaf-major; leaf ``i``
owns grid slots ``[start_i, start_i + n_i)``.  Its input index_map clamps
``g - start_i`` into range (out-of-range slots still prefetch *some* valid
block — harmless, the ``pl.when`` guard never writes it), and the kernel
body copies the active leaf's block to the output tile.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.checkpoint.reshard import flatten_tree
from repro.kernels.platform import on_platform

LANE = 128
BLOCK_ROWS = 8


def _pack_kernel(*refs, starts: Tuple[int, ...], nblocks: Tuple[int, ...]):
    ins, o_ref = refs[:-1], refs[-1]
    g = pl.program_id(0)
    for i in range(len(ins)):
        @pl.when((g >= starts[i]) & (g < starts[i] + nblocks[i]))
        def _copy(i=i):
            o_ref[...] = ins[i][...]


def pack_leaves_pallas(leaves: Sequence[jax.Array], *,
                       block_rows: int = BLOCK_ROWS, lane: int = LANE,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Pack same-dtype ``leaves`` into one ``(total_blocks·block_rows, lane)``
    device buffer, leaf-major, each leaf zero-padded to a block multiple."""
    return on_platform(
        functools.partial(_pack_call, block_rows=block_rows, lane=lane),
        *leaves, interpret=interpret)


def _pack_call(*leaves: jax.Array, block_rows: int, lane: int,
               interpret: bool) -> jax.Array:
    block = block_rows * lane
    views, nblocks = [], []
    for leaf in leaves:
        v = jnp.ravel(leaf)
        pad = (-v.size) % block
        if pad:
            v = jnp.pad(v, (0, pad))
        views.append(v.reshape(-1, lane))
        nblocks.append(v.size // block)
    starts = tuple(int(s) for s in np.cumsum([0] + nblocks[:-1]))
    nblocks = tuple(nblocks)
    total = sum(nblocks)
    in_specs = [
        pl.BlockSpec((block_rows, lane),
                     functools.partial(
                         lambda g, s, n: (jnp.clip(g - s, 0, n - 1), 0),
                         s=starts[i], n=nblocks[i]))
        for i in range(len(views))
    ]
    kernel = functools.partial(_pack_kernel, starts=starts, nblocks=nblocks)
    return pl.pallas_call(
        kernel,
        name="pack_leaves",
        grid=(total,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, lane), lambda g: (g, 0)),
        out_shape=jax.ShapeDtypeStruct((total * block_rows, lane),
                                       views[0].dtype),
        interpret=interpret,
    )(*views)


def pack_leaves_ref(leaves: Sequence[jax.Array], *,
                    block_rows: int = BLOCK_ROWS,
                    lane: int = LANE) -> jax.Array:
    """Pure-jnp reference for the pack kernel (tests + non-Pallas fallback)."""
    block = block_rows * lane
    parts = []
    for leaf in leaves:
        v = jnp.ravel(leaf)
        pad = (-v.size) % block
        if pad:
            v = jnp.pad(v, (0, pad))
        parts.append(v)
    return jnp.concatenate(parts).reshape(-1, lane)


def _on_one_device(a: jax.Array) -> jax.Array:
    """``a`` on one device, since a Pallas TPU call cannot be partitioned:
    a replicated array gives its first copy (nothing moves), a sharded one
    is gathered onto its first shard's device."""
    if len(a.sharding.device_set) == 1:
        return a
    first = a.addressable_shards[0]
    if a.sharding.is_fully_replicated:
        return first.data
    return jax.device_put(a, jax.sharding.SingleDeviceSharding(first.device))


def packed_snapshot_to_host(tree, *, block_rows: int = BLOCK_ROWS,
                            lane: int = LANE, interpret: Optional[bool] = None
                            ) -> Dict[str, np.ndarray]:
    """Fused device→host snapshot: one packed transfer per dtype group.

    Returns the same ``{path-key: ndarray}`` dict as ``snapshot_to_host``."""
    flat = flatten_tree(tree)
    block = block_rows * lane
    groups: Dict[str, List[str]] = {}
    arrs = {k: _on_one_device(jnp.asarray(v)) for k, v in flat.items()}
    out: Dict[str, np.ndarray] = {}
    for k, a in arrs.items():
        if a.size == 0:                       # nothing to transfer
            out[k] = np.zeros(a.shape, a.dtype)
        else:
            groups.setdefault(str(a.dtype), []).append(k)
    for _, ks in groups.items():
        leaves = [arrs[k] for k in ks]
        packed = pack_leaves_pallas(leaves, block_rows=block_rows, lane=lane,
                                    interpret=interpret)
        host = np.asarray(jax.device_get(packed)).reshape(-1)
        off = 0
        for k, a in zip(ks, leaves):
            n_padded = a.size + ((-a.size) % block)
            out[k] = host[off:off + a.size].reshape(a.shape).copy()
            off += n_padded
    return {k: out[k] for k in flat}          # original key order
