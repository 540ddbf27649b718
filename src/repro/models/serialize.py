"""Deterministic flat tensor-tree codec for the MLServe data plane.

Model params and KV caches travel through ``ctx.storage`` as ordinary
S3 objects (the paper's state-heavy-function story, §2/§6): the handler
GETs weight shards and KV state, PUTs updated KV state, and the
platform underneath must leave the bytes untouched — the transparency
acceptance test diffs them across every system variant.

That demands a *byte-deterministic* format. ``np.savez`` is a zip
archive (embedded timestamps), pickle is protocol-version-sensitive — so
this codec is deliberately dumber than either: the leaves of a pytree
in `jax.tree_util` flatten order, each as its raw C-contiguous buffer,
concatenated. No header, no padding, no metadata. The reader supplies
the tree of `ShapeDtypeStruct`s (from ``jax.eval_shape``, which both
executors and the calibrator derive from the same `ModelConfig`), so
sizes and offsets are fully determined before any payload exists —
which is also what lets `core.calibrate` declare exact `IOProfile`
byte sizes without materializing a single tensor.
"""
from __future__ import annotations

import math


def _leaves(tree):
    import jax
    return jax.tree_util.tree_leaves(tree)


def leaf_nbytes(leaf) -> int:
    """Size in bytes of one array/ShapeDtypeStruct-like leaf."""
    import numpy as np
    return math.prod(leaf.shape) * np.dtype(leaf.dtype).itemsize


def tree_nbytes(shapes) -> int:
    """Total encoded size of a tree of `ShapeDtypeStruct`s (or arrays).

    Pure shape arithmetic — safe on ``jax.eval_shape`` output, never
    materializes data. This is the single source of the `IOProfile`
    sizes in ``calibration.json``.
    """
    return sum(leaf_nbytes(l) for l in _leaves(shapes))


def dumps(tree) -> bytes:
    """Encode a tree of arrays to its canonical flat byte string."""
    import numpy as np
    return b"".join(np.ascontiguousarray(np.asarray(leaf)).tobytes()
                    for leaf in _leaves(tree))


def loads(shapes, data):
    """Decode ``data`` against a tree of `ShapeDtypeStruct`s.

    Returns a tree of the same structure with `jax.numpy` array leaves.
    Raises ``ValueError`` on any size mismatch — a truncated or padded
    payload must never be silently reinterpreted.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    total = sum(leaf_nbytes(l) for l in leaves)
    buf = memoryview(data)
    if len(buf) != total:
        raise ValueError(
            f"payload is {len(buf)}B but the declared tree needs {total}B")
    off = 0
    out = []
    for leaf in leaves:
        n = leaf_nbytes(leaf)
        arr = np.frombuffer(buf[off:off + n],
                            dtype=np.dtype(leaf.dtype)).reshape(leaf.shape)
        out.append(jnp.asarray(arr))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)
