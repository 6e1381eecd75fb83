"""ELL / padded-gather format: the hypersparse (power-law) path.

Power-law graphs (Twitter, Graph500 RMAT) put most edges in a few hub rows;
128x128 dense tiles would store mostly zeros (fill ratio << 1%).  The ELL
format keeps, per vertex, a padded list of neighbor ids.  On TPU this drives
XLA gathers + segment reductions on the VPU — no MXU, but bandwidth-optimal
for fill ratios where BSR would explode the footprint.

`Format auto-selection` (core.ops.auto_format) mirrors SuiteSparse's
CSR/bitmap/hypersparse switching: build BSR, check fill_ratio, fall back here.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class HostCount:
    """A storage pytree's exact stored-entry count, kept in its static data
    but left out of the treedef's equality and hash: no compiled program
    reads the count, so it must not key one (a live delta patch keeps its
    shape while its count moves with every write). Only a node rebuilt
    from concrete arrays gets it back (`rebuilt`)."""
    __slots__ = ("value",)

    def __init__(self, value: Optional[int]):
        self.value = value

    def __eq__(self, other) -> bool:
        return isinstance(other, HostCount)

    def __hash__(self) -> int:
        return hash(HostCount)

    def __repr__(self) -> str:
        return "HostCount()"

    def rebuilt(self, children) -> Optional[int]:
        """The count of a node rebuilt from ``children``: exact after a
        tree_map or device_put of concrete arrays; None where any child is
        a tracer, because one executable serves every count and the one
        met inside its trace would be that of the call that traced it."""
        if any(isinstance(c, jax.core.Tracer) for c in children):
            return None
        return self.value


def checked_nnz(kind: str, nnz: Optional[int]) -> int:
    if nnz is None:
        raise ValueError(
            f"{kind}.nnz is unknown inside a trace: the compiled program "
            f"serves every count, so the pytree leaves it out; read it on "
            f"the host, outside jit")
    return nnz


@jax.tree_util.register_pytree_node_class
class ELL:
    """``nnz`` is an exact host int, read without touching the device; the
    pytree's static data is the shape alone (see `HostCount`)."""

    def __init__(self, shape: Tuple[int, int], indices: jnp.ndarray,
                 mask: jnp.ndarray, values: jnp.ndarray,
                 nnz: Optional[int]):
        self.shape = shape
        self.indices = indices  # (n, max_deg) i32 neighbor ids, padded with 0
        self.mask = mask        # (n, max_deg) bool validity
        self.values = values    # (n, max_deg) f32 edge weights (1.0 structural)
        self._nnz = None if nnz is None else int(nnz)

    @property
    def nnz(self) -> int:
        return checked_nnz("ELL", self._nnz)

    def tree_flatten(self):
        return ((self.indices, self.mask, self.values),
                (self.shape, HostCount(self._nnz)))

    @classmethod
    def tree_unflatten(cls, aux, children):
        shape, count = aux
        return cls(shape, *children, nnz=count.rebuilt(children))

    def __repr__(self) -> str:
        n, m = self.shape
        return f"ELL {n}x{m} max_deg={self.max_deg} nnz={self._nnz}"

    @property
    def max_deg(self) -> int:
        return self.indices.shape[1]

    @staticmethod
    def from_coo(rows, cols, vals, shape, pad_deg_to: int = 8) -> "ELL":
        rows = np.asarray(rows, dtype=np.int64)
        cols = np.asarray(cols, dtype=np.int64)
        if vals is None:
            vals = np.ones(rows.shape[0], dtype=np.float32)
        vals = np.asarray(vals, dtype=np.float32)
        n, _ = shape
        order = np.argsort(rows, kind="stable")
        rows, cols, vals = rows[order], cols[order], vals[order]
        deg = np.bincount(rows, minlength=n)
        md = int(deg.max()) if deg.size and deg.max() > 0 else 1
        md = md + (-md) % pad_deg_to
        idx = np.zeros((n, md), dtype=np.int32)
        msk = np.zeros((n, md), dtype=bool)
        val = np.zeros((n, md), dtype=np.float32)
        # slot position of each edge within its row
        starts = np.zeros(n + 1, dtype=np.int64)
        starts[1:] = np.cumsum(deg)
        slot = np.arange(rows.shape[0]) - starts[rows]
        idx[rows, slot] = cols
        msk[rows, slot] = True
        val[rows, slot] = vals
        return ELL(shape=(n, shape[1]), indices=jnp.asarray(idx),
                   mask=jnp.asarray(msk), values=jnp.asarray(val),
                   nnz=int(rows.shape[0]))

    @staticmethod
    def from_entries(keys, vals, shape, pad_deg_to: int = 8) -> "ELL":
        """Build from flat row-major entry keys (``row * ncols + col``) —
        the spelling the COO set algebra (repro.core.coo) hands back from
        the sparse element-wise / assign / extract paths."""
        w = max(shape[1], 1)
        keys = np.asarray(keys, dtype=np.int64)
        return ELL.from_coo(keys // w, keys % w, vals, shape,
                            pad_deg_to=pad_deg_to)

    @staticmethod
    def from_dense(A, pad_deg_to: int = 8) -> "ELL":
        A = np.asarray(A)
        r, c = np.nonzero(A)
        return ELL.from_coo(r, c, A[r, c].astype(np.float32), A.shape,
                            pad_deg_to=pad_deg_to)

    def to_dense(self) -> jnp.ndarray:
        n, m = self.shape
        out = np.zeros((n, m), dtype=np.float32)
        idx = np.asarray(self.indices)
        msk = np.asarray(self.mask)
        val = np.asarray(self.values)
        r, s = np.nonzero(msk)
        out[r, idx[r, s]] = val[r, s]
        return jnp.asarray(out)

    def transpose(self) -> "ELL":
        idx = np.asarray(self.indices)
        msk = np.asarray(self.mask)
        val = np.asarray(self.values)
        r, s = np.nonzero(msk)
        return ELL.from_coo(idx[r, s], r, val[r, s],
                            (self.shape[1], self.shape[0]))

    def to_coo(self):
        """Host-side COO extraction (snapshot/persistence path)."""
        idx = np.asarray(self.indices)
        msk = np.asarray(self.mask)
        val = np.asarray(self.values)
        r, s = np.nonzero(msk)
        return r.astype(np.int64), idx[r, s].astype(np.int64), val[r, s]
