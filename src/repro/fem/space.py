"""H1-conforming tensor-product finite element space on a structured hex
mesh, with the E-vector <-> L-vector transitions (the G / G^T operators of
the MFEM chain A = P^T G^T B^T D B G P).

Global scalar DoFs live on the tensor grid of GLL nodes:
``(Nx, Ny, Nz) = (nx*p + 1, ny*p + 1, nz*p + 1)`` with lexicographic
numbering (x fastest).  The displacement L-vector is stored as
``(ndof, 3)``; the E-vector as ``(nelem, 3, D1D, D1D, D1D)`` with layout
``[e, c, iz, iy, ix]`` (x fastest — the unit-stride direction of the
paper's X-contraction).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.basis import BasisTables, basis_tables
from repro.fem.mesh import HexMesh

__all__ = ["H1Space"]

VDIM = 3

# Face name -> (axis, side) for the box boundary.
_FACES = {
    "x0": (0, 0), "x1": (0, 1),
    "y0": (1, 0), "y1": (1, 1),
    "z0": (2, 0), "z1": (2, 1),
}


@dataclasses.dataclass(frozen=True)
class H1Space:
    """Vector-valued H1 space of degree p on a structured hex mesh."""

    mesh: HexMesh
    p: int

    # -- basic sizes --------------------------------------------------------
    @property
    def tables(self) -> BasisTables:
        return basis_tables(self.p)

    @property
    def d1d(self) -> int:
        return self.p + 1

    @property
    def node_grid(self) -> tuple[int, int, int]:
        m = self.mesh
        return (m.nx * self.p + 1, m.ny * self.p + 1, m.nz * self.p + 1)

    @property
    def nscalar(self) -> int:
        nx, ny, nz = self.node_grid
        return nx * ny * nz

    @property
    def ndof(self) -> int:
        """True (vector) DoF count, the paper's reported metric."""
        return VDIM * self.nscalar

    @property
    def nelem(self) -> int:
        return self.mesh.nelem

    # -- element-restriction indices ----------------------------------------
    @functools.cached_property
    def gather_ids(self) -> np.ndarray:
        """(nelem, D1D, D1D, D1D) int32 global scalar-node ids, layout
        [e, iz, iy, ix]."""
        p, d1 = self.p, self.d1d
        m = self.mesh
        nx_n, ny_n, _ = self.node_grid
        ex = np.arange(m.nx)
        ey = np.arange(m.ny)
        ez = np.arange(m.nz)
        loc = np.arange(d1)
        gx = ex[:, None] * p + loc[None, :]  # (nx, D1D)
        gy = ey[:, None] * p + loc[None, :]
        gz = ez[:, None] * p + loc[None, :]
        # e = ex + nx*(ey + ny*ez); build ids[ez, ey, ex, iz, iy, ix].
        ids = (
            gx[None, None, :, None, None, :]
            + nx_n * gy[None, :, None, None, :, None]
            + nx_n * ny_n * gz[:, None, None, :, None, None]
        )
        ids = ids.reshape(m.nelem, d1, d1, d1)
        return ids.astype(np.int32)

    @functools.cached_property
    def dof_multiplicity(self) -> np.ndarray:
        """(nscalar,) number of elements sharing each node (for tests and
        counting-based restrictions)."""
        return np.bincount(self.gather_ids.reshape(-1), minlength=self.nscalar)

    # -- E <-> L ---------------------------------------------------------------
    # The mesh is a structured box, so the element restriction is dense
    # data movement: along each axis element e owns nodes e*p .. e*p+p,
    # i.e. a reshape of the first n*p nodes plus a strided slice for the
    # shared last node.  No index gather or scatter-add: on a TPU those
    # move one 3-wide row per (element, node) and dominated the operator
    # apply at the paper's sizes.  ``gather_ids`` states the same map as
    # indices (assembly and tests use it).
    def to_evec(self, u):
        """L-vector (nscalar, 3) -> E-vector (nelem, 3, D1D, D1D, D1D)."""
        return _to_evec(u, self.mesh.shape, self.p)

    def scatter_add(self, ye):
        """E-vector (nelem, 3, D1D, D1D, D1D) -> L-vector (nscalar, 3) via
        G^T (sum of element contributions at shared nodes)."""
        return _scatter_add(ye, self.mesh.shape, self.p)

    # -- node coordinates ------------------------------------------------------
    @functools.cached_property
    def node_coords_1d(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Physical 1D node coordinates along each axis (reference box,
        before any linear_map)."""
        out = []
        for n_el, L in zip(self.mesh.shape, self.mesh.lengths):
            h = L / n_el
            gll01 = (self.tables.nodes + 1.0) / 2.0  # [0, 1]
            xs = (np.arange(n_el)[:, None] * h + gll01[None, :] * h)
            # Merge shared endpoints: take all but last node of each element.
            xs = np.concatenate([xs[:, :-1].reshape(-1), [L]])
            out.append(xs)
        return tuple(out)

    def node_coords(self) -> np.ndarray:
        """(nscalar, 3) physical node coordinates (x fastest)."""
        xs, ys, zs = self.node_coords_1d
        X, Y, Z = np.meshgrid(xs, ys, zs, indexing="ij")
        pts = np.stack(
            [X.ravel(order="F"), Y.ravel(order="F"), Z.ravel(order="F")], axis=-1
        )
        if self.mesh.linear_map is not None:
            pts = pts @ np.asarray(self.mesh.linear_map).T
        return pts

    # -- boundary -----------------------------------------------------------
    def face_node_ids(self, face: str) -> np.ndarray:
        """Scalar node ids on a box face ('x0', 'x1', 'y0', ...)."""
        axis, side = _FACES[face]
        nx, ny, nz = self.node_grid
        ix = np.arange(nx)
        iy = np.arange(ny)
        iz = np.arange(nz)
        sel = [ix, iy, iz]
        sel[axis] = np.array([0 if side == 0 else self.node_grid[axis] - 1])
        IX, IY, IZ = np.meshgrid(*sel, indexing="ij")
        ids = IX + nx * (IY + ny * IZ)
        return ids.reshape(-1).astype(np.int32)

    def essential_mask(self, faces=("x0",)) -> np.ndarray:
        """(nscalar, 3) bool — True where the DoF is Dirichlet-constrained.
        The paper clamps all displacement components on boundary attribute 1
        (the x=0 face of the beam)."""
        mask = np.zeros((self.nscalar, VDIM), dtype=bool)
        for f in faces:
            mask[self.face_node_ids(f)] = True
        return mask

    # -- load vectors ---------------------------------------------------------
    def traction_rhs(self, face: str, traction, dtype=np.float64) -> np.ndarray:
        """Assemble F_i = int_Gamma t . phi_i dGamma on a box face with a
        constant traction vector (paper: t = (0, 0, -1e-2) on attr 2 = x1).

        Uses the tensor-product face quadrature; only the basis functions of
        face-adjacent elements are nonzero there, and on the structured grid
        these reduce to the face node grid directly.
        """
        t = np.asarray(traction, dtype=dtype)
        axis, _ = _FACES[face]
        tb = self.tables
        # 1D "lumped" row sums: s[i] = sum_q w_q B[q, i] per tangential axis,
        # times h/2 per element; assembled along the axis this becomes the 1D
        # mass-lumped weight vector on the global 1D node line.
        F = np.zeros((self.nscalar, VDIM), dtype=dtype)
        tang = [a for a in range(3) if a != axis]
        h = self.mesh.h
        # per-element 1D weights s (D1D,), assembled on the global line
        w1 = []
        for a in tang:
            s = (tb.qwts @ tb.B) * (h[a] / 2.0)  # (D1D,)
            n_el = self.mesh.shape[a]
            line = np.zeros(n_el * self.p + 1, dtype=dtype)
            for e in range(n_el):
                line[e * self.p : e * self.p + self.d1d] += s
            w1.append(line)
        # Face-jacobian correction for linear_map: scale by area factor.
        if self.mesh.linear_map is not None:
            A = np.asarray(self.mesh.linear_map)
            # area scaling = |(A e_t1) x (A e_t2)| for unit tangent vectors
            F_scale = np.linalg.norm(np.cross(A[:, tang[0]], A[:, tang[1]]))
        else:
            F_scale = 1.0
        ids = self.face_node_ids(face)
        nx, ny, nz = self.node_grid
        grid = [nx, ny, nz]
        face_w = np.outer(w1[0], w1[1]).reshape(-1)  # (n_t1 * n_t2,) "ij"
        # face_node_ids uses meshgrid(indexing="ij") over (ix, iy, iz) with the
        # face axis collapsed; its flattened order matches outer(w_t1, w_t2).
        F[ids] = F_scale * face_w[:, None] * t[None, :]
        return F


# Jitted so that an eager call is one executable, not one per reshape,
# slice and concatenate (inside a traced program they inline).
@functools.partial(jax.jit, static_argnums=(1, 2))
def _to_evec(u, shape, p: int):
    nx, ny, nz = shape
    g = u.reshape(nz * p + 1, ny * p + 1, nx * p + 1, VDIM)
    for axis, n in enumerate((nz, ny, nx)):
        g = _split_axis(g, 2 * axis, n, p)
    # (nz, Dz, ny, Dy, nx, Dx, 3) -> (e, 3, Dz, Dy, Dx)
    g = g.transpose(0, 2, 4, 6, 1, 3, 5)
    return g.reshape(nx * ny * nz, VDIM, p + 1, p + 1, p + 1)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _scatter_add(ye, shape, p: int):
    nx, ny, nz = shape
    g = ye.reshape(nz, ny, nx, VDIM, p + 1, p + 1, p + 1)
    g = g.transpose(0, 4, 1, 5, 2, 6, 3)  # (nz, Dz, ny, Dy, nx, Dx, 3)
    for axis, n in reversed(list(enumerate((nz, ny, nx)))):
        g = _merge_axis(g, 2 * axis, n, p)
    return g.reshape(-1, VDIM)


def _split_axis(g, ax: int, n: int, p: int):
    """n*p + 1 nodes along axis ``ax`` -> (n, p + 1) at axes (ax, ax + 1):
    element e takes nodes e*p .. e*p + p."""
    g = jnp.moveaxis(g, ax, 0)
    body = g[: n * p].reshape((n, p) + g.shape[1:])
    out = jnp.concatenate([body, g[p::p][:, None]], axis=1)
    return jnp.moveaxis(out, (0, 1), (ax, ax + 1))


def _merge_axis(g, ax: int, n: int, p: int):
    """Transpose of :func:`_split_axis`: (n, p + 1) at axes (ax, ax + 1)
    -> n*p + 1 nodes, summing the node neighbouring elements share."""
    g = jnp.moveaxis(g, (ax, ax + 1), (0, 1))
    body, last = g[:, :p], g[:, p]
    body = body.at[1:, 0].add(last[:-1])
    out = jnp.concatenate(
        [body.reshape((n * p,) + body.shape[2:]), last[-1:]], axis=0
    )
    return jnp.moveaxis(out, 0, ax)
