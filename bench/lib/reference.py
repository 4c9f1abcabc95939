"""Plain float64 reference of the beam problem, in NumPy alone.

The same mathematics as the system under test, written out again from
the problem statement: the MFEM ``beam-hex`` cantilever (an 8 x 1 x 1
box, attribute 1 for x < 4 and 2 for x >= 4, clamped on x = 0, a
constant traction on x = 8), refined uniformly, with degree-p Lagrange
elements on Gauss-Lobatto-Legendre nodes and the (p + 2)-point Gauss
rule.  The bilinear form is isotropic linear elasticity,

    a(u, v) = int lambda div(u) div(v) + 2 mu eps(u) : eps(v),

applied element by element with sum factorization (three 1D
contractions each way) and summed at shared nodes.  It imports nothing
from the program and takes nothing the program made.

Layouts follow the service's public data: an L-vector is
``(nscalar, 3)`` with node ``ix + Nx * (iy + Ny * iz)`` (x fastest) on
the ``(Nx, Ny, Nz) = (nx p + 1, ny p + 1, nz p + 1)`` node grid, and
elements are numbered ``ex + nx * (ey + ny * ez)``.
"""

from __future__ import annotations

import numpy as np
from numpy.polynomial import legendre

__all__ = ["Beam", "tables"]

BEAM_LENGTHS = (8.0, 1.0, 1.0)
BEAM_COARSE = (8, 1, 1)


def tables(p: int):
    """``(B, G, w)``: Lagrange basis values ``B[q, i]`` and derivatives
    ``G[q, i]`` on [-1, 1] at the p + 2 Gauss points, and their weights."""
    inner = legendre.Legendre.basis(p).deriv().roots() if p > 1 else []
    nodes = np.concatenate([[-1.0], np.sort(np.real(inner)), [1.0]])
    pts, w = legendre.leggauss(p + 2)
    n = p + 1
    B = np.ones((len(pts), n))
    G = np.zeros((len(pts), n))
    for i in range(n):
        others = [j for j in range(n) if j != i]
        den = np.prod([nodes[i] - nodes[j] for j in others])
        for j in others:
            B[:, i] *= pts - nodes[j]
        B[:, i] /= den
        for k in others:
            term = np.ones_like(pts)
            for j in others:
                if j != k:
                    term *= pts - nodes[j]
            G[:, i] += term
        G[:, i] /= den
    return B, G, w


class Beam:
    """The refined beam at degree ``p``: operator, load and residual."""

    def __init__(self, p: int, refine: int, block: int = 256):
        self.p, self.refine, self.block = p, refine, block
        f = 2**refine
        self.shape = tuple(n * f for n in BEAM_COARSE)  # (nx, ny, nz)
        nx, ny, nz = self.shape
        self.nelem = nx * ny * nz
        self.grid = (nx * p + 1, ny * p + 1, nz * p + 1)
        self.nscalar = int(np.prod(self.grid))
        self.ndof = 3 * self.nscalar
        self.h = np.array([L / n for L, n in zip(BEAM_LENGTHS, self.shape)])
        self.B, self.G, self.w = tables(p)
        # Element attribute: 1 where the element's centre has x < 4.
        ex = np.arange(nx)
        attr_x = np.where((ex + 0.5) * self.h[0] < BEAM_LENGTHS[0] / 2, 1, 2)
        self.attr = np.tile(attr_x, ny * nz)

    def fields(self, materials: dict) -> tuple[np.ndarray, np.ndarray]:
        """Per-element (lambda, mu) from an attribute -> (lambda, mu) map."""
        lam = np.empty(self.nelem)
        mu = np.empty(self.nelem)
        for a, (la, m) in materials.items():
            sel = self.attr == int(a)
            lam[sel], mu[sel] = la, m
        return lam, mu

    def ess_mask(self) -> np.ndarray:
        """(nscalar, 3) bool: every component clamped on x = 0."""
        Nx, Ny, Nz = self.grid
        m = np.zeros((Nz, Ny, Nx, 3), dtype=bool)
        m[:, :, 0, :] = True
        return m.reshape(-1, 3)

    def load(self, traction) -> np.ndarray:
        """F_i = int_{x = 8} t . phi_i dS, with the clamped rows zeroed."""
        p = self.p
        Nx, Ny, Nz = self.grid
        lines = []
        for a in (1, 2):  # y, then z: the face's tangential axes
            s = (self.w @ self.B) * (self.h[a] / 2.0)
            line = np.zeros(self.shape[a] * p + 1)
            for e in range(self.shape[a]):
                line[e * p: e * p + p + 1] += s
            lines.append(line)
        F = np.zeros((Nz, Ny, Nx, 3))
        F[:, :, -1, :] = (
            np.outer(lines[1], lines[0])[:, :, None]
            * np.asarray(traction, float)[None, None, :]
        )
        F = F.reshape(-1, 3)
        F[self.ess_mask()] = 0.0
        return F

    def apply(self, x: np.ndarray, lam: np.ndarray, mu: np.ndarray):
        """y = A x for an unconstrained L-vector ``x`` (nscalar, 3)."""
        p, D = self.p, self.p + 1
        nx, ny, nz = self.shape
        Nx, Ny, Nz = self.grid
        u = np.asarray(x, np.float64).reshape(Nz, Ny, Nx, 3)
        y = np.zeros_like(u)
        # Element-local views: u[ez, ey, ex, c, iz, iy, ix].
        win = u
        for ax, n in ((0, nz), (1, ny), (2, nx)):
            win = np.lib.stride_tricks.sliding_window_view(win, D, axis=ax)
            win = np.take(win, np.arange(n) * p, axis=ax)
        # win: (nz, ny, nx, 3, Dz, Dy, Dx)
        win = win.reshape(self.nelem, 3, D, D, D)
        out = np.empty_like(win)
        w3 = np.einsum("i,j,k->ijk", self.w, self.w, self.w)
        wdet = w3 * np.prod(self.h / 2.0)
        jinv = 2.0 / self.h  # diagonal of J^{-1} for the axis-aligned box
        for e0 in range(0, self.nelem, self.block):
            sl = slice(e0, min(e0 + self.block, self.nelem))
            out[sl] = self._element_block(
                win[sl], lam[sl], mu[sl], wdet, jinv
            )
        # Sum element contributions at shared nodes.
        out = out.reshape(nz, ny, nx, 3, D, D, D)
        for iz in range(D):
            for iy in range(D):
                for ix in range(D):
                    y[iz: iz + nz * p: p, iy: iy + ny * p: p,
                      ix: ix + nx * p: p, :] += out[..., iz, iy, ix]
        return y.reshape(-1, 3)

    def _element_block(self, ue, lam, mu, wdet, jinv):
        """Sum-factorized element action on (E, 3, Dz, Dy, Dx)."""
        B, G = self.B, self.G
        # d/dxi along x, y, z at the Gauss points: (E, 3, Qz, Qy, Qx) each.
        def interp(u, Az, Ay, Ax):
            t = np.einsum("qx,eczyx->eczyq", Ax, u, optimize=True)
            t = np.einsum("qy,eczyx->eczqx", Ay, t, optimize=True)
            return np.einsum("qz,eczyx->ecqyx", Az, t, optimize=True)

        grad = np.stack(
            [interp(ue, B, B, G) * jinv[0],
             interp(ue, B, G, B) * jinv[1],
             interp(ue, G, B, B) * jinv[2]],
            axis=2,
        )  # (E, c, j, Q, Q, Q): d u_c / d x_j
        lw = lam[:, None, None, None] * wdet
        mw = mu[:, None, None, None] * wdet
        div = grad[:, 0, 0] + grad[:, 1, 1] + grad[:, 2, 2]
        sym = grad + np.swapaxes(grad, 1, 2)
        sig = mw[:, None, None] * sym
        for c in range(3):
            sig[:, c, c] += lw * div
        # v-side: sum_j sig_cj * jinv_j * d phi / d xi_j, transposed.
        def interp_t(s, Az, Ay, Ax):
            t = np.einsum("qz,ecqyx->eczyx", Az, s, optimize=True)
            t = np.einsum("qy,eczqx->eczyx", Ay, t, optimize=True)
            return np.einsum("qx,eczyq->eczyx", Ax, t, optimize=True)

        return (
            interp_t(sig[:, :, 0] * jinv[0], B, B, G)
            + interp_t(sig[:, :, 1] * jinv[1], B, G, B)
            + interp_t(sig[:, :, 2] * jinv[2], G, B, B)
        )

    def residual(self, x, traction, lam, mu) -> float:
        """||b - A_c x|| / ||b||, where A_c is the operator with the
        clamped rows and columns replaced by the identity (the system the
        service solves) and b the load with clamped rows zeroed."""
        x = np.asarray(x, np.float64).reshape(-1, 3)
        m = self.ess_mask()
        b = self.load(traction)
        ax = self.apply(np.where(m, 0.0, x), lam, mu)
        r = b - np.where(m, x, ax)
        return float(np.linalg.norm(r) / np.linalg.norm(b))
