"""Group-relative position encodings (the paper's core abstraction).

The paper (Pronovost et al., "Linear Memory SE(2) Invariant Attention")
frames relative attention for a group ``G`` via a triple of functions

    phi   : G -> R^{d x d}
    phi_q : G -> R^{d x c}
    phi_k : G -> R^{c x d}      with  phi(p_n^{-1} p_m) = phi_q(p_n) phi_k(p_m)

Algorithm 1 (quadratic memory) applies ``phi`` to every query/key pair;
Algorithm 2 (linear memory) pre-transforms queries with ``phi_q^T``, keys and
values with ``phi_k``, runs a *standard* SDPA kernel (e.g. Flash Attention),
and post-transforms the output with ``phi_q``.

Every encoding below implements both views:

  * ``transform_q / transform_k / transform_v / untransform_out`` — the
    linear-memory (Algorithm 2) factorized form, O(N) memory.
  * ``apply_phi(p_rel, vec)`` — the exact target ``phi(p_rel) @ vec`` used by
    the quadratic oracle (Algorithm 1) and by approximation-error tests.

Encodings:

  * :class:`AbsoluteEncoding`  — no-op transforms; models add a learned pose
    embedding to token features instead (baseline in the paper's Table I).
  * :class:`Rope1D`            — G = R, classic rotary embeddings [Su et al.].
  * :class:`Rope2D`            — G = R^2, axis-aligned rotary blocks
    (translation invariant, not rotation invariant).
  * :class:`SE2Repr`           — G = SE(2) via the 3x3 homogeneous matrix
    representation (exact, GTA-like; unstable for large positions).
  * :class:`SE2Fourier`        — G = SE(2), the paper's contribution: block
    diagonal 2D rotations by (x_rel, y_rel, theta_rel), factorized through a
    truncated Fourier series in the query heading. Approximate but
    numerically well-behaved; invariance error is bounded by the series
    truncation error.

All transforms operate on the trailing feature dimension and broadcast over
any leading (batch / head / sequence) dimensions. Poses have trailing
dimension ``pose_dim`` (1 for R, 2 for R^2, 3 for SE(2)).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import fourier, se2


def _as_f32(x):
    return x.astype(jnp.float32)


def _rotate_pairs(x0, x1, cos, sin):
    """Apply rho(angle) with components given by (cos, sin) to pairs."""
    return x0 * cos - x1 * sin, x0 * sin + x1 * cos


class GroupEncoding:
    """Interface shared by all encodings."""

    name: str = "base"
    pose_dim: int = 0
    head_dim: int = 0

    @property
    def expanded_dim(self) -> int:
        """c — feature dim after phi_q^T / phi_k (equals head_dim for RoPE)."""
        return self.head_dim

    @property
    def expanded_v_dim(self) -> int:
        """Feature dim of a cached value row: ``expanded_dim`` when phi acts
        on values (phi_k-transformed values are what gets cached), else the
        raw head_dim. KV caches sized off this never need re-projection —
        ``transform_k``/``transform_v`` depend only on the token's own pose,
        so a cached row stays valid as the scene grows (the factorization
        property that makes incremental SE(2)-invariant decode sound; see
        docs/rollout.md)."""
        return self.expanded_dim if self.transforms_values else self.head_dim

    # --- Algorithm 2 (linear memory) ------------------------------------
    def transform_q(self, q, pose):
        return q

    def transform_k(self, k, pose):
        return k

    def transform_v(self, v, pose):
        return v

    def untransform_out(self, o, pose):
        return o

    # --- Algorithm 1 oracle ----------------------------------------------
    def apply_phi(self, p_rel, vec):
        """Exact ``phi(p_rel) @ vec``; p_rel ``(..., pose_dim)``, vec ``(..., d)``."""
        return vec

    @property
    def transforms_values(self) -> bool:
        """Whether phi acts on values too (needs output untransform)."""
        return False


@dataclasses.dataclass(frozen=True)
class AbsoluteEncoding(GroupEncoding):
    """No relative encoding; pose information is injected additively upstream."""

    head_dim: int = 0
    pose_dim: int = 3
    name: str = "absolute"


def rope_frequencies(num_freqs: int, base: float = 10000.0,
                     max_freq: float = 1.0) -> np.ndarray:
    """Geometric frequency ladder a la RoPE: max_freq * base^{-i/(n-1)}...

    We follow the RoFormer convention: frequencies base^{-2i/d} for
    i in [0, d/2); ``max_freq`` rescales the whole ladder (useful when the
    coordinate is metric rather than an integer token index).
    """
    if num_freqs == 1:
        return np.array([max_freq])
    i = np.arange(num_freqs)
    return max_freq * (base ** (-2.0 * i / (2.0 * num_freqs)))


def yarn_ramp_bounds(dim: int, base: float, original_max_position: int,
                     beta_fast: float, beta_slow: float) -> Tuple[int, int]:
    """YaRN's correction range (arXiv:2309.00071, Sec. 3.2): the pair
    indices at which a frequency makes ``beta_fast`` and ``beta_slow``
    rotations over the original context, floored and ceiled and clamped to
    [0, dim - 1], as DeepSeek-V2's ``yarn_find_correction_range``."""
    def index(rotations):
        return (dim * np.log(original_max_position / (rotations * 2 * np.pi))
                / (2 * np.log(base)))
    lo = int(np.floor(index(beta_fast)))
    hi = int(np.ceil(index(beta_slow)))
    return max(lo, 0), min(hi, dim - 1)


def yarn_frequencies(dim: int, base: float, factor: float,
                     original_max_position: int, beta_fast: float,
                     beta_slow: float) -> np.ndarray:
    """YaRN's blended rope frequencies, ``dim // 2`` of them: pair indices
    at or below the ramp's low bound keep the original frequency, those at
    or above its high bound take it divided by ``factor``, and a linear
    ramp joins them."""
    orig = rope_frequencies(dim // 2, base)
    lo, hi = yarn_ramp_bounds(dim, base, original_max_position, beta_fast,
                              beta_slow)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return orig / factor * ramp + orig * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor, ``0.1 m ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * float(np.log(factor)) + 1.0


@dataclasses.dataclass(frozen=True)
class Rope1D(GroupEncoding):
    """Rotary embeddings for G = R (token index or any scalar coordinate).

    Uses the "split half" layout (LLaMA convention): feature ``i`` pairs with
    feature ``i + head_dim // 2``.
    """

    head_dim: int = 64
    base: float = 10000.0
    max_freq: float = 1.0
    pose_dim: int = 1
    name: str = "rope1d"
    #: YaRN scaling as (factor, original_max_position, beta_fast,
    #: beta_slow): the frequencies of :func:`yarn_frequencies`
    yarn: Optional[Tuple[float, int, float, float]] = None

    def __post_init__(self):
        if self.head_dim % 2 != 0:
            raise ValueError(f"rope1d head_dim must be even, got {self.head_dim}")

    def _freqs(self, dtype):
        if self.yarn is not None:
            f = yarn_frequencies(self.head_dim, self.base, *self.yarn)
        else:
            f = rope_frequencies(self.head_dim // 2, self.base, self.max_freq)
        return jnp.asarray(f, dtype=dtype)

    def _cos_sin(self, pose):
        pos = pose[..., 0]
        ang = pos[..., None].astype(jnp.float32) * self._freqs(jnp.float32)
        return jnp.cos(ang), jnp.sin(ang)

    def _rotate(self, x, pose, sign):
        cos, sin = self._cos_sin(pose)
        cos, sin = cos.astype(x.dtype), (sign * sin).astype(x.dtype)
        h = self.head_dim // 2
        x0, x1 = x[..., :h], x[..., h:]
        r0, r1 = _rotate_pairs(x0, x1, cos, sin)
        return jnp.concatenate([r0, r1], axis=-1)

    def transform_q(self, q, pose):
        # phi_q(p)^T q = rho(-alpha p)^T q = rho(alpha p) q ... but matching
        # RoPE convention we rotate q by +p and k by +p so the score picks up
        # rho(p_m - p_n): q^T rho(-p_n)^T rho(p_m) k? Standard RoPE rotates
        # both by their own position; the score is then q^T rho(p_m - p_n) k.
        return self._rotate(q, pose, sign=+1.0)

    def transform_k(self, k, pose):
        return self._rotate(k, pose, sign=+1.0)

    def apply_phi(self, p_rel, vec):
        return self._rotate(vec, p_rel, sign=+1.0)


@dataclasses.dataclass(frozen=True)
class Rope2D(GroupEncoding):
    """Axis-aligned rotary embeddings for G = R^2 (paper Sec. II-D).

    First half of the feature dim encodes x, second half encodes y, each with
    its own geometric frequency ladder.
    """

    head_dim: int = 64
    base: float = 100.0
    max_freq: float = 1.0
    pose_dim: int = 2
    name: str = "rope2d"

    def __post_init__(self):
        if self.head_dim % 4 != 0:
            raise ValueError(f"rope2d head_dim must be divisible by 4, got {self.head_dim}")

    def _sub(self):
        return Rope1D(head_dim=self.head_dim // 2, base=self.base,
                      max_freq=self.max_freq)

    def _rotate(self, x, pose):
        sub = self._sub()
        h = self.head_dim // 2
        rx = sub.transform_q(x[..., :h], pose[..., 0:1])
        ry = sub.transform_q(x[..., h:], pose[..., 1:2])
        return jnp.concatenate([rx, ry], axis=-1)

    def transform_q(self, q, pose):
        return self._rotate(q, pose)

    def transform_k(self, k, pose):
        return self._rotate(k, pose)

    def apply_phi(self, p_rel, vec):
        return self._rotate(vec, p_rel)


def _log_spaced(n: int, lo: float, hi: float) -> np.ndarray:
    if n == 1:
        return np.array([hi])
    return np.exp(np.linspace(np.log(lo), np.log(hi), n))


@dataclasses.dataclass(frozen=True)
class SE2Repr(GroupEncoding):
    """SE(2) via the homogeneous 3x3 representation (paper Sec. II-E).

    phi(p) = psi(p), phi_q(p_n) = psi(p_n^{-1}), phi_k(p_m) = psi(p_m).
    Exact (no approximation) and c == d, but the score contains raw x/y
    coordinates, which the paper observes destabilizes training when
    positions are large. ``scales`` downscale positions per 3-wide block.
    """

    head_dim: int = 48
    min_scale: float = 0.25
    max_scale: float = 1.0
    pose_dim: int = 3
    name: str = "se2_repr"

    def __post_init__(self):
        if self.head_dim % 3 != 0:
            raise ValueError(f"se2_repr head_dim must be divisible by 3, got {self.head_dim}")

    @property
    def num_blocks(self) -> int:
        return self.head_dim // 3

    def _scales(self, dtype):
        return jnp.asarray(
            _log_spaced(self.num_blocks, self.min_scale, self.max_scale),
            dtype=dtype)

    def _apply_psi(self, x, pose, inverse: bool, transpose: bool):
        """Apply psi(pose) (optionally of the inverse pose, optionally
        transposed) blockwise to trailing dim."""
        *lead, d = x.shape
        nb = self.num_blocks
        xb = _as_f32(x).reshape(*lead, nb, 3)
        scales = self._scales(jnp.float32)
        p = pose.astype(jnp.float32)
        p = jnp.concatenate(
            [p[..., None, 0:2] * scales[:, None], p[..., None, 2:3]
             * jnp.ones_like(scales)[:, None]], axis=-1)  # (..., nb, 3)
        if inverse:
            p = se2.inverse(p)
        m = se2.matrix(p)  # (..., nb, 3, 3)
        if transpose:
            m = jnp.swapaxes(m, -1, -2)
        out = jnp.einsum("...ij,...j->...i", m, xb)
        return out.reshape(*lead, d).astype(x.dtype)

    def transform_q(self, q, pose):
        # q_tilde = phi_q(p)^T q = psi(p^{-1})^T q
        return self._apply_psi(q, pose, inverse=True, transpose=True)

    def transform_k(self, k, pose):
        return self._apply_psi(k, pose, inverse=False, transpose=False)

    def transform_v(self, v, pose):
        return self._apply_psi(v, pose, inverse=False, transpose=False)

    def untransform_out(self, o, pose):
        return self._apply_psi(o, pose, inverse=True, transpose=False)

    def apply_phi(self, p_rel, vec):
        return self._apply_psi(vec, p_rel, inverse=False, transpose=False)

    @property
    def transforms_values(self) -> bool:
        return True


@dataclasses.dataclass(frozen=True)
class SE2Fourier(GroupEncoding):
    """The paper's SE(2) Fourier encoding (Sec. III).

    Feature layout: ``head_dim`` must be divisible by 6; each 6-wide input
    block ``(x0, x1, y0, y1, t0, t1)`` is acted on by
    ``diag[rho(a_b * x_rel), rho(a_b * y_rel), rho(theta_rel)]`` where ``a_b``
    is the block's spatial scale. The factorized (linear memory) form expands
    each block to ``4F + 2`` features, so ``c = (head_dim / 6) * (4F + 2)``.

    ``num_terms`` (F) controls the Fourier truncation. Per the paper's Fig. 3,
    F = 12/18/28 reaches ~bf16-level approximation error for position
    magnitudes <= 2/4/8 respectively (positions should be downscaled so that
    ``max |a_b * (x, y)|`` stays within that budget).

    **Beyond-paper: scale-adaptive truncation** (``adaptive_terms=True``).
    The target function ``cos(a_b * u(theta))`` has Jacobi-Anger bandwidth
    ~ ``a_b * r_max``; the paper spends the same F on every block, but
    low-scale blocks are massively over-resolved. With adaptive truncation
    block ``b`` gets ``F_b ~= F * a_b / a_max`` (floored at 4), shrinking the
    expanded dim — and with it every q~/k~/v~ byte and attention MXU FLOP —
    by ~35-40% at matched worst-block error (measured in
    ``benchmarks/adaptive_basis.py``).
    """

    head_dim: int = 48
    num_terms: int = 18
    min_scale: float = 0.25
    max_scale: float = 1.0
    adaptive_terms: bool = False
    min_terms: int = 4
    term_margin: int = 3   # Jacobi-Anger tail: F_b = ceil(F*a_b/a_max)+margin
    pose_dim: int = 3
    name: str = "se2_fourier"

    def __post_init__(self):
        if self.head_dim % 6 != 0:
            raise ValueError(f"se2_fourier head_dim must be divisible by 6, got {self.head_dim}")
        if self.num_terms < 1:
            raise ValueError("num_terms must be >= 1")

    @property
    def num_blocks(self) -> int:
        return self.head_dim // 6

    def block_terms(self) -> Tuple[int, ...]:
        """Fourier basis size per block (all equal unless adaptive)."""
        if not self.adaptive_terms:
            return (self.num_terms,) * self.num_blocks
        scales = _log_spaced(self.num_blocks, self.min_scale, self.max_scale)
        return tuple(
            min(self.num_terms,
                max(self.min_terms,
                    int(np.ceil(self.num_terms * s / self.max_scale))
                    + self.term_margin))
            for s in scales)

    @property
    def expanded_dim(self) -> int:
        return sum(4 * f + 2 for f in self.block_terms())

    def _scales(self, dtype):
        return jnp.asarray(
            _log_spaced(self.num_blocks, self.min_scale, self.max_scale),
            dtype=dtype)

    def _split_blocks(self, x):
        *lead, d = x.shape
        return _as_f32(x).reshape(*lead, self.num_blocks, 6)

    def _scaled_xy(self, pose):
        """Per-block scaled (x, y); returns (..., nb) arrays plus theta (...,)."""
        scales = self._scales(jnp.float32)
        x = pose[..., 0:1].astype(jnp.float32) * scales
        y = pose[..., 1:2].astype(jnp.float32) * scales
        theta = pose[..., 2].astype(jnp.float32)
        return x, y, theta

    # -- query side -------------------------------------------------------
    def _query_pieces(self, pose):
        """v_n^{(x)}, v_n^{(y)} per block and the basis vector b_n."""
        x, y, theta = self._scaled_xy(pose)
        c, s = jnp.cos(theta)[..., None], jnp.sin(theta)[..., None]
        v_x = -x * c - y * s          # (..., nb)
        v_y = x * s - y * c           # (..., nb)
        b = fourier.eval_basis(theta, self.num_terms)  # (..., F)
        return v_x, v_y, b, theta

    def transform_q(self, q, pose):
        qb = self._split_blocks(q)                      # (..., nb, 6)
        v_x, v_y, b_full, theta = self._query_pieces(pose)
        ct, st = jnp.cos(theta)[..., None], jnp.sin(theta)[..., None]
        terms = self.block_terms()
        segs = []
        for bi, F in enumerate(terms):
            b = b_full[..., None, :F]                   # (..., 1, F)
            parts = []
            for (q0, q1, v) in ((qb[..., bi:bi + 1, 0], qb[..., bi:bi + 1, 1],
                                 v_x[..., bi:bi + 1]),
                                (qb[..., bi:bi + 1, 2], qb[..., bi:bi + 1, 3],
                                 v_y[..., bi:bi + 1])):
                cv, sv = jnp.cos(v), jnp.sin(v)
                r0, r1 = _rotate_pairs(q0, q1, cv, -sv)  # rho(-v) [q0; q1]
                parts.append(jnp.concatenate(
                    [r0[..., None] * b, r1[..., None] * b], axis=-1))
            t0, t1 = _rotate_pairs(qb[..., bi:bi + 1, 4], qb[..., bi:bi + 1, 5],
                                   ct, st)
            parts.append(jnp.stack([t0, t1], axis=-1))
            segs.append(jnp.concatenate(parts, axis=-1)[..., 0, :])
        res = jnp.concatenate(segs, axis=-1)            # (..., sum(4F_b + 2))
        return res.astype(q.dtype)

    # -- key side -----------------------------------------------------------
    def _key_coeffs(self, pose):
        """Quadrature Fourier coefficients, each (..., nb, F)."""
        x, y, _ = self._scaled_xy(pose)
        return fourier.xy_coefficients(x, y, self.num_terms)

    def _expand_k(self, k, pose):
        qb = self._split_blocks(k)                      # (..., nb, 6)
        gx, lx, gy, ly = self._key_coeffs(pose)
        theta = pose[..., 2].astype(jnp.float32)
        ct, st = jnp.cos(theta)[..., None], jnp.sin(theta)[..., None]
        terms = self.block_terms()
        segs = []
        for bi, F in enumerate(terms):
            parts = []
            for (k0, k1, gamma, lam) in (
                    (qb[..., bi:bi + 1, 0], qb[..., bi:bi + 1, 1],
                     gx[..., bi:bi + 1, :F], lx[..., bi:bi + 1, :F]),
                    (qb[..., bi:bi + 1, 2], qb[..., bi:bi + 1, 3],
                     gy[..., bi:bi + 1, :F], ly[..., bi:bi + 1, :F])):
                top = gamma * k0[..., None] - lam * k1[..., None]
                bot = lam * k0[..., None] + gamma * k1[..., None]
                parts.append(jnp.concatenate([top, bot], axis=-1))
            t0, t1 = _rotate_pairs(qb[..., bi:bi + 1, 4], qb[..., bi:bi + 1, 5],
                                   ct, st)
            parts.append(jnp.stack([t0, t1], axis=-1))
            segs.append(jnp.concatenate(parts, axis=-1)[..., 0, :])
        res = jnp.concatenate(segs, axis=-1)
        return res.astype(k.dtype)

    def transform_k(self, k, pose):
        return self._expand_k(k, pose)

    def transform_v(self, v, pose):
        return self._expand_k(v, pose)

    def untransform_out(self, o, pose):
        """o = phi_q(p_n) o_tilde, contracting (..., c) back to (..., d)."""
        *lead, c = o.shape
        of = _as_f32(o)
        v_x, v_y, b_full, theta = self._query_pieces(pose)
        ct, st = jnp.cos(theta), jnp.sin(theta)
        terms = self.block_terms()
        outs = []
        off = 0
        for bi, F in enumerate(terms):
            b = b_full[..., :F]
            seg = of[..., off:off + 4 * F + 2]
            off += 4 * F + 2
            for idx, v in ((0, v_x[..., bi]), (1, v_y[..., bi])):
                sub = seg[..., idx * 2 * F:(idx + 1) * 2 * F]
                top = jnp.sum(b * sub[..., :F], axis=-1)
                bot = jnp.sum(b * sub[..., F:], axis=-1)
                cv, sv = jnp.cos(v), jnp.sin(v)
                o0, o1 = _rotate_pairs(top, bot, cv, sv)  # rho(v) [top; bot]
                outs.extend([o0, o1])
            t0, t1 = _rotate_pairs(seg[..., 4 * F], seg[..., 4 * F + 1],
                                   ct, -st)
            outs.extend([t0, t1])
        res = jnp.stack(outs, axis=-1)   # (..., nb*6) grouped per block
        return res.astype(o.dtype)

    # -- oracle ---------------------------------------------------------------
    def apply_phi(self, p_rel, vec):
        """Exact target: diag[rho(a_b x_rel), rho(a_b y_rel), rho(theta_rel)] v."""
        vb = self._split_blocks(vec)                    # (..., nb, 6)
        scales = self._scales(jnp.float32)
        xr = p_rel[..., 0:1].astype(jnp.float32) * scales
        yr = p_rel[..., 1:2].astype(jnp.float32) * scales
        tr = p_rel[..., 2].astype(jnp.float32)[..., None] * jnp.ones_like(scales)
        outs = []
        for ang, i0 in ((xr, 0), (yr, 2), (tr, 4)):
            c, s = jnp.cos(ang), jnp.sin(ang)
            r0, r1 = _rotate_pairs(vb[..., i0], vb[..., i0 + 1], c, s)
            outs.extend([r0, r1])
        res = jnp.stack([outs[0], outs[1], outs[2], outs[3], outs[4], outs[5]],
                        axis=-1)
        *lead, nb, six = res.shape
        return res.reshape(*lead, nb * six).astype(vec.dtype)

    @property
    def transforms_values(self) -> bool:
        return True


ENCODINGS: Dict[str, type] = {
    "absolute": AbsoluteEncoding,
    "rope1d": Rope1D,
    "rope2d": Rope2D,
    "se2_repr": SE2Repr,
    "se2_fourier": SE2Fourier,
}


def make_encoding(name: str, head_dim: int, **kwargs) -> GroupEncoding:
    if name not in ENCODINGS:
        raise ValueError(f"unknown encoding {name!r}; options: {sorted(ENCODINGS)}")
    if name == "absolute":
        return AbsoluteEncoding(head_dim=head_dim)
    return ENCODINGS[name](head_dim=head_dim, **kwargs)
