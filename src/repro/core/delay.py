"""Critical-path delay modelling (paper Sec. III-A..D).

The paper's flow is: synthesize a 256x256 int8 systolic array (14 nm PDK,
0.9 V, 1.6 ns clock), extract the 100 worst timing paths with PrimeTime,
characterise ``delay(dVth_p, dVth_n, V_DD)`` in HSPICE, and fit a ternary
sixth-degree polynomial (their RMSE: 5.85e-5 ns against a ~1.5 ns nominal).

No EDA tooling exists in this environment, so the *ground truth generator* is
replaced by an analytical alpha-power-law path model (DESIGN.md Sec. 2) —

    d_i(V, dp, dn) = w_i * [ d_wire
                             + d_p * V / (V - Vth_p0 - dp)**alpha
                             + d_n * V / (V - Vth_n0 - dn)**alpha ]

with per-path scale factors ``w_i`` drawn from a seeded population whose
worst path hits exactly ``D_CRIT_NOM`` at the fresh nominal point.  The
paper's own *polynomial-fitting step is preserved verbatim*: the AVS
framework only ever consumes the fitted polynomial, so a real HSPICE sweep
can be substituted without touching anything downstream.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from .constants import D_CRIT_NOM, V_NOM

# Fitting ranges: dVth in [0, 150] mV, V_DD in [0.88, 1.06] V.
DP_RANGE = (0.0, 0.150)
DN_RANGE = (0.0, 0.150)
V_RANGE = (0.88, 1.06)
TOTAL_DEGREE = 6


@dataclasses.dataclass(frozen=True)
class PathModel:
    """Alpha-power-law ground-truth model of the worst-path population."""
    alpha: float = 1.30
    vth_p0: float = 0.38
    vth_n0: float = 0.36
    wire_frac: float = 0.30   # fraction of nominal delay that is RC / non-FET
    pn_split: float = 0.50    # PMOS share of the FET-limited delay
    n_paths: int = 100
    spread: float = 0.035     # relative spread of the worst-path population
    seed: int = 20260715

    def stage_delay(self, V, dp, dn):
        """Normalised (w_i = 1) path delay in seconds."""
        V = jnp.asarray(V)
        f_p = V / jnp.maximum(V - self.vth_p0 - dp, 1e-3) ** self.alpha
        f_n = V / jnp.maximum(V - self.vth_n0 - dn, 1e-3) ** self.alpha
        f_p0 = V_NOM / (V_NOM - self.vth_p0) ** self.alpha
        f_n0 = V_NOM / (V_NOM - self.vth_n0) ** self.alpha
        fet = self.pn_split * f_p / f_p0 + (1.0 - self.pn_split) * f_n / f_n0
        return D_CRIT_NOM * (self.wire_frac + (1.0 - self.wire_frac) * fet)

    def path_weights(self) -> np.ndarray:
        """Per-path scale factors, sorted descending; w_0 = 1 (critical)."""
        rng = np.random.default_rng(self.seed)
        eps = np.abs(rng.normal(0.0, self.spread, self.n_paths - 1))
        w = np.concatenate([[1.0], 1.0 - np.sort(eps)])
        return w

    def path_delays(self, V, dp, dn) -> jnp.ndarray:
        """All worst-path delays [s], shape (n_paths,) (+ broadcasts)."""
        base = self.stage_delay(V, dp, dn)
        return jnp.asarray(self.path_weights()) * base

    def critical_delay(self, V, dp, dn):
        """Critical-path (w_0 = 1) delay — the quantity the AVS loop watches.

        The paper characterises the 100 worst paths in HSPICE and averages to
        de-noise; our analytical generator is noise-free, so the polynomial is
        fitted to the critical path directly (nominal 1.542 ns at 0.90 V) and
        the population enters only the BER model.
        """
        return self.stage_delay(V, dp, dn)

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "PathModel":
        return cls(**d)


def _monomial_exponents(total_degree: int = TOTAL_DEGREE):
    """All (a, b, c) with a + b + c <= total_degree (84 terms for degree 6)."""
    return [
        (a, b, c)
        for a, b, c in itertools.product(range(total_degree + 1), repeat=3)
        if a + b + c <= total_degree
    ]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DelayPolynomial:
    """Ternary degree-6 polynomial ``delay(dp, dn, V)`` in seconds.

    Variables are affinely scaled to [-1, 1] over the fitting box before
    monomial expansion for conditioning.  Evaluation is pure JAX.
    """
    coeffs: jnp.ndarray              # (n_terms,)
    exponents: jnp.ndarray           # (n_terms, 3) int
    centers: jnp.ndarray             # (3,)
    halfspans: jnp.ndarray           # (3,)
    rmse: float = 0.0

    def tree_flatten(self):
        return ((self.coeffs, self.exponents, self.centers, self.halfspans),
                (self.rmse,))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, rmse=aux[0])

    def __call__(self, dp, dn, V):
        x = (jnp.stack(jnp.broadcast_arrays(
            jnp.asarray(dp, jnp.float32), jnp.asarray(dn, jnp.float32),
            jnp.asarray(V, jnp.float32)), axis=-1) - self.centers) / self.halfspans
        # powers[..., k, d] = x_d ** k
        max_deg = TOTAL_DEGREE
        pows = jnp.stack([x ** k for k in range(max_deg + 1)], axis=-2)
        e = self.exponents
        terms = (pows[..., e[:, 0], 0] * pows[..., e[:, 1], 1]
                 * pows[..., e[:, 2], 2])
        # full f32 on every backend: a TPU's default matmul precision
        # would move the delay that decides every AVS boost
        return jnp.dot(terms, self.coeffs,
                       precision=jax.lax.Precision.HIGHEST)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "coeffs": np.asarray(self.coeffs, np.float64).tolist(),
            "exponents": np.asarray(self.exponents).tolist(),
            "centers": np.asarray(self.centers, np.float64).tolist(),
            "halfspans": np.asarray(self.halfspans, np.float64).tolist(),
            "rmse": float(self.rmse),
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "DelayPolynomial":
        return cls(
            coeffs=jnp.asarray(d["coeffs"], jnp.float32),
            exponents=jnp.asarray(d["exponents"], jnp.int32),
            centers=jnp.asarray(d["centers"], jnp.float32),
            halfspans=jnp.asarray(d["halfspans"], jnp.float32),
            rmse=float(d["rmse"]),
        )


def fit_delay_polynomial(path_model: PathModel, *, grid: int = 13,
                         total_degree: int = TOTAL_DEGREE) -> DelayPolynomial:
    """Least-squares fit of the mean worst-path delay over the fitting box."""
    dps = np.linspace(*DP_RANGE, grid)
    dns = np.linspace(*DN_RANGE, grid)
    vs = np.linspace(*V_RANGE, grid + 1)
    DP, DN, VV = np.meshgrid(dps, dns, vs, indexing="ij")
    y = np.asarray(path_model.critical_delay(jnp.asarray(VV.ravel()),
                                             jnp.asarray(DP.ravel()),
                                             jnp.asarray(DN.ravel())), np.float64)

    centers = np.array([np.mean(DP_RANGE), np.mean(DN_RANGE), np.mean(V_RANGE)])
    halfspans = np.array([np.ptp(DP_RANGE) / 2, np.ptp(DN_RANGE) / 2,
                          np.ptp(V_RANGE) / 2])
    X = np.stack([DP.ravel(), DN.ravel(), VV.ravel()], axis=-1)
    Xs = (X - centers) / halfspans

    exps = _monomial_exponents(total_degree)
    basis = np.stack([
        Xs[:, 0] ** a * Xs[:, 1] ** b * Xs[:, 2] ** c for a, b, c in exps
    ], axis=-1)
    coeffs, *_ = np.linalg.lstsq(basis, y, rcond=None)
    rmse = float(np.sqrt(np.mean((basis @ coeffs - y) ** 2)))
    return DelayPolynomial(
        coeffs=jnp.asarray(coeffs, jnp.float32),
        exponents=jnp.asarray(np.array(exps), jnp.int32),
        centers=jnp.asarray(centers, jnp.float32),
        halfspans=jnp.asarray(halfspans, jnp.float32),
        rmse=rmse,
    )
