"""Deterministic closed-form ridge regression from features to cycle counts.

The model is intentionally the simplest thing that ranks well: standardize
the integer feature matrix, center the targets, and solve the ridge normal
equations

    (Xs' Xs + ridge * n * I) beta = Xs' (y - mean(y))

once, in float64 under scoped x64 on the host's CPU device
(``jnp.linalg.solve`` — no iterative optimizer, no learning-rate knobs, no
RNG). For fixed inputs the
coefficients are bit-reproducible run to run, which is what lets tests pin
them with ``assert_array_equal`` and lets CI gate rank quality.

Prediction cost is one [B, F] @ [F] matmul — pruning thousands of candidate
placements costs microseconds, versus seconds-to-minutes of cycle-accurate
simulation each (the bridge ROADMAP asked for).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .features import FeatureExtractor


@dataclasses.dataclass(frozen=True)
class SurrogateModel:
    """Fitted ridge coefficients + the feature extractor they apply to."""

    extractor: FeatureExtractor
    mu: np.ndarray        # [F] float64 feature means (training set)
    sigma: np.ndarray     # [F] float64 feature scales (0 -> 1)
    beta: np.ndarray      # [F] float64 ridge coefficients
    y_mean: float         # training-target mean (intercept)
    ridge: float
    n_train: int

    def predict_batch(self, placements) -> np.ndarray:
        """[B] float64 predicted cycle counts of stacked [B, N] placements."""
        x = self.extractor.features_batch(placements)
        return self.y_mean + ((x - self.mu) / self.sigma) @ self.beta

    def predict(self, placement) -> float:
        return float(self.predict_batch(np.asarray(placement)[None])[0])

    def rank(self, placements) -> np.ndarray:
        """[B] candidate indices, best (fewest predicted cycles) first.

        Stable sort: prediction ties keep candidate order, so the ranking is
        as deterministic as the coefficients.
        """
        return np.argsort(self.predict_batch(placements), kind="stable")


def fit_features(
    extractor: FeatureExtractor,
    features: np.ndarray,
    cycles: np.ndarray,
    *,
    ridge: float = 1e-3,
) -> SurrogateModel:
    """Closed-form ridge fit of ``features [n, F] -> cycles [n]``."""
    x = np.asarray(features, dtype=np.float64)
    y = np.asarray(cycles, dtype=np.float64)
    if x.ndim != 2 or y.shape != (x.shape[0],):
        raise ValueError(
            f"need features [n, F] and cycles [n]; got {x.shape} / {y.shape}")
    if x.shape[0] < 2:
        raise ValueError(f"need >= 2 training placements, got {x.shape[0]}")
    mu = x.mean(axis=0)
    sigma = x.std(axis=0)
    sigma = np.where(sigma == 0, 1.0, sigma)
    y_mean = float(y.mean())
    # A deliberate host computation: the system is [F, F] with F ~ 30, and
    # XLA:TPU has no float64 LU ("Only F32 and C64 types are implemented in
    # LuDecomposition"). Pinning it to the CPU device on every backend runs
    # the same XLA:CPU program everywhere, so beta stays bit-identical across
    # machines (numpy's LAPACK solve differs from it in the last bits).
    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        xs = (jnp.asarray(x) - jnp.asarray(mu)) / jnp.asarray(sigma)
        yc = jnp.asarray(y) - y_mean
        gram = xs.T @ xs + ridge * x.shape[0] * jnp.eye(x.shape[1])
        beta = jnp.linalg.solve(gram, xs.T @ yc)
    return SurrogateModel(
        extractor=extractor,
        mu=mu, sigma=sigma, beta=np.asarray(beta, dtype=np.float64),
        y_mean=y_mean, ridge=float(ridge), n_train=int(x.shape[0]),
    )


def spearman(a, b) -> float:
    """Spearman rank correlation with average-rank ties (pure numpy)."""

    def _ranks(v):
        v = np.asarray(v, dtype=np.float64)
        order = np.argsort(v, kind="stable")
        ranks = np.empty(len(v), dtype=np.float64)
        ranks[order] = np.arange(len(v), dtype=np.float64)
        # Average ranks across ties so equal values compare equal.
        uniq, inv, counts = np.unique(v, return_inverse=True,
                                      return_counts=True)
        sums = np.zeros(len(uniq))
        np.add.at(sums, inv, ranks)
        return sums[inv] / counts[inv]

    ra, rb = _ranks(a), _ranks(b)
    ra -= ra.mean()
    rb -= rb.mean()
    denom = float(np.sqrt((ra * ra).sum() * (rb * rb).sum()))
    return float((ra * rb).sum() / denom) if denom else 0.0
