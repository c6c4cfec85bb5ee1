"""A seeded, time-evolving E3SM-like field on fixed locations.

Copied from ``repro.data.spatial.e3sm_like_field`` (the program's synthetic
E3SM slice: 48,602 points uniform on the sphere, a latitudinal trend, a
random-Fourier-feature Gaussian random field and observation noise), so
that no later change to the program can move the benchmark's data.
Two changes make it an in-situ stream:

* the locations come from a fixed seed, as a simulation's grid does, so
  every run partitions the same points into the same padded shapes;
* the random field is a rotation between two independent fields,
  ``cos(theta_t) F1 + sin(theta_t) F2``, with fresh noise each slice, so
  slice t costs O(n) after set-up and every slice differs.
"""
from __future__ import annotations

import numpy as np

LOCATION_SEED = 0  # the simulation's grid does not change between runs


def sphere_locations(n: int) -> tuple[np.ndarray, np.ndarray]:
    """(x, lonlat): the GP inputs (lon/36, lat/18) and raw degrees of n
    points uniform on the sphere, from the fixed location seed."""
    rng = np.random.default_rng(LOCATION_SEED)
    lon = 360.0 * rng.uniform(size=n)
    lat = np.degrees(np.arcsin(2.0 * rng.uniform(size=n) - 1.0))
    lonlat = np.stack([lon, lat], axis=-1)
    x = np.stack([lon / 36.0, lat / 18.0], axis=-1).astype(np.float32)
    return x, lonlat


def _unit_vectors(lonlat: np.ndarray) -> np.ndarray:
    lon = np.radians(lonlat[:, 0])
    lat = np.radians(lonlat[:, 1])
    return np.stack(
        [np.cos(lat) * np.cos(lon), np.cos(lat) * np.sin(lon), np.sin(lat)], axis=-1
    )


def _grf(u: np.ndarray, rng: np.random.Generator, num_features: int, corr_length: float):
    w = rng.normal(scale=1.0 / corr_length, size=(num_features, 3))
    phi = rng.uniform(0.0, 2.0 * np.pi, size=num_features)
    a = rng.normal(size=num_features) * np.sqrt(2.0 / num_features)
    return np.cos(u @ w.T + phi) @ a


class Field:
    """Slices y_t of one seeded field over the fixed locations.

    ``slice(t)`` is the standardized observation vector at time step t;
    slice 0 is the state the model was fitted to before the run."""

    def __init__(
        self,
        n: int,
        seed: int,
        *,
        num_features: int = 256,
        corr_length: float = 0.35,
        grf_amplitude: float = 6.0,
        noise_sd: float = 0.5,
        phase_per_step: float = 0.05,
    ):
        self.x, lonlat = sphere_locations(n)
        self._seed = seed
        rng = np.random.default_rng(seed)
        u = _unit_vectors(lonlat)
        self._f1 = grf_amplitude * _grf(u, rng, num_features, corr_length)
        self._f2 = grf_amplitude * _grf(u, rng, num_features, corr_length)
        self._trend = 32.0 * np.cos(np.radians(lonlat[:, 1])) ** 2 - 12.0
        self._noise_sd = noise_sd
        self._phase = phase_per_step

    def slice(self, t: int) -> np.ndarray:
        theta = self._phase * t
        rng = np.random.default_rng([self._seed, t])
        y = (
            self._trend
            + np.cos(theta) * self._f1
            + np.sin(theta) * self._f2
            + rng.normal(scale=self._noise_sd, size=self.x.shape[0])
        )
        return ((y - y.mean()) / y.std()).astype(np.float32)
