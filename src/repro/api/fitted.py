"""``fit`` and ``FittedPSVGP`` — train once, persist a parsimonious
artifact, serve forever.

The paper's in-situ story (§5/§6): the simulation trains the partitioned
surface where the data lives, persists a FEW-KB-PER-PARTITION summary
(inducing-point parameters + cached posterior factors — never the raw
field), and analyses answer queries against that artifact post hoc. This
module is that unit of exchange:

    fitted = api.fit(FitConfig(grid=8, m=10), (x, y))   # train
    fitted.save("runs/e3sm_t42/")                        # persist
    ...
    server = api.Server.from_artifact("runs/e3sm_t42/", ServeConfig(...))

The artifact directory holds ``artifact.json`` (FitConfig + grid geometry,
plain JSON — readable before jax initializes, which the sharded serving
path needs to size its device mesh) next to the ``repro.checkpoint``
npz/msgpack pytree of the trained parameters and the
``repro.core.posterior.PosteriorCache`` factors. Loading rebuilds the
serving bundle exactly: cached-factor prediction is bitwise-identical to
the in-memory model (gated in tests/test_api.py), and no retraining or
refactorization happens on the load path.

A LOADED artifact is a serving object: ``predict`` and ``Server`` work in
full, but the training-time topology tables (neighbor distribution,
direction permutations) are not persisted — resume training from a
``checkpoint.save_train_state`` checkpoint instead.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.config import FitConfig, RefitConfig
from repro.checkpoint import load_pytree, save_pytree
from repro.checkpoint import store as artifact_store
from repro.core import posterior, psvgp, svgp
from repro.core.blend import predict_blended
from repro.core.partition import PartitionGrid, make_grid, partition_data
from repro.gp.covariances import CovarianceParams, make_covariance
from repro.obs import span
from repro.optim import AdamState, adam_init

ARTIFACT_MANIFEST = "artifact.json"
ARTIFACT_FORMAT = 1
INPUT_DIM = 2  # spatial modeling: (lon, lat) / (x, y) coordinates


def _psvgp_config(cfg: FitConfig) -> psvgp.PSVGPConfig:
    """The one FitConfig -> PSVGPConfig mapping every entry point shares."""
    return psvgp.PSVGPConfig(
        svgp=svgp.SVGPConfig(
            num_inducing=cfg.m,
            input_dim=INPUT_DIM,
            covariance=cfg.covariance,
            jitter=cfg.jitter,
            whitened=cfg.whitened,
        ),
        delta=cfg.delta,
        batch_size=cfg.batch_size,
        learning_rate=cfg.learning_rate,
        comm=cfg.comm,
        seed=cfg.seed,
    )


def _zeros(*shape) -> jnp.ndarray:
    return jnp.zeros(shape, jnp.float32)


def _artifact_templates(cfg: FitConfig) -> tuple[svgp.SVGPParams, posterior.PosteriorCache]:
    """Shape/dtype templates for the checkpointed pytrees — derived from the
    FitConfig alone, which is why the manifest makes the artifact
    self-describing (``checkpoint.load_pytree`` restores INTO a template)."""
    P, m, d = cfg.num_partitions, cfg.m, INPUT_DIM
    params = svgp.SVGPParams(
        m_star=_zeros(P, m),
        s_tril=_zeros(P, m, m),
        z=_zeros(P, m, d),
        cov=CovarianceParams(log_lengthscale=_zeros(P, d), log_variance=_zeros(P)),
        log_beta=_zeros(P),
    )
    cache = posterior.PosteriorCache(
        z=_zeros(P, m, d),
        w=_zeros(P, m, m),
        u=_zeros(P, m, m),
        c=_zeros(P, m),
        cov=CovarianceParams(log_lengthscale=_zeros(P, d), log_variance=_zeros(P)),
        log_beta=_zeros(P),
    )
    return params, cache


def peek_fit_config(path: str, *, step: int | None = None) -> FitConfig:
    """Read an artifact's FitConfig WITHOUT touching the jax backend.

    The sharded serving path must force virtual host devices before the
    jax backend initializes, and it needs the artifact's grid side to know
    how many — this is the pure-JSON peek that makes
    ``Server.from_artifact`` / ``serve --gp-artifact`` possible.

    ``path`` may be a format=1 artifact directory or a format=2 store
    (``checkpoint.store``); for a store, ``step`` picks a committed
    simulation step (latest when None).
    """
    if artifact_store.is_store(path):
        path = artifact_store.step_dir(path, step)
    elif step is not None:
        raise ValueError(
            f"{path!r} is a single format-1 artifact, not a format-2 store "
            "— it has no step index to select from"
        )
    with open(os.path.join(path, ARTIFACT_MANIFEST)) as f:
        manifest = json.load(f)
    return FitConfig.from_dict(manifest["fit_config"])


def peek_steps(path: str) -> list[int]:
    """The committed step ids of a format=2 store, in ascending order —
    pure JSON, readable before the jax backend initializes (the ops
    dashboard's "what steps do we have" query)."""
    return artifact_store.store_steps(path)


class FittedPSVGP:
    """A trained partitioned surface: config + grid + params + cached factors.

    Construct via :func:`fit` or :meth:`load`; hand to ``api.Server`` to
    serve. Attributes:

      config: the :class:`FitConfig` that produced (or describes) it.
      grid:   the ``PartitionGrid`` the state was trained on.
      static / state: the ``repro.core.psvgp`` bundle (training-time
        ``static.dist``/``perms``/``p_dir`` are None on loaded artifacts).
      cache:  the P-stacked ``PosteriorCache`` — factorized lazily once
        (O(P m^3)) and reused by every prediction and by ``save``.
    """

    def __init__(
        self,
        config: FitConfig,
        grid: PartitionGrid,
        static: psvgp.PSVGPStatic,
        state: psvgp.PSVGPState,
        cache: posterior.PosteriorCache | None = None,
    ):
        self.config = config
        self.grid = grid
        self.static = static
        self.state = state
        self._cache = cache
        # lifecycle observability: wall-clock of the training (or warm
        # refit) that produced this state — re-partitioning, topology
        # build and SGD steps — and its split by phase ({partition_s,
        # build_s, steps_s}); None on loaded artifacts.
        # Server.lifecycle() surfaces the refit's per served version.
        self.train_seconds: float | None = None
        self.train_phases: dict | None = None
        self.refit_seconds: float | None = None
        self.refit_phases: dict | None = None
        # sharded-serving context (mesh, sharded cache, blend programs),
        # built and memoized by api.Server — kept here so several Server
        # views of one model (serial + pipelined lanes of a benchmark, say)
        # share one device placement and one compile per kernel backend.
        self._sharded_ctx: dict = {}

    @property
    def cache(self) -> posterior.PosteriorCache:
        if self._cache is None:
            with span("fit.cache"):
                self._cache = psvgp.posterior_cache(self.static, self.state)
                jax.block_until_ready(self._cache)
        return self._cache

    def predict(self, points) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Replicated blended prediction at (N, 2) points -> (mean, var),
        served from the cached factors (``blend.predict_blended``)."""
        return predict_blended(
            self.static, self.state, self.grid, points, cache=self.cache
        )

    def save(self, path: str) -> str:
        """Persist the serving artifact to ``path`` (a directory).

        Writes ``artifact.json`` (FitConfig + grid geometry) and the
        checkpointed {params, cache} pytrees. Returns ``path``.
        """
        os.makedirs(path, exist_ok=True)
        manifest = {
            "format": ARTIFACT_FORMAT,
            "fit_config": self.config.to_dict(),
            "grid": {
                "gx": int(self.grid.gx),
                "gy": int(self.grid.gy),
                "wrap_x": bool(self.grid.wrap_x),
                "x_edges": np.asarray(self.grid.x_edges, np.float64).tolist(),
                "y_edges": np.asarray(self.grid.y_edges, np.float64).tolist(),
            },
        }
        with open(os.path.join(path, ARTIFACT_MANIFEST), "w") as f:
            json.dump(manifest, f, indent=2)
        save_pytree(path, {"params": self.state.params, "cache": self.cache})
        return path

    def save_step(self, store_path: str, step: int, *, meta: dict | None = None) -> str:
        """Commit this model as simulation step ``step`` of a format=2
        append-only store (``repro.checkpoint.store``).

        Writes a FULL format=1 artifact into ``store_path/step_NNNNNNNN/``
        (``artifact.json`` + the {params, cache} pytrees — same layout as
        :meth:`save`), then atomically appends the step to ``store.json``
        — the index rewrite is the commit point, so a crash mid-save
        leaves only an unindexed orphan directory, never a half-indexed
        step. ``meta`` (plain-JSON: refit wall-clock, fit metrics, ...)
        rides along in the step's index entry. Steps are append-only and
        strictly increasing. Returns the step directory.
        """
        dirname = artifact_store.step_dir_name(step)
        full = os.path.join(store_path, dirname)
        committed = (
            artifact_store.store_steps(store_path)
            if artifact_store.is_store(store_path)
            else []
        )
        if int(step) in committed or (committed and int(step) <= max(committed)):
            # fail BEFORE overwriting the step directory the index points at
            raise ValueError(
                f"step {step} cannot be committed to the store at "
                f"{store_path!r} (committed steps: {committed}) — the store "
                "is append-only, strictly increasing"
            )
        self.save(full)
        if meta is None and self.refit_seconds is not None:
            meta = {"refit_s": self.refit_seconds}
        artifact_store.commit_step(store_path, step, dirname, meta)
        return full

    @classmethod
    def load(cls, path: str, *, step: int | None = None) -> "FittedPSVGP":
        """Restore a serving artifact — no retraining, no refactorization;
        the cached factors come back bitwise and the first prediction is
        O(Q m^2) like any other.

        ``path`` is either a format=1 directory written by :meth:`save`
        or a format=2 store written by :meth:`save_step`; for a store,
        ``step`` selects a committed simulation step (latest when None).
        """
        if artifact_store.is_store(path):
            path = artifact_store.step_dir(path, step)
        elif step is not None:
            raise ValueError(
                f"{path!r} is a single format-1 artifact, not a format-2 "
                "store — it has no step index to select from"
            )
        with open(os.path.join(path, ARTIFACT_MANIFEST)) as f:
            manifest = json.load(f)
        if manifest.get("format") != ARTIFACT_FORMAT:
            raise ValueError(
                f"artifact at {path!r} has format {manifest.get('format')!r}; "
                f"this build reads format {ARTIFACT_FORMAT}"
            )
        config = FitConfig.from_dict(manifest["fit_config"])
        g = manifest["grid"]
        grid = PartitionGrid(
            gx=int(g["gx"]),
            gy=int(g["gy"]),
            x_edges=np.asarray(g["x_edges"], np.float64),
            y_edges=np.asarray(g["y_edges"], np.float64),
            wrap_x=bool(g["wrap_x"]),
        )
        if grid.gx != config.grid or grid.gy != config.grid:
            raise ValueError(
                f"artifact grid {grid.gx}x{grid.gy} disagrees with its "
                f"FitConfig grid={config.grid} — corrupt manifest"
            )
        params_t, cache_t = _artifact_templates(config)
        tree = load_pytree(path, {"params": params_t, "cache": cache_t})
        pcfg = _psvgp_config(config)
        static = psvgp.PSVGPStatic(
            cfg=pcfg,
            cov_fn=make_covariance(config.covariance),
            dist=None,  # training-time tables are not part of the artifact
            perms=None,
            p_dir=None,
        )
        state = psvgp.PSVGPState(
            params=tree["params"],
            opt=AdamState(step=jnp.zeros((), jnp.int32), mu=None, nu=None),
            step=jnp.zeros((), jnp.int32),
        )
        return cls(config, grid, static, state, cache=tree["cache"])


def _extract_xy(data: Any) -> tuple[np.ndarray, Any]:
    """The one data-adapter ``fit`` and ``refit`` share: an object with
    ``.x``/``.y`` attributes or an ``(x, y)`` tuple -> validated arrays."""
    if hasattr(data, "x") and hasattr(data, "y"):
        x, y = data.x, data.y
    else:
        x, y = data
    x = np.asarray(x, np.float32)
    if x.ndim != 2 or x.shape[1] != INPUT_DIM:
        raise ValueError(f"data x must be (N, {INPUT_DIM}), got {x.shape}")
    return x, y


def _train(
    config: FitConfig, x: np.ndarray, y: Any, init_state: psvgp.PSVGPState | None
) -> FittedPSVGP:
    """The shared training recipe behind ``fit`` and ``refit``: grid from
    the data's bounding box, padded partition storage, ``psvgp.build``,
    then ``psvgp.fit`` for ``config.train_iters`` from either a fresh
    ``psvgp.init(PRNGKey(config.seed))`` state (``init_state=None`` — the
    ``fit()`` path) or the given warm state. One code path means the
    refit-from-scratch gate (refit == fit, bitwise) holds by construction.

    ``train_seconds`` is the wall-clock of all three phases, and
    ``train_phases`` its split, at the boundaries of the ``fit.partition``,
    ``fit.build`` and ``fit.steps`` spans.
    """
    t0 = time.perf_counter()
    with span("fit.partition"):
        grid = make_grid(x, config.grid, config.grid)
        pdata = partition_data(x, y, grid)
    t1 = time.perf_counter()
    pcfg = _psvgp_config(config)
    with span("fit.build"):
        static = psvgp.build(pcfg, pdata)
        if init_state is None:
            init_state = psvgp.init(jax.random.PRNGKey(config.seed), pcfg, pdata)
    t2 = time.perf_counter()
    with span("fit.steps", steps=config.train_iters):
        state = psvgp.fit(static, init_state, pdata, config.train_iters)
        jax.block_until_ready(state.params)
    t3 = time.perf_counter()
    fitted = FittedPSVGP(config, grid, static, state)
    fitted.train_seconds = t3 - t0
    fitted.train_phases = {"partition_s": t1 - t0, "build_s": t2 - t1, "steps_s": t3 - t2}
    return fitted


def fit(config: FitConfig, data: Any, *, verbose: bool = False) -> FittedPSVGP:
    """Train a partitioned surface: ``FitConfig`` + data -> :class:`FittedPSVGP`.

    Args:
      config: the training recipe (grid side, m, delta, SGD budget, ...).
      data: either an object with ``.x`` (N, 2) and ``.y`` (N,) attributes
        (e.g. ``repro.data.spatial.SpatialDataset``) or an ``(x, y)`` tuple
        of array-likes.
      verbose: print the one-line training summary the serving drivers show.

    The recipe is exactly the pre-api driver path (grid from the data's
    bounding box, padded partition storage, ``psvgp.build``/``init``/
    ``fit`` with ``PRNGKey(config.seed)``) — a fixed seed reproduces the
    same trained state bitwise.
    """
    x, y = _extract_xy(data)
    fitted = _train(config, x, y, None)
    if verbose:
        print(
            f"trained P={fitted.grid.num_partitions} partitions, m={config.m}, "
            f"{config.train_iters} iters in {fitted.train_seconds:.1f} s"
        )
    return fitted


def refit(
    fitted: FittedPSVGP,
    data: Any,
    config: RefitConfig | None = None,
    *,
    verbose: bool = False,
) -> FittedPSVGP:
    """One in-situ step: update ``fitted`` against a NEW time slice.

    Args:
      fitted: the previous step's model (from :func:`fit`, a previous
        ``refit``, or ``FittedPSVGP.load``).
      data: the new slice — same shapes as :func:`fit` accepts: ``.x``
        (N, 2) / ``.y`` (N,), or an ``(x, y)`` tuple.
      config: the :class:`~repro.api.config.RefitConfig` step recipe
        (default ``RefitConfig()``: warm start, 50 iterations).

    Returns a NEW :class:`FittedPSVGP` (the input is never mutated — the
    old model keeps serving while this one trains; hand the result to
    ``Server.swap`` to go live). The new model reuses ``fitted.config``
    with ``train_iters`` (and optionally ``learning_rate``) replaced by
    the refit budget; the partition grid and topology tables are rebuilt
    from the new slice's bounding box.

    Semantics by ``config.init``:
      * ``"warm"`` — previous params AND Adam moments carry over (the
        moments are re-zeroed when ``reset_optimizer`` is set, or when
        the artifact was loaded from disk and has none); the SGD key
        sequence continues from the carried step counter, so a refit
        never replays step 0's mini-batches.
      * ``"scratch"`` — re-initialize from ``PRNGKey(seed)`` and run the
        SAME code path as :func:`fit`; with the full FitConfig budget
        this is bitwise-identical to ``fit()`` on the new slice (gated
        in tests/test_lifecycle.py).

    ``result.refit_seconds`` records the wall-clock of the step:
    re-partitioning, topology build and SGD steps, not the posterior
    cache (the lifecycle SLO input; ``save_step`` persists it into the
    store index). ``result.refit_phases`` splits it by phase.
    """
    cfg = RefitConfig() if config is None else config
    fit_cfg = fitted.config
    if cfg.learning_rate is not None:
        fit_cfg = dataclasses.replace(fit_cfg, learning_rate=cfg.learning_rate)
    fit_cfg = dataclasses.replace(fit_cfg, train_iters=int(cfg.train_iters))
    x, y = _extract_xy(data)
    if cfg.init == "scratch":
        warm = None
    else:
        warm = fitted.state
        if cfg.reset_optimizer or warm.opt.mu is None:
            # loaded artifacts persist params only — no Adam moments
            warm = psvgp.PSVGPState(
                params=warm.params, opt=adam_init(warm.params), step=warm.step
            )
    new = _train(fit_cfg, x, y, warm)
    new.refit_seconds = new.train_seconds
    new.refit_phases = new.train_phases
    if verbose:
        print(
            f"refit ({cfg.init}) P={new.grid.num_partitions} partitions, "
            f"{fit_cfg.train_iters} iters in {new.refit_seconds:.1f} s"
        )
    return new
