"""What every cell shares: the world it starts from, the loader of the
runner its traffic mix asks for, and the serving cells' check.

A mix's ``kind`` names a file ``kinds/<kind>.py`` under the bench
directory, with two functions: ``run(run)`` does the set-up (warming
every shape its window will use), measures, and fills ``run.numbers``
with what the check compares; ``control(run)`` gives the same numbers
with the plain reference at its lower precision in the program's place.
A new kind of traffic adds a file there. Host spans named ``bench.*``
mark what the benchmark was doing, for the trace reduction.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

from configs import psvgp_reference as ref
from harness import check, model
from harness.params import seed_params
from traffic import generator
from traffic.field import Field

WARM_SEED = 12345  # the warm-up replay: the same for every run of a cell


def load_kind(bench_dir: str, kind: str):
    """The runner module ``kinds/<kind>.py``."""
    path = os.path.join(bench_dir, "kinds", f"{kind}.py")
    if not os.path.exists(path):
        raise ValueError(f"no runner for the traffic kind {kind!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_kind_{kind}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def fit_seed(seed: int) -> int:
    """The program's FitConfig.seed (PRNGKey wants 32 bits) from --seed."""
    return int(np.random.SeedSequence([seed, 2]).generate_state(1)[0] & 0x7FFFFFFF)


class World:
    """What every cell starts from: the data field, the reference grid,
    the seed-made parameters and the program's model holding them."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        self.cfg = cfg
        self.seed = seed
        self.field = Field(int(cfg["n_obs"]), seed)
        self.x = self.field.x
        gx, gy = (int(g) for g in cfg["grid"])
        self.grid = ref.grid_of(self.x, gx, gy)
        y0 = self.field.slice(0)
        self.params = seed_params(cfg, self.grid, self.x, y0, seed)
        self.fit_seed = fit_seed(seed)
        # a refit mix starts from a model mid-way through a fit: Adam's
        # count at ``step`` and its moments over the mini-batches before
        adam = mix.get("adam_state")
        self.adam = None
        if adam is not None:
            step = int(adam["step"])
            mu, nu = ref.moments(self.params, self.x, y0, self.grid, seed=self.fit_seed,
                                 first_step=step, window=int(adam["window"]),
                                 delta=float(cfg["delta"]), batch=int(cfg["batch_size"]),
                                 jitter=float(cfg["jitter"]))
            self.adam = (step, mu, nu)
        self.fitted = model.fitted(cfg, self.x, self.params, self.fit_seed, self.adam)

    @property
    def bounds(self):
        g = self.grid
        return (g.x_edges[0], g.y_edges[0]), (g.x_edges[-1], g.y_edges[-1])


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


# -- serving -----------------------------------------------------------------


def frontdoor_config(mix: dict):
    from repro import api

    return api.FrontDoorConfig(**mix.get("frontdoor", {}))


def warm_shapes(server, bounds, sizes) -> None:
    """One blocking request of each coalesced batch size the window can
    make: the replicated path compiles per batch shape."""
    pts = generator.law("locations", "uniform")({}, bounds, max(sizes),
                                                np.random.default_rng(WARM_SEED))
    for n in sizes:
        server.submit(pts[:n])


def keep_sample(run, sample) -> None:
    """Keep the sampled requests' points and served answers for the check."""
    if sample:
        run.evidence["points"] = np.concatenate([p for p, _ in sample])
        run.evidence["got"] = tuple(np.concatenate([np.asarray(a[k]) for _, a in sample])
                                    for k in (0, 1))
    run.note("sample", requests=len(sample))


def serve_reference(run, mode: str = "highest"):
    """The reference's answers at the sampled points."""
    w = run.world
    return ref.blend(w.params, w.grid, run.evidence["points"], mode=mode,
                     jitter=float(run.cfg["jitter"]))


def serve_check(run) -> None:
    """After the window: the reference's answers and the gaps."""
    if "points" in run.evidence:
        run.evidence["want"] = serve_reference(run)
        run.numbers.update(check.answer_gaps(*run.evidence["got"], *run.evidence["want"]))
    else:
        run.numbers.update(mean_rms=float("inf"), var_rms=float("inf"))


def serve_control(run) -> dict:
    """The reference at ``high`` in the program's place."""
    return check.answer_gaps(*serve_reference(run, mode="high"), *run.evidence["want"])
