"""The one generator that turns a traffic mix file into requests.

A mix (``traffic/<name>.json``) names its laws, and each law is a file
of its own under ``traffic/laws/``, found by the name the mix gives it:

  ``arrivals``   when each request is due (``laws/arrivals.<law>.py``)
  ``sizes``      how many points each request asks for (``laws/sizes.<law>.py``)
  ``locations``  where the points lie (``laws/locations.<law>.py``)

Each law file holds one ``draw`` function. A new mix with new laws adds
files; nothing here changes. The open-loop schedule follows the seeded
Poisson process of the program's ``benchmarks/bench_frontdoor.py``, with
one change that keeps runs comparable: every seed gets the same multiset
of gaps and sizes, in its own order, at its own locations.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
from typing import NamedTuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str, root: str | None = None) -> dict:
    """The mix file ``traffic/<name>.json`` under the bench directory."""
    path = os.path.join(root or HERE, f"{name}.json")
    with open(path) as f:
        return json.load(f)


@functools.cache
def law(category: str, name: str):
    """The ``draw`` function of ``laws/<category>.<name>.py``."""
    path = os.path.join(HERE, "laws", f"{category}.{name}.py")
    if not os.path.exists(path):
        raise ValueError(f"no {category} law {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_law_{category}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.draw


def points(mix: dict, bounds, n: int, rng: np.random.Generator) -> np.ndarray:
    """n query points by the mix's location law."""
    return law("locations", mix.get("locations", "uniform"))(mix, bounds, n, rng)


class OpenLoop(NamedTuple):
    due_s: np.ndarray  # (R,) due time of each request from the window start
    requests: list  # R arrays of (n_i, 2) float32 points


def open_loop(mix: dict, bounds, seconds: float, seed: int) -> OpenLoop:
    """The schedule of a mix for a window of ``seconds``."""
    rng = np.random.default_rng(seed)
    due = law("arrivals", mix["arrivals"])(mix, seconds, rng)
    sizes = law("sizes", mix["sizes"])(mix, len(due), rng)
    pts = points(mix, bounds, int(sizes.sum()), rng)
    return OpenLoop(due, np.split(pts, np.cumsum(sizes)[:-1]))


def closed_pool(mix: dict, bounds, seed: int) -> list:
    """The pool of requests the closed-loop clients cycle through."""
    rng = np.random.default_rng(seed)
    rows = int(mix["request_rows"])
    return [points(mix, bounds, rows, rng) for _ in range(int(mix["pool"]))]
