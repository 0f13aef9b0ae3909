"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — jax locks the device count on first use, and
only dryrun.py sets the 512-host-device XLA flag.

Every mesh is built with Auto axes: the model and the executors place
arrays with ``NamedSharding``/``with_sharding_constraint`` and let GSPMD
propagate, which ``jax.make_mesh``'s Explicit default refuses.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType

from repro.config.base import MeshConfig, MULTI_POD, SINGLE_POD


def _auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(cfg: MeshConfig):
    return _auto_mesh(cfg.shape, cfg.axes)


def make_test_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many devices the test process has."""
    return _auto_mesh((data, model), ("data", "model"))


def make_scoring_mesh(num_devices: Optional[int] = None):
    """1-D ("data",) mesh for the streaming data-parallel loops: the
    scoring executor (repro.engine.executor) row-shards document tiles
    over it, and the offline indexer (repro.engine.ingest) row-shards
    embedding token batches over it — so the right shape is simply
    every device the process owns. ``None`` = all local devices; a
    1-device mesh degrades to the single-device path of both."""
    devs = jax.devices()
    n = num_devices or len(devs)
    return _auto_mesh((n,), ("data",), devices=devs[:n])
