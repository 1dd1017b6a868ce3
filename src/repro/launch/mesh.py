"""Production mesh definitions.

Axis conventions (DESIGN.md §7):

* ``pod``   — outer data-parallel over DCN (2 pods in the assigned target);
  also the commit axis for delayed gradient commit, and re-bindable to
  pipeline stages (knob left for >2-pod deployments).
* ``data``  — within-pod data parallel + FSDP (ZeRO-3 parameter sharding).
* ``model`` — tensor/expert parallel.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""

from __future__ import annotations

import jax

from repro.dist.compat import make_mesh

__all__ = ["make_production_mesh", "make_host_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, n // data)
    return make_mesh((data, model), ("data", "model"))
