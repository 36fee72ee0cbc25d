"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required because only launch/dryrun.py runs with
the 512-device host-platform flag.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.configs.base import MeshConfig


def make_production_mesh(*, multi_pod: bool = False):
    """The assigned production meshes: 16x16 single-pod, 2x16x16 multi-pod."""
    return make_mesh(MeshConfig(pods=2 if multi_pod else 1))


def make_mesh(cfg: MeshConfig):
    """Arbitrary (pods, data, model) mesh — the elastic-scaling entry point
    and the one mesh constructor of the repo.

    Axes are ``AxisType.Auto``: GSPMD propagates shardings from the
    ``NamedSharding`` placements and ``constrain`` annotations.  (JAX's
    default, explicit-sharding axes, would need an ``out_sharding`` on ops
    such as the vocab-sharded embedding gather.)
    """
    shape, axes = (cfg.data, cfg.model), ("data", "model")
    if cfg.pods > 1:
        shape, axes = (cfg.pods,) + shape, ("pod",) + axes
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def single_device_mesh():
    """Trivial mesh for tests/examples on one device."""
    return make_mesh(MeshConfig(data=1, model=1))


def parse_mesh_arg(arg: str) -> MeshConfig:
    """Parse a ``--mesh`` CLI value like ``"data=2,model=4"`` into a
    :class:`MeshConfig` (axes default to 1; ``pod=``/``pods=`` accepted)."""
    sizes = {"pods": 1, "data": 1, "model": 1}
    alias = {"pod": "pods", "pods": "pods", "data": "data", "model": "model"}
    for part in arg.split(","):
        if not part.strip():
            continue
        name, sep, value = part.partition("=")
        key = alias.get(name.strip())
        if key is None or not sep or not value.strip().isdigit() \
                or int(value) < 1:
            raise ValueError(
                f"bad --mesh entry {part!r}: expected axis=size with axis "
                f"in {sorted(set(alias))} and size a positive integer "
                f"(e.g. \"data=2,model=4\")")
        sizes[key] = int(value)
    return MeshConfig(**sizes)


def force_host_device_count(n: int) -> None:
    """Pin ``--xla_force_host_platform_device_count=n`` in ``XLA_FLAGS``,
    replacing any existing occurrence (appending a second copy leaves the
    effective count to XLA's duplicate-flag handling).

    Must run before the jax *backend* initializes — importing jax is fine,
    touching devices is not.  Shared by ``launch/serve.py --fake-devices``,
    the bench_fps sharded child and tests/_sharded_child.py.
    """
    import os
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={int(n)}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
