"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — required because the dry-run
launcher must set XLA_FLAGS before any jax initialization.

Production target: TPU v5e pods, 256 chips/pod.
  single-pod:  (16, 16)    axes ('data', 'model')
  multi-pod:   (2, 16, 16) axes ('pod', 'data', 'model')
'pod' is pure data parallelism across pods (params replicated, gradient
all-reduce crosses the DCN/ICI pod boundary); 'data' is FSDP/ZeRO-3;
'model' is tensor/expert parallelism.
"""
from __future__ import annotations

import jax
import numpy as np


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """jax.make_mesh with Auto axes: the sharding hints
    (`with_sharding_constraint`) and gathers the models use are written
    for GSPMD's automatic propagation, not for Explicit sharding types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return _auto_mesh((n // model, model), ("data", "model"))


def parse_mesh(spec: str) -> tuple[int, int]:
    """Parse a `--mesh` flag value into (data, model) axis sizes.

    Accepts bare sizes ('2,1', '4') or named ('data=2,model=1' in either
    order); a single number is the data axis with model=1.
    """
    parts = [p.strip() for p in spec.split(",") if p.strip()]
    assert parts, f"empty mesh spec: {spec!r}"
    if any("=" in p for p in parts):
        kv = dict(p.split("=", 1) for p in parts)
        unknown = set(kv) - {"data", "model"}
        assert not unknown, f"unknown mesh axes {sorted(unknown)} in {spec!r}"
        return int(kv.get("data", 1)), int(kv.get("model", 1))
    assert len(parts) <= 2, f"mesh spec has >2 axes: {spec!r}"
    data = int(parts[0])
    model = int(parts[1]) if len(parts) == 2 else 1
    return data, model


def make_serving_mesh(data: int = 1, model: int = 1):
    """('data', 'model') mesh over the first data*model devices — unlike
    `make_host_mesh` it does not have to cover every device, so a serving
    job can pin a sub-mesh (and leave the rest to replicas)."""
    assert data >= 1 and model >= 1, (data, model)
    devs = jax.devices()
    need = data * model
    assert need <= len(devs), \
        f"mesh {data}x{model} needs {need} devices, have {len(devs)} " \
        f"(simulate with XLA_FLAGS=--xla_force_host_platform_device_count=N)"
    return jax.sharding.Mesh(
        np.asarray(devs[:need]).reshape(data, model), ("data", "model"))


def batch_axes(mesh) -> tuple[str, ...]:
    """Mesh axes the global batch is sharded over."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


# v5e hardware constants used by the roofline analysis (per chip)
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link
