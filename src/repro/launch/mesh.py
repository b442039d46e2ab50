"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

`make_production_mesh` is a FUNCTION so importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes) -> Mesh:
    """jax.make_mesh with Auto axes: the sharding rules of this package
    are written for Auto (propagated) sharding, while jax.make_mesh
    defaults to Explicit axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_clients: int = 4, model: int = 2):
    """Small mesh over forced host devices for tests / examples."""
    return make_mesh((n_clients, model), ("data", "model"))


def client_layout(mesh, strategy: str = "auto", arch_id: str = ""):
    """-> (client_axes, tp_axes, n_clients).

    'data_clients': clients along data (and pod, if present) — the default:
        single-pod 16 clients, multi-pod 32 clients, TP=model(16).
    'pod_clients': clients along pod only; TP spans (data, model)=256 —
        required for deepseek-v2-236b whose per-client shards do not fit one
        16-chip row (see EXPERIMENTS.md §Dry-run).
    """
    axes = mesh.axis_names
    multi_pod = "pod" in axes
    if strategy == "auto":
        strategy = ("pod_clients" if multi_pod
                    and arch_id == "deepseek-v2-236b" else "data_clients")
    if strategy == "pod_clients":
        if not multi_pod:
            raise ValueError("pod_clients needs the multi-pod mesh")
        return ("pod",), ("data", "model"), mesh.shape["pod"]
    client_axes = ("pod", "data") if multi_pod else ("data",)
    n_clients = 1
    for a in client_axes:
        n_clients *= mesh.shape[a]
    return client_axes, ("model",), n_clients
