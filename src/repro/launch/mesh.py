"""Production mesh + logical-axis sharding resolution.

Meshes (TPU v5e target):
  * single pod:  (data=16, model=16)            -- 256 chips
  * multi pod:   (pod=2, data=16, model=16)     -- 512 chips

Model code never names physical axes; it uses *logical* axes resolved here:

  "fsdp"   -> ('pod','data') | ('data',)   weight/optimizer sharding (ZeRO-3)
  "dp"     -> ('pod','data') | ('data',)   batch dimension
  "tp"     -> 'model'                      heads / d_ff / vocab (Megatron TP)
  "expert" -> 'model'                      MoE expert parallelism (EP co-located
                                           with TP; see models/moe.py)
  None     -> replicated

`make_production_mesh` is a function (not a module constant) so importing this
module never touches jax device state.
"""

from __future__ import annotations

import contextlib
import contextvars
import math
from typing import Any, Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

_MESH: contextvars.ContextVar[Mesh | None] = contextvars.ContextVar(
    "repro_mesh", default=None
)
_MANUAL: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "repro_manual", default=False
)


@contextlib.contextmanager
def manual_mode():
    """Mark a region as running *inside* shard_map (per-device code): sharding
    constraints become no-ops and nested collectives layers use local paths."""
    token = _MANUAL.set(True)
    try:
        yield
    finally:
        _MANUAL.reset(token)


def in_manual_mode() -> bool:
    return _MANUAL.get()


def make_mesh(
    axis_shapes: Sequence[int], axis_names: Sequence[str], *, devices=None
) -> Mesh:
    """THE mesh constructor: every axis is ``AxisType.Auto``.

    ``jax.make_mesh`` defaults to ``AxisType.Explicit`` axes, under which
    sharding-in-types rejects the contractions over sharded dims that the
    ``shard_map`` + ``psum`` placements and GSPMD propagation rely on.  Uses
    the first ``prod(axis_shapes)`` of ``devices`` (default: all local
    devices), so a mesh may cover fewer devices than the process sees.
    """
    shape = tuple(int(s) for s in axis_shapes)
    devices = list(jax.devices() if devices is None else devices)
    n = math.prod(shape)
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} -- set "
            "XLA_FLAGS=--xla_force_host_platform_device_count before importing jax"
        )
    return jax.make_mesh(
        shape,
        tuple(axis_names),
        axis_types=(AxisType.Auto,) * len(shape),
        devices=devices[:n],
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over the locally available devices (tests / examples)."""
    return make_mesh((data, model), ("data", "model"))


def make_node_mesh(
    nodes: int, devices_per_node: int, *, axis_names: tuple[str, str] = ("node", "device")
) -> Mesh:
    """Two-level ``(nodes, devices_per_node)`` mesh for hierarchical collectives.

    Axis ``axis_names[0]`` (default ``"node"``) spans the *nodes* -- the
    slow-DCN level a flat ring would drag full blocks across -- and
    ``axis_names[1]`` (default ``"device"``) spans the devices within one
    node (the fast-ICI level ``repro.dist.collectives.hierarchical_psum``
    reduce-scatters over).  On real multi-host hardware the device order of
    ``jax.devices()`` already groups by process, so consecutive blocks of
    ``devices_per_node`` land on one host; on the CI fake-device backend the
    grouping is synthetic but exercises the identical collective structure.
    Declare the intra level to the planner via
    ``Problem(intra_axes=(axis_names[1],))``.
    """
    return make_mesh((nodes, devices_per_node), axis_names)


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    token = _MESH.set(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        _MESH.reset(token)


def current_mesh() -> Mesh | None:
    return _MESH.get()


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def dp_spec_entry(mesh: Mesh):
    """The data-parallel axes as one PartitionSpec entry: a tuple when the
    batch dim is sharded over several mesh axes, the bare name otherwise."""
    dp = dp_axes(mesh)
    return dp if len(dp) > 1 else dp[0]


def resolve_logical(logical: Sequence[Any] | None, mesh: Mesh) -> P:
    """Map a tuple of logical axis names to a PartitionSpec for ``mesh``."""
    if logical is None:
        return P()
    out: list[Any] = []
    for ax in logical:
        if ax is None:
            out.append(None)
        elif ax in ("fsdp", "dp"):
            dp = dp_axes(mesh)
            out.append(dp if len(dp) > 1 else dp[0])
        elif ax in ("tp", "expert"):
            out.append("model")
        else:
            raise ValueError(f"unknown logical axis {ax!r}")
    return P(*out)


def named_sharding(logical: Sequence[Any] | None, mesh: Mesh | None = None):
    mesh = mesh or current_mesh()
    assert mesh is not None, "no mesh in context"
    return NamedSharding(mesh, resolve_logical(logical, mesh))


def constraint(x: jax.Array, *logical: Any) -> jax.Array:
    """``with_sharding_constraint`` against the ambient mesh; no-op without one.

    Model code calls this at layer boundaries; GSPMD propagates the rest.
    """
    mesh = current_mesh()
    if mesh is None or in_manual_mode():
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, resolve_logical(logical, mesh))
    )


def tp_size(mesh: Mesh | None = None) -> int:
    mesh = mesh or current_mesh()
    if mesh is None:
        return 1
    return mesh.shape.get("model", 1)
