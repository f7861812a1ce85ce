"""Device mesh + sharding: the TPU-native replacement for mshadow-ps.

Reference: the multi-device path in ``src/nnet/nnet_impl-inl.hpp`` splits the
batch across per-device threads and aggregates gradients via the
``"local"``/``"dist"`` parameter server (InitParamServer :376-390,
``async_updater-inl.hpp``).  Here the same data parallelism is one SPMD
program over a ``jax.sharding.Mesh``: the batch is sharded on the ``data``
axis, parameters are replicated (or sharded on ``model`` for the
fullc_gather-style tensor-parallel mode), and XLA inserts the psum over ICI —
no keys, no async callbacks, no server.  Multi-host runs the same program on
a global mesh (DCN between hosts), which is the ``param_server = dist``
equivalent.

The axes are named, not hard-coded to "batch", so sequence/context/expert
axes can attach later (survey §5.7 note).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def parse_device_spec(dev: str) -> Dict:
    """Parse ``dev = cpu | tpu | tpu:0 | tpu:0-3 | gpu:1,3`` (reference
    nnet_impl-inl.hpp:32-51 parses the gpu:0-3 form)."""
    dev = dev.strip()
    if ":" not in dev:
        return {"platform": dev, "ids": None}
    platform, rng = dev.split(":", 1)
    ids: List[int] = []
    for part in rng.split(","):
        if "-" in part:
            a, b = part.split("-")
            ids.extend(range(int(a), int(b) + 1))
        else:
            ids.append(int(part))
    return {"platform": platform, "ids": ids}


def ensure_host_platform_devices(n: int) -> None:
    """Best-effort: ask XLA's host platform for ``n`` CPU devices (a
    ``dev = cpu:0-3`` + ``mesh = data:2,model:2`` config needs them).
    Only effective BEFORE the first backend initialization — call it
    before anything touches ``jax.devices()``/``jax.process_count()``;
    afterwards it is a harmless no-op and callers must check the visible
    count themselves."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def platform_devices(platform: str) -> List[jax.Device]:
    """Every device of ``platform`` (global after ``init_distributed``).
    A platform that was asked for and is absent is an error naming it and
    listing what is visible: ``dev = tpu`` must never train on the CPU
    unnoticed, and ``dev = cpu`` is the one way to get the CPU."""
    try:
        return list(jax.devices(platform))
    except RuntimeError as e:
        visible = sorted({d.platform for d in jax.devices()})
        raise RuntimeError(
            f"dev = {platform}: no {platform} device is visible to JAX "
            f"(visible platforms: {', '.join(visible)}; JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}); set dev = cpu to "
            "run on the CPU") from e


def select_devices(dev: str) -> List[jax.Device]:
    """Devices for a ``dev =`` spec.  Multi-host (after
    ``init_distributed``) the mesh must span the global device set, so the
    whole platform is returned; local id selection (``dev = tpu:0-3``)
    only makes sense single-host."""
    spec = parse_device_spec(dev)
    platform = spec["platform"]
    if platform == "cpu":
        # dev = cpu on a machine with an accelerator: restrict JAX to the
        # CPU backend before anything below initializes the backends, so
        # a CPU run never takes (or waits for) the chip
        jax.config.update("jax_platforms", "cpu")
    devices = platform_devices(platform)
    if jax.process_count() > 1:
        return devices
    if spec["ids"] is None:
        return devices[:1]
    for i in spec["ids"]:
        if i >= len(devices):
            raise ValueError(
                f"device id {i} out of range: only {len(devices)} "
                f"{platform} devices visible")
    return [devices[i] for i in spec["ids"]]


#: mesh axis names the framework gives semantics to: ``data`` shards the
#: batch, ``model`` shards fullc/moe weights (tensor/weight parallelism),
#: ``seq`` ring attention, ``expert`` MoE dispatch, ``pipe`` pipeline
#: stages.  ``mesh=`` is a first-class config key; an unknown axis name
#: would silently shard nothing, so parse rejects it with a suggestion.
KNOWN_AXES = ("data", "model", "seq", "expert", "pipe")


@dataclasses.dataclass
class MeshSpec:
    """Named mesh axes, e.g. {"data": 4, "model": 2}."""

    axes: Dict[str, int]

    @classmethod
    def parse(cls, s: str) -> "MeshSpec":
        """Parse ``mesh = data:4,model:2`` config syntax.  Raises
        ``ValueError`` on unknown/duplicate axis names or non-positive
        sizes (surfaced as a config-lint error by graftlint and as an
        init-time error by the trainer)."""
        axes: Dict[str, int] = {}
        for part in s.split(","):
            name, sep, size = part.partition(":")
            name = name.strip()
            if not sep:
                raise ValueError(
                    f"mesh axis {part.strip()!r}: expected name:size")
            if name not in KNOWN_AXES:
                from ..analysis.schema import did_you_mean
                sugg = did_you_mean(name, KNOWN_AXES)
                raise ValueError(
                    f"unknown mesh axis {name!r} (axes with semantics: "
                    f"{', '.join(KNOWN_AXES)})"
                    + (f"; did you mean {sugg!r}?" if sugg else ""))
            if name in axes:
                raise ValueError(f"duplicate mesh axis {name!r}")
            try:
                n = int(size)
            except ValueError:
                raise ValueError(
                    f"mesh axis {name}: size {size.strip()!r} is not an "
                    "integer") from None
            if n < 1:
                raise ValueError(f"mesh axis {name}: size must be >= 1, "
                                 f"got {n}")
            axes[name] = n
        return cls(axes)

    @property
    def size(self) -> int:
        n = 1
        for v in self.axes.values():
            n *= v
        return n

    def axis_size(self, name: str) -> int:
        """Size of ``name`` (1 when the axis is absent)."""
        return self.axes.get(name, 1)


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    """Axis name -> size for a BUILT mesh — the axis metadata the SPMD
    deep lint (analysis/spmdlint.py) checks traced collective axis names
    against.  One accessor so the checker and the runtime can never
    disagree about which axes exist or how wide they are (a collective
    on an axis missing here is the multi-host deadlock class)."""
    return {str(name): int(size) for name, size in mesh.shape.items()}


def build_mesh(devices: Sequence[jax.Device],
               spec: Optional[MeshSpec] = None) -> Mesh:
    """Build a Mesh; default one-axis "data" mesh over all given devices."""
    if spec is None:
        spec = MeshSpec({"data": len(devices)})
    assert spec.size == len(devices), \
        f"mesh axes {spec.axes} need {spec.size} devices, got {len(devices)}"
    arr = np.array(devices).reshape(tuple(spec.axes.values()))
    return Mesh(arr, tuple(spec.axes.keys()))


def batch_pspec(mesh: Mesh) -> P:
    """Batch dim sharded over "data" (if present), rest replicated."""
    if "data" in mesh.axis_names:
        return P("data")
    return P()


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, batch_pspec(mesh))


def init_distributed(coordinator: str, num_processes: int, process_id: int,
                     local_device_ids=None) -> None:
    """Multi-host bring-up: join the JAX distributed runtime so all
    processes see one global device set and compiled programs run SPMD
    across hosts (collectives ride ICI within a slice, DCN across).

    This replaces the reference's parameter-server topology
    (``param_server = dist`` + launcher, nnet_ps_server.cpp:162-170): there
    is no server process — every host runs the same program on its shard of
    the global mesh.  Config keys (see main.py): ``dist_coordinator``
    (host:port of process 0), ``dist_num_proc``, ``dist_proc_rank``; the
    env vars CXN_COORDINATOR / CXN_NUM_PROC / CXN_PROC_RANK override, so
    one config file serves every worker like the reference's single conf
    (nnet_ps_server.cpp:41-48).
    """
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids)
