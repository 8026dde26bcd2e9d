"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device,
and only launch/dryrun.py sets XLA_FLAGS for 512 placeholder devices.

Mesh geometry (TPU v5e pods):
  single-pod   (16, 16)        axes ("data", "model")  — 256 chips
  multi-pod    (2, 16, 16)     axes ("pod", "data", "model") — 512 chips

The "model" axis carries TP / EP / sequence(cache) sharding; "data" carries
batch + FSDP parameter sharding; "pod" is pure data parallelism whose
gradient all-reduce crosses the inter-pod links (the axis gradient
compression targets — see optim/grad_utils.py).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto(axes: Tuple[str, ...]) -> Tuple[AxisType, ...]:
    """Auto axes: the sharding rules (``distributed/policy.constrain``
    under ``with mesh:``) leave propagation to the compiler, which
    ``jax.make_mesh``'s default Explicit axes refuse."""
    return (AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(shape, axes, axis_types=_auto(axes))
    # single-pod mesh on a 512-device host platform: take the first pod
    assert len(devices) >= n, (
        f"need {n} devices, have {len(devices)} — dryrun.py must set "
        "XLA_FLAGS=--xla_force_host_platform_device_count=512 first")
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes,
                axis_types=_auto(axes))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh over however many devices this process sees (tests)."""
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_host_mesh(data: Optional[int] = None, model: Optional[int] = None):
    """Small mesh over the host's actual devices — used by sharded CPU tests
    (run under XLA_FLAGS=--xla_force_host_platform_device_count=8)."""
    n = len(jax.devices())
    if data is None and model is None:
        model = 1
        data = n
    data = data or n // (model or 1)
    model = model or n // data
    assert data * model == n, (data, model, n)
    axes = ("data", "model")
    return jax.make_mesh((data, model), axes, axis_types=_auto(axes))


def phase_device_groups(devices: Optional[List] = None
                        ) -> Tuple[List, List]:
    """Split the visible devices into (prefill_group, decode_group) for
    disaggregated serving (serving/executor.DisaggregatedExecutor).

    HALO dedicates DIFFERENT hardware to each phase (CiM prefill, CiD
    decode); here the analogue is disjoint halves of the device list —
    prefill takes the first half, decode the second.  A single-device
    host cannot split, so both groups share that one device: program
    pinning becomes a no-op while the handoff/migration accounting (the
    2.5D-link analogue) still runs for real, which is what keeps greedy
    streams bit-identical colocated vs disaggregated in tests."""
    devs = list(devices) if devices is not None else list(jax.devices())
    if len(devs) < 2:
        return devs, devs
    half = len(devs) // 2
    return devs[:half], devs[half:]


def mesh_axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Batch-sharding axes: ("pod","data") when the pod axis exists."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
