"""Production mesh definitions (TPU v5e target).

Single pod:  (data=16, model=16)            = 256 chips
Multi-pod:   (pod=2, data=16, model=16)     = 512 chips

SFPrompt mapping: the client plane is ('pod', 'data') — each index hosts a
cohort of simulated clients, with per-client parameter copies sharded along
it; the server plane is 'model' — the frozen body is tensor-parallel (and
FSDP-sharded over 'data' for storage). Defined as a FUNCTION so importing
this module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.sharding.rules import report_fallbacks

# v5e hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # bytes/s
ICI_BW = 50e9                  # bytes/s per link


def _mesh(shape, axes, devices=None):
    """A mesh whose axes the compiler partitions (GSPMD): params and
    cohorts are placed by explicit in/out shardings, and the computations
    between them need no sharding in their types."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(n: int = 0, *, model: int = 1):
    """Host-device mesh — the off-TPU stand-in for the production mesh.
    model=1 (default): 1-D ('data',) client plane, as before. model>1:
    2-D ('data', 'model') — the client plane shrinks to n // model and the
    'model' axis becomes a real tensor-parallel compute axis (the frozen
    body's params_pspecs 'model' shardings stop being no-ops). With
    XLA_FLAGS=--xla_force_host_platform_device_count=8 (set BEFORE jax
    initializes; see launch/dryrun.py) a CPU host exposes 8 virtual
    devices, so e.g. make_host_mesh(model=4) gives (data=2, model=4).
    n=0 uses every visible device."""
    devices = jax.devices()
    n = len(devices) if n <= 0 else n
    if n > len(devices):
        raise ValueError(
            f"requested a {n}-device host mesh but only {len(devices)} "
            "device(s) are visible — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count before jax "
            "initializes")
    if model <= 1:
        return _mesh((n,), ("data",), devices[:n])
    if n % model != 0:
        raise ValueError(
            f"model={model} does not divide the {n}-device host mesh — "
            "pick a model-axis size that divides the device count")
    return _mesh((n // model, model), ("data", "model"), devices[:n])


def report_sharding_fallbacks(context: str = "", tracer=None) -> tuple:
    """Drain the divisibility fallbacks recorded while building partition
    specs (sharding.rules.guard_divisibility) and warn ONCE if any rule
    quietly fell back to replication — a mis-sized mesh should be visible,
    not silently slow. With a tracer, the entries additionally land as a
    structured `sharding.fallback` event (sharding.rules.report_fallbacks).
    Returns the drained (path, axis, shape) tuples so launchers can also
    log them."""
    return report_fallbacks(context, tracer)


def data_parallel_size(mesh) -> int:
    size = mesh.shape["data"]
    if "pod" in mesh.shape:
        size *= mesh.shape["pod"]
    return size
