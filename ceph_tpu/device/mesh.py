"""Local device-mesh enumeration for the mesh-aware DeviceRuntime.

The multi-chip dispatch discipline follows "Large Scale Distributed
Linear Algebra With Tensor Processing Units" (arXiv:2112.09017,
PAPERS.md): the host enumerates its local chips once, work is placed
per chip with plain `jax.device_put` (computation follows data), and
nothing in the hot path performs a cross-chip collective —
MULTICHIP_SCALING.json proves EC encode stays collective-free over the
stripe axis for every dp=1..8 program, which is exactly what makes
per-chip isolation sound: a chip's failure cannot wedge another chip's
in-flight program.

Chip count resolution, in priority order:

1. ``CEPH_TPU_MESH_CHIPS`` — explicit logical mesh size.  Logical
   chips beyond the physical device count map onto physical devices
   round-robin; this is how tier-1 CI exercises a 4-chip mesh on the
   single CPU "device" without restarting the process.
2. ``len(jax.local_devices())`` — the real mesh (a v5e host sees its
   local chips; CPU CI sees the forced count when launched under
   ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
3. 1 — jax not installed (host-only builds).
"""

from __future__ import annotations

import os

MESH_ENV = "CEPH_TPU_MESH_CHIPS"
FORCE_HOST_FLAG = "--xla_force_host_platform_device_count"


def local_devices() -> list:
    """The process's jax devices ([] when jax is not installed).
    Imported lazily: mesh construction must not force jax init on
    host-only paths that never dispatch.  A jax that is installed but
    cannot initialise its backend raises: on a machine with a chip
    that is a fault, not a host-only build."""
    try:
        import jax
    except ImportError:     # pragma: no cover - jax baked into image
        return []
    return list(jax.local_devices())


def chip_count() -> int:
    """Logical mesh size for this process (see module docstring)."""
    env = os.environ.get(MESH_ENV)
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    devs = local_devices()
    return max(1, len(devs))


def device_for(chip_index: int):
    """The jax device backing logical chip `chip_index` (round-robin
    when logical chips outnumber physical devices), or None when jax
    has no devices to offer."""
    devs = local_devices()
    if not devs:
        return None
    return devs[chip_index % len(devs)]


def backend() -> str:
    """The jax backend serving this mesh ("cpu" when jax is not
    installed).  The dispatch-stream bench gate keys its published
    comparisons on this: CPU-CI figures never gate a real-TPU run and
    vice versa."""
    try:
        import jax
    except ImportError:     # pragma: no cover - jax baked into image
        return "cpu"
    return str(jax.default_backend())


def affinity(osd_id: int, n_chips: int) -> int:
    """OSD -> chip affinity: deterministic modulo placement, so
    co-located daemons land on distinct chips until the mesh is full
    and a chip loss maps to a knowable OSD subset."""
    return int(osd_id) % max(1, int(n_chips))


def describe() -> dict:
    """Mesh identity for trace/export metadata: how many chips this
    process sees, what backs them, and whether the count was forced
    (so an exported timeline records what hardware its device lanes
    actually ran on)."""
    devs = local_devices()
    out = {"chips": chip_count(),
           "physical_devices": len(devs),
           "forced": bool(os.environ.get(MESH_ENV))}
    if devs:
        out["platform"] = getattr(devs[0], "platform", "unknown")
    return out


def simulated_mesh_env(n: int, base: dict | None = None) -> dict:
    """Environment for a subprocess that should see `n` real host
    devices (the CI simulation recipe: XLA must be told before jax
    initializes, hence a fresh process)."""
    env = dict(base if base is not None else os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith(FORCE_HOST_FLAG)]
    flags.append("%s=%d" % (FORCE_HOST_FLAG, int(n)))
    env["XLA_FLAGS"] = " ".join(flags)
    env.setdefault("JAX_PLATFORMS", "cpu")
    return env
