"""The look for a chip.  A measurement path that finds no TPU fails; it
never falls back to the CPU, and it never runs with a switch set that
turns a kernel or the device path off without a trace."""

import os
import sys

# switches of the program that turn a kernel off, or the device path
KILL_SWITCHES = ("CEPH_TPU_EC_FUSED", "CEPH_TPU_NO_PALLAS_CRUSH",
                 "CEPH_TPU_PALLAS_INTERPRET", "CEPH_TPU_EC_OFFLOAD",
                 "CEPH_TPU_MESH_CHIPS", "CEPH_TPU_NO_NATIVE")


def require_chips(chips: int) -> dict:
    """The device as JAX reports it, or exit non-zero with no result."""
    set_ = [v for v in KILL_SWITCHES if os.environ.get(v) is not None]
    if set_:
        print("benchmark: refusing to run with %s set" % ", ".join(set_),
              file=sys.stderr)
        raise SystemExit(2)
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu" or dev["count"] < chips:
        print("benchmark: the cell needs %d TPU chip(s), JAX reports %s"
              % (chips, dev), file=sys.stderr)
        raise SystemExit(1)
    return dev
