"""The rehearsal runs the drivers on the CPU backend at a tiny size: EC
offload forced on, Pallas kernels interpreted.  Set before JAX is imported."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["CEPH_TPU_EC_OFFLOAD"] = "1"
os.environ["CEPH_TPU_PALLAS_INTERPRET"] = "1"
