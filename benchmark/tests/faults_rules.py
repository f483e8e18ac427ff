"""Faults planted under the two-step crush cell, and its control: each
must make a run of crush_churn_rules come out `correct: false`, by the
number named.  Used by test_rehearsal_rules.py at a tiny size on the CPU
and by control_rules.py at the cell's own size on the chip.

  stale_mapping           every remap hands out the previous epoch's table
                          (faults.py's control for the crush cells): a
                          stale answer where it was exact; mismatched_pgs.
  second_step_from_root   the rule's second step takes the root, not the
                          rack the first step chose: every chunk lands on a
                          host of its own, but anywhere in the cluster;
                          locality_violations and mismatched_pgs.
  holes_shifted           rows compacted as a replicated pool's are, the
                          holes moved to the end: the OSDs are the right
                          ones in the wrong shards; mismatched_pgs on every
                          row with a hole before its last OSD (and, where
                          an OSD crosses into the other half of its row,
                          locality_violations besides).  So that
                          sampled rows have holes, the run also marks one
                          whole host's OSDs down (an out OSD leaves no
                          hole: indep's retries seat the chunk elsewhere).
"""

from contextlib import ExitStack, contextmanager

import numpy as np

from .faults import _mapping, _patched, stale_mapping

NONE = 0x7FFFFFFF


@contextmanager
def _fresh_mappers():
    """No compiled program from before the fault serves under it, and
    none traced under it serves after."""
    from ceph_tpu.osd.osdmap import _device_mapper_for
    _device_mapper_for.cache_clear()
    try:
        yield
    finally:
        _device_mapper_for.cache_clear()


def second_step_from_root():
    from ceph_tpu.ops.crush import device
    real_plan, real_chain = device.DeviceMapper._plan, device._chain_step

    def plan(self, ruleno, result_max):
        """Every later step planned as a descent from the rule's TAKE."""
        p = real_plan(self, ruleno, result_max)
        return p._replace(steps=p.steps[:1] + tuple(
            st._replace(outer_ds=self._depth_sizes([p.take_id],
                                                   st.want_type))
            for st in p.steps[1:]))

    def chain_step(fm, st, w, xs, *a, **kw):
        import jax.numpy as jnp
        root = next(a1 for rule in fm.rules.values()
                    for op, a1, _a2 in rule.steps if op == device.TAKE)
        return real_chain(fm, st, jnp.where(w < 0, jnp.int32(root), w), xs,
                          *a, **kw)

    stack = ExitStack()
    stack.enter_context(_fresh_mappers())
    stack.enter_context(_patched(device.DeviceMapper, "_plan", plan))
    stack.enter_context(_patched(device, "_chain_step", chain_step))
    return stack


def holes_shifted():
    from ..drivers import crush_churn_rules as driver
    real = driver.build_osdmap

    def build_osdmap(cfg):
        from ceph_tpu.osd.osdmap import OSD_UP
        m, ruleno = real(cfg)
        inc = m.new_incremental()
        for o in range(cfg["crush"]["osds_per_host"]):
            inc.new_state[o] = OSD_UP       # xor: host 0 of rack 0 is down
        m.apply_incremental(inc)
        return m, ruleno

    def alter(mp):
        for pm in mp.pools.values():
            for arr in (pm.up, pm.acting):
                order = np.argsort(arr == NONE, axis=1, kind="stable")
                arr[:] = np.take_along_axis(arr, order, axis=1)

    stack = ExitStack()
    stack.enter_context(_patched(driver, "build_osdmap", build_osdmap))
    stack.enter_context(_mapping(alter))
    return stack


FAULTS = {f.__name__: f for f in (stale_mapping, second_step_from_root,
                                  holes_shifted)}
