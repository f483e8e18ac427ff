"""Plain reference for a replicated pool placed by a rule of several
TAKE/EMIT blocks (a stretch cluster's `take site1; chooseleaf firstn 2
type host; emit; take site2; chooseleaf firstn 2 type host; emit`): one
PG at a time, Python integers.

    m = crush_rules_ref.Map({...})
    up, up_primary, acting, acting_primary = pg_to_up_acting(
        m, steps, pool_id, pg_num, size, ps, osd_weight, osd_up)

The rule runs through crush_rules_ref.Map.do_rule, which runs the blocks
as crush_do_rule does: a TAKE starts the working vector afresh, each
choose step's draws start at r = 0, and EMIT appends the working vector
to the result, at most result_max entries in all.  Above it, the OSDMap
steps of a replicated pool with no upmap, temp or primary-affinity
entries (OSDMap.cc _pg_to_raw_osds, _raw_to_up_osds, _pick_primary):
pps from the pg, raw from the rule, up is raw with every OSD that is
down or does not exist dropped and the rest shifted to the front
(a replicated pool's positions are no shards), acting is up, and the
primary is up's first OSD.

Imports nothing of the program.  Its witnesses (benchmark/tests/
test_reference_stretch.py): a rule of two blocks answers what its two
one-block rules answer, put together and cut to result_max, on the maps
whose one-block answers upstream's C gave; and a one-block replicated
pool's rows equal crush_ref.py's, the sibling cell's reference.
"""

from .crush_ref import hash32_2, stable_mod
from .crush_rules_ref import NONE, Map  # noqa: F401  (the map it takes)


def pg_to_up_acting(crush: Map, steps: list, pool_id: int, pg_num: int,
                    size: int, ps: int, osd_weight: list,
                    osd_up: list) -> tuple:
    """(up, up_primary, acting, acting_primary) of pg pool_id.ps of a
    replicated pool with pgp_num == pg_num and the hashpspool flag: up
    and acting padded with NONE to `size` entries, as the program's
    tables hold them; the primary -1 where no OSD is up."""
    mask = (1 << (pg_num - 1).bit_length()) - 1
    pps = hash32_2(stable_mod(ps, pg_num, mask), pool_id)
    raw = crush.do_rule(steps, pps, size, osd_weight)
    up = [o for o in raw
          if o != NONE and 0 <= o < len(osd_up) and osd_up[o]]
    primary = up[0] if up else -1
    up += [NONE] * (size - len(up))
    return up, primary, list(up), primary
