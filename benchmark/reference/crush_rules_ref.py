"""Plain reference for crush rules of any number of steps on straw2 maps
of any depth: one input at a time, Python integers.

    m = Map({-1: (3, [-2, -3], [w, w]), -2: (1, [0, 1], [w, w]), ...})
    m.do_rule(steps, x, result_max, osd_weight)

`steps` are (op, arg1, arg2) with the names below for `op`: take,
choose_firstn, choose_indep, chooseleaf_firstn, chooseleaf_indep,
set_choose_tries, set_chooseleaf_tries, set_chooseleaf_vary_r,
set_chooseleaf_stable, emit.  The tunables are the optimal profile
(choose_total_tries 50, chooseleaf_descend_once 1, chooseleaf_vary_r 1,
chooseleaf_stable 1, no local retries); every bucket is straw2.

Written from the published semantics of src/crush/mapper.c
(crush_do_rule, crush_choose_firstn, crush_choose_indep, is_out,
bucket_straw2_choose); the hashes and crush_ln are crush_ref.py's, which
have upstream's witnesses.  Imports nothing of the program.  Departures
from the C, none of which changes an answer on these maps:

  - no local retries (`flocal`, the permutation fallback): the optimal
    tunables set both to 0, and a rule cannot ask for them here;
  - no uniform, list, tree or straw buckets, so indep's `r` never takes
    the uniform bucket's stride and there is no per-bucket work space;
  - no choose_args (weight-sets): an item's weight is the bucket's own;
  - the scratch vectors a, b, c of crush_do_rule are Python lists made
    per step; `o + osize` is a slice, not a pointer.

Its witnesses are upstream's C for every piece (benchmark/tests/
test_reference_rules.py on data/upstream_crush_rules_golden.json: one
indep step, one chooseleaf indep step, two chained firstn steps,
reweights included).  No upstream answer is on record for two chained
indep steps: there the only witness is the program's host engine on
small maps (PERF.md, section 7).

Above do_rule, `pg_to_up_acting` runs the OSDMap steps of an erasure pool
with no upmap, temp or primary-affinity entries: pps from the pg, raw from
the rule, up is raw with a down or non-existent OSD made a hole in place
(an erasure pool's positions are shards: nothing shifts), acting is up,
the primary the first that is no hole.
"""

from .crush_ref import crush_ln, hash32_2, hash32_3, stable_mod

NONE = 0x7FFFFFFF       # CRUSH_ITEM_NONE: no mapping for this position
UNDEF = 0x7FFFFFFE      # CRUSH_ITEM_UNDEF: indep, not decided yet
S64_MIN = -(1 << 63)

(TAKE, CHOOSE_FIRSTN, CHOOSE_INDEP, EMIT, CHOOSELEAF_FIRSTN,
 CHOOSELEAF_INDEP, SET_CHOOSE_TRIES, SET_CHOOSELEAF_TRIES,
 SET_CHOOSELEAF_VARY_R, SET_CHOOSELEAF_STABLE) = (
    "take", "choose_firstn", "choose_indep", "emit", "chooseleaf_firstn",
    "chooseleaf_indep", "set_choose_tries", "set_chooseleaf_tries",
    "set_chooseleaf_vary_r", "set_chooseleaf_stable")


class Map:
    """`buckets`: id (< 0) -> (type, items, weights), weights 16.16 fixed
    point; a device is an id >= 0 of type 0."""

    TOTAL_TRIES = 50
    DESCEND_ONCE = 1
    VARY_R = 1
    STABLE = 1

    def __init__(self, buckets: dict, max_devices: int = None):
        self.buckets = buckets
        self.max_devices = (max_devices if max_devices is not None else 1 + max(
            [i for _t, items, _w in buckets.values() for i in items
             if i >= 0], default=-1))

    # -- one draw ----------------------------------------------------------

    def straw2(self, bucket: int, x: int, r: int) -> int:
        """bucket_straw2_choose: the item of the highest draw
        ln(hash / 65536) / weight; C's division truncates towards 0."""
        _type, items, weights = self.buckets[bucket]
        high, high_draw = 0, 0
        for i, (item, w) in enumerate(zip(items, weights)):
            if w:
                ln = crush_ln(hash32_3(x, item, r) & 0xFFFF) - (1 << 48)
                draw = -(-ln // w)      # ln <= 0: truncation, not floor
            else:
                draw = S64_MIN
            if i == 0 or draw > high_draw:
                high, high_draw = i, draw
        return items[high]

    def type_of(self, item: int) -> int:
        return self.buckets[item][0] if item < 0 else 0

    def is_out(self, osd_weight: list, item: int, x: int) -> bool:
        if item >= len(osd_weight):
            return True
        w = osd_weight[item]
        if w >= 0x10000:
            return False
        if w == 0:
            return True
        return (hash32_2(x, item) & 0xFFFF) >= w

    # -- crush_choose_firstn -----------------------------------------------

    def choose_firstn(self, bucket, weight, x, numrep, want, out, outpos,
                      out_size, tries, recurse_tries, to_leaf, vary_r,
                      stable, out2, parent_r) -> int:
        """Fills out[outpos:] with up to numrep distinct items of type
        `want` below `bucket`, in order; with to_leaf also out2 with one
        device below each.  Returns the new outpos."""
        count = out_size
        rep = 0 if stable else outpos
        while rep < numrep and count > 0:
            ftotal, placed = 0, None
            while True:             # retry_descent
                cur, give_up, reject = bucket, False, False
                r = rep + parent_r + ftotal
                while True:         # walk down to an item of the type
                    if not self.buckets[cur][1]:
                        reject = True
                        break
                    item = self.straw2(cur, x, r)
                    if item >= self.max_devices:
                        give_up = True
                        break
                    if self.type_of(item) != want:
                        if item >= 0 or item not in self.buckets:
                            give_up = True
                            break
                        cur = item
                        continue
                    break
                if give_up:
                    break           # skip this replica
                if not reject:
                    collide = item in out[:outpos]
                    if not collide and to_leaf:
                        if item < 0:
                            sub_r = r >> (vary_r - 1) if vary_r else 0
                            got = self.choose_firstn(
                                item, weight, x, 1 if stable else outpos + 1,
                                0, out2, outpos, count, recurse_tries, 0,
                                False, vary_r, stable, None, sub_r)
                            reject = got <= outpos
                        else:
                            out2[outpos] = item
                    if not reject and not collide and want == 0:
                        reject = self.is_out(weight, item, x)
                    if not reject and not collide:
                        placed = item
                        break
                ftotal += 1
                if ftotal >= tries:
                    break           # skip this replica
            if placed is not None:
                out[outpos] = placed
                outpos += 1
                count -= 1
            rep += 1
        return outpos

    # -- crush_choose_indep ------------------------------------------------

    def choose_indep(self, bucket, weight, x, left, numrep, want, out,
                     outpos, tries, recurse_tries, to_leaf, out2,
                     parent_r) -> None:
        """Decides positions out[outpos:outpos+left], each on its own
        sequence of draws, so that a position keeps its item when another
        fails; a position that cannot be filled ends as NONE."""
        end = outpos + left
        for rep in range(outpos, end):
            out[rep] = UNDEF
            if out2 is not None:
                out2[rep] = UNDEF
        for ftotal in range(tries):
            if left <= 0:
                break
            for rep in range(outpos, end):
                if out[rep] != UNDEF:
                    continue
                cur = bucket
                while True:
                    r = rep + parent_r + numrep * ftotal
                    if not self.buckets[cur][1]:
                        break               # empty: try again next round
                    item = self.straw2(cur, x, r)
                    if item >= self.max_devices:
                        out[rep] = NONE
                        if out2 is not None:
                            out2[rep] = NONE
                        left -= 1
                        break
                    if self.type_of(item) != want:
                        if item >= 0 or item not in self.buckets:
                            out[rep] = NONE
                            if out2 is not None:
                                out2[rep] = NONE
                            left -= 1
                            break
                        cur = item
                        continue
                    if item in out[outpos:end]:
                        break               # collision
                    if to_leaf:
                        if item < 0:
                            self.choose_indep(
                                item, weight, x, 1, numrep, 0, out2, rep,
                                recurse_tries, 0, False, None, r)
                            if out2[rep] == NONE:
                                break       # no leaf under it
                        else:
                            out2[rep] = item
                    if want == 0 and self.is_out(weight, item, x):
                        break
                    out[rep] = item
                    left -= 1
                    break
        for rep in range(outpos, end):
            if out[rep] == UNDEF:
                out[rep] = NONE
            if out2 is not None and out2[rep] == UNDEF:
                out2[rep] = NONE

    # -- crush_do_rule -----------------------------------------------------

    def do_rule(self, steps: list, x: int, result_max: int,
                osd_weight: list) -> list:
        tries = self.TOTAL_TRIES + 1        # the historical off-by-one
        leaf_tries = 0
        vary_r, stable = self.VARY_R, self.STABLE
        result, w = [], []
        for op, arg1, arg2 in steps:
            if op == TAKE:
                if arg1 in self.buckets or 0 <= arg1 < self.max_devices:
                    w = [arg1]
            elif op == SET_CHOOSE_TRIES:
                if arg1 > 0:
                    tries = arg1
            elif op == SET_CHOOSELEAF_TRIES:
                if arg1 > 0:
                    leaf_tries = arg1
            elif op == SET_CHOOSELEAF_VARY_R:
                if arg1 >= 0:
                    vary_r = arg1
            elif op == SET_CHOOSELEAF_STABLE:
                if arg1 >= 0:
                    stable = arg1
            elif op in (CHOOSE_FIRSTN, CHOOSELEAF_FIRSTN, CHOOSE_INDEP,
                        CHOOSELEAF_INDEP):
                if not w:
                    continue
                firstn = op in (CHOOSE_FIRSTN, CHOOSELEAF_FIRSTN)
                to_leaf = op in (CHOOSELEAF_FIRSTN, CHOOSELEAF_INDEP)
                o, c = [0] * result_max, [0] * result_max
                osize = 0
                for taken in w:
                    numrep = arg1 if arg1 > 0 else arg1 + result_max
                    if numrep <= 0 or taken not in self.buckets:
                        continue    # NONE, a device: no room taken
                    # the C passes o + osize: the callee sees position 0
                    room = result_max - osize
                    win, win2 = [0] * room, [0] * room
                    if firstn:
                        recurse = (leaf_tries if leaf_tries else
                                   1 if self.DESCEND_ONCE else tries)
                        n = self.choose_firstn(
                            taken, osd_weight, x, numrep, arg2, win, 0,
                            room, tries, recurse, to_leaf, vary_r, stable,
                            win2, 0)
                    else:
                        n = min(numrep, room)
                        self.choose_indep(
                            taken, osd_weight, x, n, numrep, arg2, win, 0,
                            tries, leaf_tries if leaf_tries else 1,
                            to_leaf, win2, 0)
                    o[osize:osize + n] = win[:n]
                    c[osize:osize + n] = win2[:n]
                    osize += n
                w = (c if to_leaf else o)[:osize]
            elif op == EMIT:
                result += w[:result_max - len(result)]
                w = []
            else:
                raise ValueError("step %r is outside this reference" % (op,))
        return result


def pg_to_up_acting(crush: Map, steps: list, pool_id: int, pg_num: int,
                    size: int, ps: int, osd_weight: list,
                    osd_up: list) -> tuple:
    """(up, up_primary, acting, acting_primary) of pg pool_id.ps of an
    erasure pool with pgp_num == pg_num and the hashpspool flag: `size`
    positions, NONE where the rule placed nothing or the OSD is down."""
    mask = (1 << (pg_num - 1).bit_length()) - 1
    pps = hash32_2(stable_mod(ps, pg_num, mask), pool_id)
    raw = crush.do_rule(steps, pps, size, osd_weight)
    up = [o if o != NONE and o < len(osd_up) and osd_up[o] else NONE
          for o in raw]
    up += [NONE] * (size - len(up))
    primary = next((o for o in up if o != NONE), -1)
    return up, primary, list(up), primary
