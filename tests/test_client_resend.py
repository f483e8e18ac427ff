"""The client's resend deadline follows the round trip it measures.

RadosClient keeps an RFC 6298 estimator fed by every data op answered on
its first send; an op's resend ramp starts at the estimator's rto and no
deadline, jitter included, lies under it.  OP_RESEND_BASE/OP_RESEND_CAP
are the floors: a client that has measured nothing, or a cluster that
answers in milliseconds, keeps the ramp those two give.
"""

import asyncio
import random

import pytest

from ceph_tpu.client.rados import RadosClient, RttEstimator, _InFlight
from ceph_tpu.msg.messages import MOSDOpReply
from ceph_tpu.testing import LocalCluster
from ceph_tpu.utils.backoff import ExpBackoff

BASE, CAP = RadosClient.OP_RESEND_BASE, RadosClient.OP_RESEND_CAP


def run(coro, timeout=300):
    return asyncio.run(asyncio.wait_for(coro, timeout=timeout))


def client(seed=5, name="client.0") -> RadosClient:
    return RadosClient("127.0.0.1:1", name=name, seed=seed)


def estimator() -> RttEstimator:
    return RttEstimator(floor=BASE / 2, ceiling=CAP / 2)


def test_constants_keep_their_values():
    assert (BASE, CAP) == (0.5, 5.0)


def test_first_sample_seeds_then_smooths():
    est = estimator()
    assert est.srtt is None and est.rto == BASE / 2
    est.sample(0.4)
    assert (est.srtt, est.rttvar) == (0.4, 0.2)
    assert est.rto == pytest.approx(0.4 + 4 * 0.2)
    est.sample(0.8)     # rttvar from the old srtt, then srtt (RFC 6298 2.3)
    assert est.rttvar == pytest.approx(0.2 + (0.4 - 0.2) / 4)
    assert est.srtt == pytest.approx(0.4 + (0.8 - 0.4) / 8)
    assert est.rto == pytest.approx(est.srtt + 4 * est.rttvar)


@pytest.mark.parametrize("samples", [
    [0.002] * 40,                   # a tier-1 cluster
    [0.002, 0.3, 0.002, 0.004],
    [0.0],
], ids=["fast", "one-spike", "zero"])
def test_rto_never_under_the_floor(samples):
    est = estimator()
    for r in samples:
        est.sample(r)
        assert est.rto >= BASE / 2
    if len(samples) == 40:
        assert est.rto == BASE / 2      # and a fast cluster sits on it


# what the client has measured when an op is sent -> nothing, a fast
# cluster, round trips over today's first deadline, over the cap's, and
# a backed-off timer
STATES = {
    "fresh": ([], 0),
    "fast": ([0.003] * 20, 0),
    "slow": ([0.55, 0.6, 0.5, 0.7], 0),
    "slower-than-cap": ([4.0, 5.0, 4.5], 0),
    "fast-backed-off": ([0.003] * 20, 3),
    "slow-backed-off": ([0.55, 0.6, 0.5, 0.7], 2),
}


def learned(c: RadosClient, state: str) -> RadosClient:
    samples, timeouts = STATES[state]
    for r in samples:
        c.rtt.sample(r)
    for _ in range(timeouts):
        c.rtt.timed_out(c.rtt.rto)
    return c


@pytest.mark.parametrize("state", sorted(STATES))
def test_no_deadline_under_the_rto_or_the_floor(state):
    c = learned(client(), state)
    rto = c.rtt.rto
    for _ in range(200):
        ramp = c._resend_ramp()
        assert ramp.base >= BASE and ramp.cap >= CAP
        assert ramp.base == max(BASE, 2 * rto)
        assert ramp.cap == max(CAP, 2 * rto)
        waits = [ramp.next_delay() for _ in range(8)]
        assert min(waits) >= rto >= BASE / 2
        assert waits[0] <= 2 * rto
        assert max(waits) <= ramp.cap
    if state in ("fresh", "fast"):
        assert (ramp.base, ramp.cap) == (BASE, CAP)


def test_fresh_client_keeps_todays_schedule():
    c = client(seed=11)
    old = ExpBackoff(base=BASE, cap=CAP,
                     rng=random.Random("%s|%s" % (11, "client.0")))
    for r in (0.002, 0.004, 0.003):
        ramp = c._resend_ramp()
        assert [ramp.next_delay() for _ in range(6)] == \
            [old.next_delay() for _ in range(6)]
        old.reset()
        c.rtt.sample(r)


def test_timeout_doubles_rto_until_a_clean_sample():
    est = estimator()
    for r in (0.55, 0.6, 0.5, 0.7):
        est.sample(r)
    clean = est.rto
    assert clean > BASE
    # sixteen ops armed under one rto time out together: one back-off
    est.timed_out(clean * 1.5)
    assert est.rto == 2 * clean
    for _ in range(15):
        est.timed_out(clean * 1.5)
    assert est.rto == 2 * clean
    # the copies, armed under the doubled rto, time out as well: never
    # past the larger of the ceiling and twice what was measured
    est.timed_out(est.rto)
    assert est.rto == max(CAP / 2, 2 * clean)
    est.timed_out(est.rto)
    assert est.rto == max(CAP / 2, 2 * clean)
    est.sample(0.6)
    assert est.rto == pytest.approx(est.srtt + 4 * est.rttvar) \
        and est.rto < 2 * clean


def test_timeouts_with_no_sample_walk_todays_ramp():
    """A cluster that answers nothing: the deadlines' lower edges are
    0.25, 0.5, 1, 2, 2.5, 2.5 as OP_RESEND_BASE/CAP have them, and a
    release for cause (wait 0) moves nothing."""
    est = estimator()
    edges = []
    for _ in range(6):
        edges.append(est.rto)
        est.timed_out(0.0)
        assert est.rto == edges[-1]
        est.timed_out(est.rto)
    assert edges == [0.25, 0.5, 1.0, 2.0, 2.5, 2.5]


def _reply_to(c: RadosClient, tid: int, sends: int, age: float):
    loop = asyncio.get_running_loop()
    op = _InFlight(tid, 1, "obj", [{"op": "stat"}], loop.create_future())
    op.sends = sends
    op.first_sent = loop.time() - age
    c._inflight[tid] = op
    c._handle_reply(MOSDOpReply(tid=tid, result=0, outs=[{}]))
    assert op.future.done() and tid not in c._inflight


def test_only_an_op_sent_once_feeds_the_estimator():
    async def main():
        c = client()
        _reply_to(c, 1, sends=2, age=3.0)       # Karn's rule
        assert c.rtt.srtt is None and c.rtt.rto == BASE / 2
        _reply_to(c, 2, sends=1, age=0.6)
        assert c.rtt.srtt == pytest.approx(0.6, abs=0.05)
        before = (c.rtt.srtt, c.rtt.rttvar, c.rtt.rto)
        _reply_to(c, 3, sends=3, age=9.0)
        assert (c.rtt.srtt, c.rtt.rttvar, c.rtt.rto) == before
        c.rtt.timed_out(c.rtt.rto)
        _reply_to(c, 4, sends=2, age=9.0)       # nor ends a back-off
        assert c.rtt.rto == 2 * before[2]

    run(main())


def test_seeded_rng_replays_the_schedule():
    def schedule(seed):
        c = client(seed=seed)
        waits = []
        for r in (0.4, 0.9, 0.6, 2.0, 0.5):
            c.rtt.sample(r)
            ramp = c._resend_ramp()
            waits += [ramp.next_delay() for _ in range(3)]
        return waits

    assert schedule(7) == schedule(7)
    assert schedule(7) != schedule(8)


def test_slow_link_stops_resending_and_lossy_link_still_recovers():
    """The fault at tier-1 size: every frame from the client to an OSD
    is held 0.5-0.6 s, over the first deadline the constants alone give
    (0.25-0.5 s), so a client that takes its deadline from them sends
    every write twice.  The estimator learns the round trip inside the
    warm-up; then the same client on a fast, lossy link (a fifth of its
    frames dropped) still completes every write."""

    async def main():
        c = await LocalCluster(n_osds=3, seed=21).start()
        try:
            pid = await c.create_pool("data", pg_num=8, size=2)
            await c.wait_health(pid)
            inj = c.injector("client")
            inj.add_rule(src="client.0", dst="osd.*", delay_p=1.0,
                         delay=0.5, delay_max=0.6)
            io = c.client.io_ctx("data")
            payloads = {}

            async def write(i):
                oid = "slow-%d" % i
                payloads[oid] = (b"payload-%d|" % i) * 20
                await asyncio.wait_for(
                    io.write_full(oid, payloads[oid]), 60)

            for i in range(5):
                await write(i)
            warm = c.client.op_resends
            assert c.client.rtt.rto > 0.5
            for i in range(5, 25):
                await write(i)
            assert inj.frames_delayed >= 25 and inj.frames_dropped == 0
            assert (c.client.op_resends - warm) / 20 < 0.1, \
                (warm, c.client.op_resends, c.client.rtt.rto)

            inj.clear_rules()
            inj.add_rule(src="client.0", dst="osd.*", drop=0.2)
            for i in range(25, 40):
                await write(i)
            assert inj.frames_dropped > 0, "schedule injected nothing"
            inj.clear_rules()
            for oid, data in payloads.items():
                assert await io.read(oid) == data
        finally:
            await c.stop()

    run(main())
