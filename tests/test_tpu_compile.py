"""The main path's Pallas kernels, compiled for a described v5e chip.

No chip is attached here: the TPU compiler that is installed compiles
for a topology that is only described, and refuses what the chip's
compiler would refuse (misaligned slices, too much VMEM, a program
that does not fit HBM) — which interpret mode cannot show.  Nothing
runs, so these say nothing about results or time.

The topology is described inside a fixture (one process at a time may
load libtpu, and every xdist worker imports this file), the builders
are steered onto their Mosaic branch inside the test, and every object
is built fresh: nothing Mosaic-built may reach a process-wide cache
that the CPU tests of the same worker read next.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # such a compile can be written to the persistent cache but not
    # read back without a chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Kernel builders decide interpret-vs-Mosaic from the backend at
    build time; here the backend is the CPU, so say "tpu" while a test
    builds and lowers."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _fused(k, m):
    from ceph_tpu.ec.kernels import FusedEncoder
    from ceph_tpu.ec.matrices import isa_rs_vandermonde_matrix
    # the tile ec/batcher.py picks for the profile
    tile = 262144 if k + m <= 11 else 131072
    return FusedEncoder(isa_rs_vandermonde_matrix(k, m), tile_bytes=tile)


@pytest.mark.parametrize("chunk_bytes", [4 << 10, 4 << 20])
@pytest.mark.parametrize("k,m", [(8, 3), (4, 2), (10, 4)])
def test_fused_encode_compiles(one_chip, mosaic, k, m, chunk_bytes):
    enc = _fused(k, m)
    lanes = chunk_bytes // 4
    _compiled_text(enc._fn_for(lanes), one_chip,
                   ((k, lanes), jnp.uint32))


def test_fused_one_shard_decoder_compiles(one_chip, mosaic):
    dec = _fused(8, 3).decoder_for(
        (2,), tuple(i for i in range(11) if i != 2))
    lanes = (64 << 10) // 4
    _compiled_text(dec._fn_for(lanes), one_chip,
                   ((8, lanes), jnp.uint32))


def _flat_map():
    """A fresh FlatMap of the 1000-OSD map chip_smoke.py maps: 50
    straw2 hosts of 20 under one straw2 root."""
    from chip_smoke import build_osdmap
    from ceph_tpu.ops.crush.device import FlatMap
    return FlatMap(build_osdmap(1000, 4096).crush)


@pytest.mark.parametrize("depth_sizes,want_type",
                         [((50,), 1), ((50, 20), 0)],
                         ids=["outer", "two-level"])
def test_crush_descend_compiles(one_chip, mosaic, depth_sizes,
                                want_type):
    from ceph_tpu.ops.crush import pallas_draw
    fn = pallas_draw.make_descend_kernel(_flat_map(), depth_sizes,
                                         want_type)
    assert fn is not None, "map outside the kernel's table budget"
    lanes = ((1 << 20,), jnp.int32)     # DeviceMapper.CHUNK
    _compiled_text(fn, one_chip, lanes, lanes, lanes, lanes)
    # the dense pass's tail: CHUNK / RC_ROW row groups of TAIL_KT slots
    tail = ((1 << 17,), jnp.int32)
    _compiled_text(fn, one_chip, tail, tail, tail, tail)


def _flat_map_10k():
    """A fresh FlatMap of the benchmark's three-level map: 20 racks of
    25 hosts of 20 OSDs, 521 buckets."""
    import json
    import os
    from benchmark.drivers.crush_churn_rules import build_crush
    from ceph_tpu.ops.crush.device import FlatMap
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "crush-10kosd-lrc-4m.json")) as f:
        return FlatMap(build_crush(json.load(f)))


@pytest.mark.parametrize("depth_sizes,want_type,chunks",
                         [((20,), 2, 1), ((25,), 1, 2), ((20,), 0, 2)],
                         ids=["root-rack", "rack-host", "host-osd"])
def test_crush_descend_compiles_for_the_two_step_rule(
        one_chip, mosaic, depth_sizes, want_type, chunks):
    """`choose indep 2 type rack; chooseleaf indep 4 type host`: the
    first step's descent at one chunk's lanes, the second step's two
    at two chunks' (its two takes run as lanes of one choose)."""
    from ceph_tpu.ops.crush import pallas_draw
    fn = pallas_draw.make_descend_kernel(_flat_map_10k(), depth_sizes,
                                         want_type)
    assert fn is not None, "map outside the kernel's table budget"
    lanes = ((chunks << 20,), jnp.int32)
    _compiled_text(fn, one_chip, lanes, lanes, lanes, lanes)


def test_lrc_pool_program_compiles_with_its_tails(one_chip, mosaic):
    """The dense program of the benchmark's 4M-PG LRC pool, as
    map_pool_state builds it: both steps of the rule with a tail of
    their own, sized from the steps' geometry, every descent in Pallas
    at the chunk's, the takes' and both tails' widths, and no more
    code than the three dense rounds it replaces (68.5 MB and nine
    descents when compiled here for this chip at PR 35; the tails'
    rounds are a loop, so the program holds six)."""
    import json
    import os
    from benchmark.drivers.crush_churn_rules import build_crush, make_rule
    from ceph_tpu.ops.crush.device import DeviceMapper
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "crush-10kosd-lrc-4m.json")) as f:
        cfg = json.load(f)
    crush = build_crush(cfg)
    ruleno = make_rule(cfg, crush)
    dm = DeviceMapper(crush)
    chunk, n_chunks, size = DeviceMapper.CHUNK, 4, 8
    tails = tuple(
        dm._tail_slots(ruleno, size, chunk,
                       dm._tail_start(ruleno, size, i), i)
        for i in range(2))
    # two racks of 20 collide in 5% of the lanes, four hosts of 25 in
    # 22% of the takes: 102 and 456 hits a row group and two deviations
    assert tails == (128, 512)
    pg_num = chunk * n_chunks
    fn = dm._compiled_pool(ruleno, size, False, False, pg_num,
                           pg_num - 1, 1, True, chunk, n_chunks, tails)
    osds = 10000
    args = [jax.ShapeDtypeStruct((osds,), d, sharding=one_chip)
            for d in (jnp.int32, np.bool_, np.bool_, jnp.int32)]
    compiled = fn.lower(*args).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    for kernel, times in (("crush_straw2_descend", 6),
                          ("crush_rowcompact", 2), ("crush_rowgather", 2),
                          ("crush_rowexpand", 2), ("crush_post_up", 1)):
        assert sum('/%s/pallas_call"' % kernel in line
                   for line in calls) == times, kernel
    widths = (chunk, 2 * chunk, chunk // 2048 * 128,
              2 * chunk // 2048 * 512)
    assert all(dm.fm.descent_in_pallas[n] for n in widths)
    code = compiled.memory_analysis().generated_code_size_in_bytes
    print("LRC pool program: %.1f MB of code" % (code / 1e6))
    assert code < 68.5e6


def test_crush_post_compiles_for_an_erasure_pool(one_chip, mosaic):
    """Eight positional slots, nothing shifts, one chunk of a pass."""
    from ceph_tpu.ops.crush import pallas_draw
    fn = pallas_draw.make_post_kernel(10000, 8, False)
    _compiled_text(fn, one_chip, ((1 << 20, 8), jnp.int32),
                   ((10000,), np.bool_))


# the 10M-PG pool of the smoke: ten DeviceMapper.CHUNK-sized chunks
NPG = 10 << 20


@pytest.mark.parametrize("kernel", ["post", "hitscan", "rowcompact",
                                    "rowcompact-tail", "rowgather-tail",
                                    "rowexpand-tail"])
def test_crush_lane_kernels_compile(one_chip, mosaic, kernel):
    from ceph_tpu.ops.crush import pallas_draw
    from ceph_tpu.ops.crush.device import DeviceMapper
    raw = ((NPG, 3), jnp.int32)
    osds = ((1000,), np.bool_)
    if kernel == "post":
        fn = pallas_draw.make_post_kernel(1000, 3, True)
        _compiled_text(fn, one_chip, raw, osds)
    elif kernel == "hitscan":
        fn = pallas_draw.make_hitscan_kernel(1000, 3)
        _compiled_text(fn, one_chip, raw, osds)
    elif kernel == "rowcompact":
        fn = pallas_draw.make_rowcompact_kernel(
            NPG, DeviceMapper.RC_ROW, DeviceMapper.RC_KT, 10_000_000)
        _compiled_text(fn, one_chip, ((NPG,), np.bool_))
    elif kernel == "rowcompact-tail":
        # one chunk of the dense pass, its own index space
        chunk = DeviceMapper.CHUNK
        fn = pallas_draw.make_rowcompact_kernel(
            chunk, DeviceMapper.RC_ROW, DeviceMapper.TAIL_KT, chunk)
        _compiled_text(fn, one_chip, ((chunk,), np.bool_))
    elif kernel == "rowgather-tail":
        # an indep step's widest tail on a second step's takes: four
        # slots of out and of leaves, and the take
        lanes, kt = 2 * DeviceMapper.CHUNK, DeviceMapper.INDEP_TAIL_KT_MAX
        fn = pallas_draw.make_rowgather_kernel(
            lanes, DeviceMapper.RC_ROW, kt, 9)
        _compiled_text(fn, one_chip, ((lanes,), np.bool_),
                       ((lanes, 9), jnp.int32))
    else:
        # three replicas and the flag, at the widest tail there is
        chunk, kt = DeviceMapper.CHUNK, DeviceMapper.TAIL_KT_MAX
        fn = pallas_draw.make_rowexpand_kernel(
            chunk, DeviceMapper.RC_ROW, kt, 4)
        _compiled_text(fn, one_chip, ((chunk,), np.bool_),
                       ((chunk, 4), jnp.int32),
                       ((chunk // DeviceMapper.RC_ROW * kt, 4), jnp.int32))
