"""crushtool/osdmaptool/compiler/tester coverage.

Mirrors src/test/crush/ + crushtool CLI behavior: text round-trip
preserves mappings bit-for-bit, tester statistics behave, and the CLI
entry points run end to end.
"""

import json
import os

from ceph_tpu.cli import crushtool, osdmaptool
from ceph_tpu.models.crushcompiler import compile, decompile
from ceph_tpu.models.crushmap import STRAW2
from ceph_tpu.models.crushtester import CrushTester
from ceph_tpu.ops.crush.host import Mapper

MAP_TEXT = """
# minimal two-level map
tunable choose_total_tries 50
device 0 osd.0
device 1 osd.1
device 2 osd.2
device 3 osd.3
device 4 osd.4
device 5 osd.5
type 0 osd
type 1 host
type 2 root
host host0 {
    id -2
    alg straw2
    hash 0
    item osd.0 weight 1.000
    item osd.1 weight 1.000
    item osd.2 weight 2.000
}
host host1 {
    id -3
    alg straw2
    hash 0
    item osd.3 weight 1.000
    item osd.4 weight 1.000
    item osd.5 weight 1.000
}
root default {
    id -1
    alg straw2
    hash 0
    item host0
    item host1
}
rule replicated_rule {
    id 0
    type replicated
    step take default
    step chooseleaf firstn 0 type host
    step emit
}
"""


def test_compile_basics():
    m = compile(MAP_TEXT)
    assert m.buckets[-2].alg == STRAW2
    assert m.buckets[-2].item_weights == [0x10000, 0x10000, 0x20000]
    # parent picked up subtree weights
    assert m.buckets[-1].item_weights == [0x40000, 0x30000]
    assert m.types[1] == "host"
    assert m.rules[0].name == "replicated_rule"


def test_roundtrip_preserves_mappings():
    m = compile(MAP_TEXT)
    m2 = compile(decompile(m))
    weights = [0x10000] * 6
    a, b = Mapper(m), Mapper(m2)
    for x in range(512):
        assert a.do_rule(0, x, 3, weights) == b.do_rule(0, x, 3, weights)


STRETCH_RULE = ["step take site1", "step chooseleaf firstn 2 type host",
                "step emit", "step take site2",
                "step chooseleaf firstn 2 type host", "step emit"]


def test_the_stretch_rule_round_trips():
    """upstream's stretch_rule (doc/rados/operations/stretch-mode.rst),
    two TAKE/EMIT blocks on a map of two datacenters: decompile writes
    its six lines back, and compiling them again gives the same steps
    and the same mappings."""
    sites = "".join(
        "datacenter %s {\n id %d\n alg straw2\n hash 0\n item host%d\n}\n"
        % (name, -4 - i, i) for i, name in enumerate(("site1", "site2")))
    text = MAP_TEXT.replace("type 2 root", "type 8 datacenter\ntype 11 root")
    text = text.replace(
        "root default {", sites + "root default {").replace(
        "    item host0\n    item host1", "    item site1\n    item site2")
    text = text.replace(
        "    step take default\n    step chooseleaf firstn 0 type host\n"
        "    step emit",
        "\n".join("    " + line for line in STRETCH_RULE))
    m = compile(text)
    out = decompile(m)
    rule = out[out.index("rule replicated_rule {"):].splitlines()
    assert [line.strip() for line in rule[3:9]] == STRETCH_RULE
    m2 = compile(out)
    assert m2.rules[0].steps == m.rules[0].steps
    assert len(m.rules[0].steps) == 6
    weights = [0x10000] * 6
    a, b = Mapper(m), Mapper(m2)
    for x in range(256):
        got = a.do_rule(0, x, 4, weights)
        assert got == b.do_rule(0, x, 4, weights)
        assert got[0] in (0, 1, 2) and got[1] in (3, 4, 5)


def test_tester_statistics():
    m = compile(MAP_TEXT)
    t = CrushTester(m)
    rep = t.test_rule(0, 2, num_inputs=2048)
    assert rep.bad_mappings == 0
    assert rep.total_placements == 4096
    # osd.2 has double weight: it must land clearly above its peers
    counts = rep.device_counts
    assert counts[2] > counts[0]
    assert counts[2] > counts[1]
    # utilization stays near 1.0 for a healthy straw2 map
    assert rep.max_deviation() < 0.25
    cmp = t.compare(0, 2, num_inputs=512)
    assert cmp["rule"]["bad_mappings"] == 0
    assert cmp["random_placement"]["num_inputs"] == 512


def test_crushtool_cli_roundtrip(tmp_path):
    src = tmp_path / "map.txt"
    src.write_text(MAP_TEXT)
    binp = tmp_path / "map.bin"
    outp = tmp_path / "out.txt"
    assert crushtool.main(["-c", str(src), "-o", str(binp)]) == 0
    assert crushtool.main(["-d", str(binp), "-o", str(outp)]) == 0
    m = compile(outp.read_text())
    assert m.buckets[-1].items == [-2, -3]
    assert crushtool.main(["-i", str(binp), "--test", "--rule", "0",
                           "--num-rep", "2", "--max-x", "255"]) == 0


def test_crushtool_build(tmp_path, capsys):
    binp = tmp_path / "built.bin"
    assert crushtool.main(["--build", "--num-osds", "8",
                           "host", "straw2", "4",
                           "-o", str(binp)]) == 0
    m = crushtool.load_map(str(binp))
    hosts = [b for b in m.buckets.values() if b.type == 1]
    assert len(hosts) == 2
    assert all(len(h.items) == 4 for h in hosts)


def test_osdmaptool_cli(tmp_path, capsys):
    mapfile = tmp_path / "osdmap.bin"
    assert osdmaptool.main(["--createsimple", "6", str(mapfile),
                            "--pg-num", "64"]) == 0
    capsys.readouterr()
    assert osdmaptool.main([str(mapfile), "--print"]) == 0
    info = json.loads(capsys.readouterr().out)
    assert info["max_osd"] == 6 and info["num_up"] == 6
    assert osdmaptool.main([str(mapfile), "--test-map-pgs"]) == 0
    stats = json.loads(capsys.readouterr().out)
    assert stats["pg_total"] == 64
    assert stats["size_histogram"] == {"3": 64}
    # the bulk (vectorized) mapper agrees with the scalar pipeline
    assert osdmaptool.main([str(mapfile), "--test-map-pgs",
                            "--bulk"]) == 0
    bulk = json.loads(capsys.readouterr().out)
    assert bulk == stats
