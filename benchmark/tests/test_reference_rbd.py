"""The overwrite cell's plain references (beside test_reference.py, which
holds the rados payloads' and the CRUSH reference's): the Reed-Solomon
encoder of rs_isa.py held to closed forms and to the isa k=8,m=3 entry of
tests/golden/ec_corpus.json (a self-vector of this repository: bytes this
program's isa plug-in produced once, not upstream's), and the image of
rbd_image.py: its draws, its stamps, and what a block may hold when writes
met on it."""

import hashlib
import itertools
import json
import os

import numpy as np
import pytest

from benchmark.reference import rs_isa
from benchmark.reference.rbd_image import Image

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _shift_and_add(a: int, b: int) -> int:
    out = 0
    while b:
        if b & 1:
            out ^= a
        a <<= 1
        if a & 0x100:
            a ^= rs_isa.POLY
        b >>= 1
    return out


def test_gf_mul_is_shift_and_add_on_every_pair():
    want = np.array([[_shift_and_add(a, b) for b in range(256)]
                     for a in range(256)], np.uint8)
    assert np.array_equal(rs_isa.MUL, want)
    assert rs_isa.gf_mul(0x80, 2) == 0x1d       # the polynomial's tail


def test_coding_rows_are_powers_of_two_to_the_r():
    rows = rs_isa.coding_rows(8, 3)
    assert rows[0] == [1] * 8
    assert rows[1] == [1, 2, 4, 8, 16, 32, 64, 128]
    for r, row in enumerate(rows):
        g = rs_isa.gf_pow(2, r)
        assert row[0] == 1
        assert all(row[j + 1] == rs_isa.gf_mul(row[j], g) for j in range(7))


def _rank(rows: list) -> int:
    """Gaussian elimination over GF(2^8)."""
    m = [list(r) for r in rows]
    rank = 0
    for col in range(len(m[0])):
        piv = next((i for i in range(rank, len(m)) if m[i][col]), None)
        if piv is None:
            continue
        m[rank], m[piv] = m[piv], m[rank]
        inv = next(x for x in range(1, 256)
                   if rs_isa.gf_mul(m[rank][col], x) == 1)
        m[rank] = [rs_isa.gf_mul(v, inv) for v in m[rank]]
        for i in range(len(m)):
            if i != rank and m[i][col]:
                f = m[i][col]
                m[i] = [v ^ rs_isa.gf_mul(f, p)
                        for v, p in zip(m[i], m[rank])]
        rank += 1
    return rank


def test_any_8_of_the_11_rows_are_invertible():
    k, m = 8, 3
    full = [[int(i == j) for j in range(k)] for i in range(k)] \
        + rs_isa.coding_rows(k, m)
    for keep in itertools.combinations(range(k + m), k):
        assert _rank([full[i] for i in keep]) == k, keep


def test_parity_0_is_the_xor_of_the_data_chunks():
    rng = np.random.default_rng(3)
    data = [rng.integers(0, 256, 4096, dtype=np.uint8) for _ in range(8)]
    parity = rs_isa.encode([d.tobytes() for d in data], 3)
    assert parity[0] == np.bitwise_xor.reduce(data).tobytes()
    assert len(parity) == 3 and len({len(p) for p in parity}) == 1
    with pytest.raises(ValueError):
        rs_isa.encode([b"ab", b"c"], 1)


def test_encode_is_linear_which_is_what_a_parity_delta_rests_on():
    rng = np.random.default_rng(5)
    old = [rng.integers(0, 256, 512, dtype=np.uint8) for _ in range(4)]
    new = [c.copy() for c in old]
    new[2][100:200] = rng.integers(0, 256, 100, dtype=np.uint8)
    delta = [(a ^ b).tobytes() for a, b in zip(old, new)]
    p_old = rs_isa.encode([c.tobytes() for c in old], 2)
    p_new = rs_isa.encode([c.tobytes() for c in new], 2)
    p_delta = rs_isa.encode(delta, 2)
    for i in range(2):
        assert bytes(a ^ b for a, b in zip(p_old[i], p_delta[i])) \
            == p_new[i]


def test_encode_gives_the_corpus_entrys_parity_chunks():
    with open(os.path.join(ROOT, "tests", "golden", "ec_corpus.json")) as f:
        corpus = json.load(f)
    entry, = [e for e in corpus["entries"] if e["plugin"] == "isa"
              and e["profile"] == {"k": "8", "m": "3",
                                   "technique": "reed_sol_van"}]
    payload = bytes((7 * i + 3) % 256 for i in range(4096)) + b"tail-bytes!"
    assert hashlib.sha256(payload).hexdigest() == corpus["payload_sha256"]
    cs = entry["chunk_size"]
    padded = payload + bytes(8 * cs - len(payload))
    chunks = [padded[j * cs:(j + 1) * cs] for j in range(8)]
    for j in range(8):
        assert hashlib.sha256(chunks[j]).hexdigest() \
            == entry["sha256"][str(j)]
    parity = rs_isa.encode(chunks, 3)
    for i in range(3):
        assert hashlib.sha256(parity[i]).hexdigest() \
            == entry["sha256"][str(8 + i)], "parity %d" % i


# -- the image ---------------------------------------------------------------


def test_the_image_is_made_from_the_seed_alone():
    a = Image(7, 1 << 20, 1 << 16, 4096, 4)
    b = Image(7, 1 << 20, 1 << 16, 4096, 4)
    c = Image(2 ** 31 + 11, 1 << 20, 1 << 16, 4096, 4)
    assert (a.blocks, a.objects) == (256, 16)
    draws = [a.block(i) for i in range(5000)]
    assert draws == [b.block(i) for i in range(5000)]
    assert draws != [c.block(i) for i in range(5000)]
    assert min(draws) >= 0 and max(draws) < 256
    assert len(set(draws)) == 256       # uniform: every block is drawn
    assert a.payload(5) == b.payload(5) != c.payload(5)
    assert len(a.payload(5)) == 4096
    assert a.payload(5) != a.payload(5 + 4)     # same buffer, other stamp
    assert a.object(3) == b.object(3) and len(a.object(3)) == 1 << 16
    assert a.prefill_block(17) == a.object(1)[4096:8192]
    with pytest.raises(ValueError):
        Image(7, 1 << 20, 3 << 15, 4096, 4)


def _two_writes_to_one_block(img):
    first = next(i for i in range(1, 5000) if img.block(i) == img.block(0))
    return 0, first


def test_a_block_holds_the_last_acknowledged_write():
    img = Image(7, 1 << 20, 1 << 16, 4096, 4)
    w0, w1 = _two_writes_to_one_block(img)
    b = img.block(w0)
    assert img.allowed(b) == {img.prefill_block(b)}
    img.submitted(w0)
    # in flight, never acknowledged: either
    assert img.allowed(b) == {img.prefill_block(b), img.payload(w0)}
    img.acknowledged(w0)
    assert img.allowed(b) == {img.payload(w0)}
    img.submitted(w1)       # after w0's ack: w0 may no longer be there,
    img.acknowledged(w1)    # once w1 is acknowledged
    assert img.allowed(b) == {img.payload(w1)}
    assert img.overwritten() == [b]


def test_writes_in_flight_together_may_land_in_either_order():
    img = Image(7, 1 << 20, 1 << 16, 4096, 4)
    w0, w1 = _two_writes_to_one_block(img)
    b = img.block(w0)
    img.submitted(w0)
    img.submitted(w1)
    img.acknowledged(w1)
    img.acknowledged(w0)
    assert img.allowed(b) == {img.payload(w0), img.payload(w1)}
    assert img.readable(b) == {img.prefill_block(b), img.payload(w0),
                               img.payload(w1)}


def test_mismatched_blocks_counts_touched_and_untouched_alike():
    img = Image(7, 1 << 20, 1 << 16, 4096, 4)
    img.submitted(0)
    img.acknowledged(0)
    n, at = divmod(img.block(0) * 4096, 1 << 16)
    good = bytearray(img.object(n))
    good[at:at + 4096] = img.payload(0)
    assert img.mismatched_blocks(n, bytes(good)) == 0
    assert img.mismatched_blocks(n, img.object(n)) == 1      # write lost
    other = (at + 8192) % (1 << 16)
    bad = bytearray(good)
    bad[other + 5] ^= 1                 # a byte nobody wrote
    assert img.mismatched_blocks(n, bytes(bad)) == 1
    bad[at + 7] ^= 1
    assert img.mismatched_blocks(n, bytes(bad)) == 2
    assert img.mismatched_blocks(n, b"") == 16              # no answer
    assert img.mismatched_blocks((n + 1) % 16,
                                 img.object((n + 1) % 16)) == 0
