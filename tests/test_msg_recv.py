"""The messenger's receive path (msg/messenger.py: _Wire), fed by hand.

No socket and no cluster: a fake transport, and the stream handed to the
protocol through get_buffer/buffer_updated in pieces of a chosen size,
as asyncio's selector transport does after each recv_into.  Frames must
come out byte-equal and in order whatever the pieces; the bytes that
landed in a large frame's own buffer are counted by the span msgr.recv
(``direct``) and held to a model made from the pieces alone; a damaged
stream ends the transport as a fault with nothing of it dispatched; a
dispatcher that sleeps stops the reads.
"""

import asyncio
import contextlib
import os
import struct
import zlib

import pytest

from ceph_tpu.msg import Messenger, Policy, messenger
from ceph_tpu.msg.message import encode_message
from ceph_tpu.msg.messages import MOSDOpReply
from ceph_tpu.msg.messenger import (BANNER, MAX_FRAME, RX_BUF, TAG_ACK,
                                    TAG_MSG, Connection, _HDR, _Wire)

KIB = 1024


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, timeout=60))


class FakeTransport:
    def __init__(self):
        self.reading = True
        self.pauses = 0
        self.resumes = 0
        self.written = bytearray()
        self.closed = False

    def pause_reading(self):
        self.reading = False
        self.pauses += 1

    def resume_reading(self):
        self.reading = True
        self.resumes += 1

    def write(self, data):
        self.written += data

    def close(self):
        self.closed = True

    def is_closing(self):
        return self.closed


def new_wire(framed=True):
    wire = _Wire()
    wire.connection_made(FakeTransport())
    if framed:
        wire.start_frames()
    return wire


def frame(tag, payload):
    return _HDR.pack(tag, len(payload), zlib.crc32(payload)) + payload


def give(wire, data, piece=1 << 30):
    """One recv_into: at most `piece` bytes of `data` into the buffer
    the wire hands out.  Returns how many it took."""
    buf = wire.get_buffer(-1)
    assert len(buf) > 0
    n = min(len(buf), piece, len(data))
    buf[:n] = data[:n]
    wire.buffer_updated(n)
    return n


@pytest.fixture
def recv_spans(monkeypatch):
    """[(bytes, direct)] of every msgr.recv span entered."""
    seen = []

    def spy(name, **args):
        if name == "msgr.recv":
            seen.append((args["bytes"], args["direct"]))
        return contextlib.nullcontext()

    monkeypatch.setattr(messenger, "span", spy)
    return seen


def _mix(name):
    def msg(size):
        return (TAG_MSG, os.urandom(size))
    ack = (TAG_ACK, struct.pack(">Q", 7))
    if name == "small-300B":
        return [msg(300) for _ in range(40)]
    if name == "shards-512K":
        return [msg(512 * KIB + 181) for _ in range(3)]
    if name == "4M-between-small":
        return [msg(300), msg(300), msg(300), msg(4096 * KIB + 310),
                msg(300), msg(300), ack]
    assert name == "ack-glued"
    return [msg(300), ack, msg(5000), ack, ack, msg(300)]


@pytest.mark.parametrize("piece", [1, 7, 9, 64 * KIB, 1 << 30],
                         ids=["1B", "7B", "9B-header", "64K", "all"])
@pytest.mark.parametrize("mix", ["small-300B", "shards-512K",
                                 "4M-between-small", "ack-glued"])
def test_frames_come_out_whole_and_in_order(mix, piece, recv_spans):
    frames = _mix(mix)
    stream = b"".join(frame(t, p) for t, p in frames)
    # where each large payload lies in the stream
    large, off = [], 0
    for _t, p in frames:
        if _HDR.size + len(p) > RX_BUF:
            large.append((off + _HDR.size, off + _HDR.size + len(p)))
        off += _HDR.size + len(p)
    wire, out, cuts, off = new_wire(), [], [0], 0
    view = memoryview(stream)
    while off < len(stream):
        n = piece
        if piece < 64:
            # a large payload's middle goes in one piece (a byte at a
            # time over 4 MiB proves nothing more and takes a minute)
            for lo, hi in large:
                if lo + 64 <= off < hi - 64:
                    n = hi - 64 - off
        off += give(wire, view[off:off + n], n)
        cuts.append(off)
        while wire.frames:      # a consumer that keeps up
            tag, crc, payload = wire.frames.popleft()
            assert zlib.crc32(payload) == crc
            out.append((tag, bytes(payload)))
    assert out == frames
    assert wire.transport.pauses == 0
    assert sum(b for b, _d in recv_spans) == len(stream)
    # the model: of a large payload, what arrived after the read that
    # brought its header's last byte went straight to its own buffer
    want = 0
    for lo, hi in large:
        behind_header = min(c for c in cuts if c >= lo)
        want += hi - min(max(lo, behind_header), hi)
    assert sum(d for _b, d in recv_spans) == want
    assert all(0 <= d <= b and d in (0, b) for b, d in recv_spans)
    if large:
        total = sum(hi - lo for lo, hi in large)
        assert total - len(large) * RX_BUF <= want <= total
        if piece <= _HDR.size:
            assert want >= total - len(large) * piece
    else:
        assert want == 0


def test_what_each_read_is_offered():
    wire = new_wire()
    assert len(wire.get_buffer(-1)) == RX_BUF
    give(wire, frame(TAG_ACK, b"8" * 8) * 50)
    assert len(wire.frames) == 50
    part = frame(TAG_MSG, os.urandom(3000))
    give(wire, part[:1000])     # a read ends inside a frame
    assert len(wire.get_buffer(-1)) == RX_BUF - 1000   # the rest of it
    give(wire, part[1000:])
    assert len(wire.frames) == 51
    assert len(wire.get_buffer(-1)) == RX_BUF
    # a large frame: the reads go to its own buffer, a piece of RX_BUF
    # at a time and exactly to its end
    big = frame(TAG_MSG, os.urandom(2 * RX_BUF + 5))
    give(wire, big[:_HDR.size])
    assert len(wire.get_buffer(-1)) == RX_BUF
    give(wire, big[_HDR.size:_HDR.size + 10])
    assert len(wire.get_buffer(-1)) == RX_BUF
    assert give(wire, big[_HDR.size + 10:]) == RX_BUF
    assert give(wire, big[_HDR.size + 10 + RX_BUF:-7]) == RX_BUF - 12
    assert len(wire.get_buffer(-1)) == 7
    give(wire, big[-7:])
    assert bytes(wire.frames[51][2]) == big[_HDR.size:]
    assert len(wire.get_buffer(-1)) == RX_BUF


# -- through a Connection's session: faults, replay, back-pressure ---------


class Sink:
    def __init__(self):
        self.tids = []

    def ms_dispatch(self, conn, msg):
        self.tids.append(msg.tid)
        return True


def msg_frame(seq, data=b"x" * 64, src="osd.1"):
    m = MOSDOpReply(tid=seq, result=0, outs=[data], epoch=1, version=0)
    m.seq, m.src = seq, src
    return frame(TAG_MSG, encode_message(m, stamp=1.0))


async def _settle(turns=5):
    for _ in range(turns):
        await asyncio.sleep(0)


def _damaged(kind):
    good = msg_frame(2, os.urandom(RX_BUF + 5))
    if kind == "crc":
        bad = bytearray(good)
        bad[-100] ^= 1
        return bytes(bad), False
    if kind == "over-cap":
        return _HDR.pack(TAG_MSG, MAX_FRAME + 1, 0) + b"junk" * 10, False
    assert kind == "truncated"
    return good[:len(good) // 2], True


@pytest.mark.parametrize("kind", ["crc", "over-cap", "truncated"])
def test_damaged_stream_is_a_transport_fault(kind):
    """The frame before the damage is dispatched, the damaged one never;
    the session ends as a fault (not as a close), so a lossless peer
    keeps its unacked messages and replays them on the next transport."""

    async def main():
        msgr = Messenger("osd.0")
        sink = Sink()
        msgr.add_dispatcher(sink)
        conn = Connection(msgr, None, Policy.lossless_peer())
        conn.peer_entity = "osd.1"
        conn.send(MOSDOpReply(tid=77, result=0, outs=[], epoch=1,
                              version=0))
        (_seq, sent), = conn.unacked
        wire = new_wire(framed=False)
        session = asyncio.ensure_future(conn._session(wire))
        await _settle()
        assert sent in bytes(wire.transport.written)
        bad, then_eof = _damaged(kind)
        data = memoryview(msg_frame(1) + bad)
        while len(data) and wire.transport.reading:
            data = data[give(wire, data):]
        if then_eof:
            assert wire.eof_received() is None      # the transport closes
            wire.connection_lost(None)
        closed = await asyncio.wait_for(session, 5)
        assert closed is False                      # a fault, not a close
        assert sink.tids == [1]
        assert wire.transport.closed
        assert conn.is_open and conn.unacked == [(_seq, sent)]
        assert conn.stats.rx_msgs == 1
        # the next transport of the same session replays it
        wire2 = new_wire(framed=False)
        session2 = asyncio.ensure_future(conn._session(wire2))
        await _settle()
        assert sent in bytes(wire2.transport.written)
        assert conn.stats.resends == 2      # once per transport
        session2.cancel()
        await asyncio.gather(session2, return_exceptions=True)

    run(main())


def test_bytes_behind_the_handshake_are_frames():
    """The dialer's first frames can share a segment with its last
    handshake bytes: the handshake's reads take only their own, and what
    is left is cut as frames, a large frame's start among it."""

    async def main():
        wire = new_wire(framed=False)
        ident = b"i" * 90
        big = os.urandom(RX_BUF + 77)
        tail = frame(TAG_MSG, b"first") + frame(TAG_ACK, b"8" * 8) \
            + frame(TAG_MSG, big)
        data = memoryview(BANNER + struct.pack(">I", len(ident)) + ident
                          + tail)
        data = data[give(wire, data):]      # one segment
        assert await wire.readexactly(len(BANNER)) == BANNER
        (n,) = struct.unpack(">I", await wire.readexactly(4))
        assert await wire.readexactly(n) == ident
        assert not wire.frames
        wire.start_frames()
        assert [(t, bytes(p)) for t, _c, p in wire.frames] == \
            [(TAG_MSG, b"first"), (TAG_ACK, b"8" * 8)]
        while len(data):
            data = data[give(wire, data):]
        assert await wire.next_frame() == (TAG_MSG, zlib.crc32(b"first"),
                                           b"first")
        await wire.next_frame()
        tag, crc, payload = await wire.next_frame()
        assert (tag, crc, bytes(payload)) == (TAG_MSG, zlib.crc32(big),
                                              big)

    run(main())


def test_handshake_reads_wait_are_bounded_and_see_the_fault():
    async def main():
        wire = new_wire(framed=False)
        with pytest.raises(messenger.ConnectionError_):
            await wire.readexactly(RX_BUF + 1)      # no such blob
        reader = asyncio.ensure_future(wire.readexactly(10))
        await _settle()
        give(wire, b"12345")
        await _settle()
        assert not reader.done()
        give(wire, b"67890abc")
        assert await reader == b"1234567890"
        # nobody reads (an accepted transport waiting for its session):
        # the buffer fills, the reads stop, and start again once drained
        flood = memoryview(os.urandom(2 * RX_BUF))
        while wire.transport.reading:
            flood = flood[give(wire, flood):]
        assert wire._hi == RX_BUF and wire.transport.pauses == 1
        assert await wire.readexactly(3) == b"abc"
        assert not wire.transport.reading
        reader = asyncio.ensure_future(wire.readexactly(RX_BUF))
        await _settle()
        assert wire.transport.reading and not reader.done()
        give(wire, flood)
        assert len(await reader) == RX_BUF
        # the peer goes away mid-blob
        reader = asyncio.ensure_future(wire.readexactly(4))
        await _settle()
        wire.eof_received()
        with pytest.raises(ConnectionResetError):
            await reader

    run(main())


@pytest.mark.parametrize("size", [64, RX_BUF + 64],
                         ids=["small-frames", "large-frames"])
def test_a_sleeping_dispatcher_pauses_the_reads(size):
    """One message in dispatch, the frames of two reads behind it, then
    the transport is told to stop; it starts again when they are gone,
    and every message arrives, in order."""

    async def main():
        gate = asyncio.Event()
        sink = Sink()

        class Slow:
            async def ms_dispatch(self, conn, msg):
                await gate.wait()
                return sink.ms_dispatch(conn, msg)

        msgr = Messenger("osd.0")
        msgr.add_dispatcher(Slow())
        conn = Connection(msgr, None, Policy.lossy_client())
        conn.peer_entity = "client.1"
        wire = new_wire(framed=False)
        session = asyncio.ensure_future(conn._session(wire))
        await _settle()
        total = 12
        data = memoryview(b"".join(
            msg_frame(i, os.urandom(size), src="client.1")
            for i in range(1, total + 1)))
        one = len(data) // total
        held = 0
        while len(data):
            while len(data) and wire.transport.reading:
                data = data[give(wire, data, one):]     # a frame a read
                await _settle()
            if not len(data):
                break
            # paused: one in dispatch, two waiting, nothing more taken in
            assert len(wire.frames) == 2 and not sink.tids[held:]
            assert wire._body is None and wire._hi < one
            held = len(sink.tids) + 3
            gate.set()
            await _settle(10)
            gate.clear()
            assert wire.transport.reading
        gate.set()
        await _settle(10)
        assert sink.tids == list(range(1, total + 1))
        t = wire.transport
        assert t.pauses >= 2 and t.resumes == t.pauses
        session.cancel()
        await asyncio.gather(session, return_exceptions=True)

    run(main())


def test_drain_waits_for_the_transport_and_hears_its_loss():
    async def main():
        wire = new_wire()
        await asyncio.wait_for(wire.drain(), 1)     # nothing to wait for
        wire.pause_writing()
        waiting = asyncio.ensure_future(wire.drain())
        cancelled = asyncio.ensure_future(wire.drain())
        await _settle()
        assert not waiting.done()
        cancelled.cancel()
        await _settle()
        wire.resume_writing()
        await asyncio.wait_for(waiting, 1)
        wire.pause_writing()
        waiting = asyncio.ensure_future(wire.drain())   # after a cancel
        await _settle()
        assert not waiting.done()
        wire.connection_lost(ConnectionResetError("gone"))
        with pytest.raises(ConnectionResetError):
            await waiting
        with pytest.raises(ConnectionResetError):
            await wire.next_frame()

    run(main())
