"""Content-defined chunking + batched fingerprints: the data-reduction
plane's device kernels.

"GPUs as Storage System Accelerators" (arXiv:1202.3669, PAPERS.md)
names hashing/deduplication as the canonical storage offload, and the
two expensive phases of dedup are exactly the primitives this repo
already runs on-chip: a rolling hash over every byte position
(`device/lzkernel.py`'s gram machinery) and a digest per chunk
(`device/digest.py`'s CRC lanes).  This module composes them:

* **rolling-hash boundary candidates on-device** — every position i
  hashes the 8-byte window ending at i as two le32 grams mixed with
  the lzkernel multiplicative constant plus a second odd prime:
  ``mix = (le32(b[i-7:i-3]) * C1) ^ (le32(b[i-3:i+1]) * C2)``; a
  position is a CANDIDATE cut iff ``mix & (CHUNK_AVG-1) == MAGIC``.
  Fully parallel across positions and lanes — blobs split into
  fixed ``SEG``-byte body segments with an 8-byte left margin (the
  Ragged Paged Attention discipline: variable-length blobs inside
  fixed-geometry programs), lanes bucket pow2 between ``_MIN_LANES``
  and ``_MAX_LANES`` (3 programs), oversized batches chunk into more
  dispatches of the SAME programs.
* **sequential min/avg/max resolution on host in BOTH paths** — the
  candidate mask is the parallel 99%; walking it into actual cuts
  (first candidate >= start+CHUNK_MIN, forced cut at start+CHUNK_MAX)
  is a cheap O(cuts) host walk shared verbatim by the device and
  fallback paths, so bit-parity of the cut lists reduces to
  bit-parity of the masks — which is exact by construction (the host
  mask zero-pads the blob front exactly like the first segment's
  staged margin).
* **chunk fingerprints through the digest lanes** — one
  ``crc32_batch`` dispatch per chunk batch (``CHUNK_MAX`` ==
  digest.DEVICE_MAX_BYTES, so every chunk digests in one lane);
  fingerprints are ``"%08x-%x" % (crc32, len)`` and a chunk object's
  oid embeds its fingerprint — content addressing the deep scrub can
  verify against the stored bytes for free.
* **admission + degradation identical to the digest plane** — the
  ``background`` class, DeviceBusy / poisoned chip / offload-off /
  mid-dispatch failure (poisons THIS chip) all land on the numpy
  reference, which is the same function.

Bit-parity contract: `chunk_host` and the device path produce the
identical cut lists and fingerprints (pinned by tests/test_dedup.py).
"""

from __future__ import annotations

import functools
import os

import numpy as np

from ..device.runtime import DeviceBusy, DeviceRuntime, K_BACKGROUND

# chunk-size policy: candidates fire at 1/CHUNK_AVG positions, the
# resolution walk enforces [CHUNK_MIN, CHUNK_MAX].  CHUNK_MAX equals
# digest.DEVICE_MAX_BYTES so every chunk fingerprints in one CRC lane.
CHUNK_MIN = 2048
CHUNK_AVG = 8192                # mask = CHUNK_AVG - 1 (pow2 required)
CHUNK_MAX = 16384

SEG = 8192                      # body bytes per device lane
MARGIN = 8                      # rolling-window left margin per lane

_MIX1 = np.uint32(2654435761)   # lzkernel's multiplicative hash prime
_MIX2 = np.uint32(0x85EBCA77)   # second odd prime (xxhash PRIME32_2)
_MAGIC = np.uint32(0x13AB)      # boundary residue (< CHUNK_AVG)

_MIN_LANES = 8                  # pow2 lane floor
_MAX_LANES = 32                 # lane cap: 3 programs total

CHUNK_OID_PREFIX = "chunk."


def device_dedup_enabled() -> bool:
    """Device chunking defaults to on where device EC offload is on
    (a real accelerator backend, or the CEPH_TPU_EC_OFFLOAD test
    override); CEPH_TPU_DEDUP_OFFLOAD=1/0 forces it independently —
    the same gate shape as the digest and compression planes."""
    v = os.environ.get("CEPH_TPU_DEDUP_OFFLOAD")
    if v is not None:
        return v not in ("0", "false", "no")
    from ..ec.batcher import device_offload_enabled
    return device_offload_enabled()


def _pow2_lanes(n: int) -> int:
    return 1 << max(int(n) - 1, _MIN_LANES - 1).bit_length()


# -- fingerprint / chunk-oid helpers (shared with scrub) -------------------


def fingerprint(crc: int, size: int) -> str:
    return "%08x-%x" % (crc & 0xFFFFFFFF, size)


def chunk_oid(fp: str) -> str:
    return CHUNK_OID_PREFIX + fp


def parse_chunk_oid(oid: str) -> tuple[int, int] | None:
    """(crc32, size) when ``oid`` is a content-addressed chunk oid,
    else None — the deep scrub uses this to verify stored bytes
    against the address they claim."""
    if not oid.startswith(CHUNK_OID_PREFIX):
        return None
    body = oid[len(CHUNK_OID_PREFIX):]
    crc_s, sep, size_s = body.partition("-")
    if not sep or len(crc_s) != 8:
        return None
    try:
        return int(crc_s, 16), int(size_s, 16)
    except ValueError:
        return None


# -- host reference (and the device kernel's parity oracle) ----------------


def candidate_mask_host(data) -> np.ndarray:
    """Boundary-candidate mask for one whole blob: mask[i] is True
    iff the 8-byte window ending at i (zero-padded off the front,
    exactly like the first device segment's staged margin) hits the
    boundary residue.  Pure numpy — this IS the host fallback's mask,
    and the device kernel below is this function transcribed to jax
    over fixed-geometry segments."""
    a = np.frombuffer(bytes(data), np.uint8)
    n = a.size
    if n == 0:
        return np.zeros(0, bool)
    p = np.zeros(n + MARGIN, np.uint8)
    p[MARGIN:] = a
    b = p.astype(np.uint32)
    i = np.arange(n, dtype=np.int64)
    w = [b[i + t + 1] for t in range(8)]
    g1 = w[0] | (w[1] << np.uint32(8)) | (w[2] << np.uint32(16)) \
        | (w[3] << np.uint32(24))
    g2 = w[4] | (w[5] << np.uint32(8)) | (w[6] << np.uint32(16)) \
        | (w[7] << np.uint32(24))
    mix = (g1 * _MIX1) ^ (g2 * _MIX2)
    return (mix & np.uint32(CHUNK_AVG - 1)) == _MAGIC


def _mask_lanes_host(stage: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """The staged-lane form of `candidate_mask_host`: identical
    arithmetic over a [lanes, MARGIN+SEG] stage — the per-dispatch
    host fallback, bit-identical to the device kernel."""
    idx = np.arange(SEG, dtype=np.int64)
    b = stage.astype(np.uint32)
    w = [b[:, idx + t + 1] for t in range(8)]
    g1 = w[0] | (w[1] << np.uint32(8)) | (w[2] << np.uint32(16)) \
        | (w[3] << np.uint32(24))
    g2 = w[4] | (w[5] << np.uint32(8)) | (w[6] << np.uint32(16)) \
        | (w[7] << np.uint32(24))
    mix = (g1 * _MIX1) ^ (g2 * _MIX2)
    hit = (mix & np.uint32(CHUNK_AVG - 1)) == _MAGIC
    return hit & (idx[None, :] < lens.astype(np.int64)[:, None])


def resolve_cuts(mask: np.ndarray, n: int) -> list[int]:
    """Walk a candidate mask into interior cut offsets: the next cut
    is one past the first candidate position >= start+CHUNK_MIN-1,
    forced at start+CHUNK_MAX when none fires, and the tail is never
    cut below CHUNK_MIN.  Cheap sequential host work shared by both
    paths — parity of cuts reduces to parity of masks."""
    cuts: list[int] = []
    pos = np.flatnonzero(mask)
    start = 0
    while n - start > CHUNK_MIN:
        lo = start + CHUNK_MIN - 1
        hi = min(start + CHUNK_MAX - 1, n - 2)
        j = int(np.searchsorted(pos, lo))
        if j < pos.size and pos[j] <= hi:
            c = int(pos[j]) + 1
        elif start + CHUNK_MAX < n:
            c = start + CHUNK_MAX
        else:
            break
        cuts.append(c)
        start = c
    return cuts


def chunk_host(data) -> list[int]:
    """Interior cut offsets for one blob — the host fallback AND the
    device path's parity oracle."""
    return resolve_cuts(candidate_mask_host(data), len(data))


def split(data: bytes, cuts: list[int]) -> list[bytes]:
    bounds = [0] + list(cuts) + [len(data)]
    return [bytes(data[bounds[i]:bounds[i + 1]])
            for i in range(len(bounds) - 1)]


# -- device kernel ---------------------------------------------------------


@functools.lru_cache(maxsize=8)
def _kernel(lanes: int):
    """One jitted boundary-candidate program per lane bucket (width is
    fixed at MARGIN+SEG): the exact arithmetic of
    `candidate_mask_host` over staged segments."""
    import jax
    import jax.numpy as jnp

    def run(data, lens):
        idx = jnp.arange(SEG, dtype=jnp.int32)
        b = data.astype(jnp.uint32)
        w = [b[:, idx + jnp.int32(t + 1)] for t in range(8)]
        g1 = w[0] | (w[1] << jnp.uint32(8)) \
            | (w[2] << jnp.uint32(16)) | (w[3] << jnp.uint32(24))
        g2 = w[4] | (w[5] << jnp.uint32(8)) \
            | (w[6] << jnp.uint32(16)) | (w[7] << jnp.uint32(24))
        mix = (g1 * jnp.uint32(_MIX1)) ^ (g2 * jnp.uint32(_MIX2))
        hit = (mix & jnp.uint32(CHUNK_AVG - 1)) == jnp.uint32(_MAGIC)
        return hit & (idx[None, :] < lens[:, None])

    return jax.jit(run)


def _segments(blobs) -> tuple[list[tuple[int, np.ndarray, np.ndarray]],
                              list[int]]:
    """(segments, blob lengths): each segment is (blob index, margin
    bytes, body bytes) with the margin the 8 bytes preceding the body
    in ITS blob (empty for a blob's first segment — the kernel's
    zero-filled margin is the host mask's zero front-pad)."""
    segs: list[tuple[int, np.ndarray, np.ndarray]] = []
    ns: list[int] = []
    for bi, blob in enumerate(blobs):
        a = np.frombuffer(bytes(blob), np.uint8)
        ns.append(a.size)
        for off in range(0, a.size, SEG):
            segs.append((bi, a[max(0, off - MARGIN):off],
                         a[off:off + SEG]))
    return segs, ns


def _stage_segments(segs, lanes: int, stage: np.ndarray) -> np.ndarray:
    lens = np.zeros(lanes, np.int32)
    for i, (_bi, margin, body) in enumerate(segs):
        stage[i, :MARGIN] = 0
        if margin.size:
            stage[i, MARGIN - margin.size:MARGIN] = margin
        stage[i, MARGIN:MARGIN + body.size] = body
        lens[i] = body.size
    return lens


async def boundary_batch(blobs, chip: int | None = None,
                         klass: str = K_BACKGROUND
                         ) -> tuple[list[list[int]], str]:
    """Cut lists for every blob, the candidate masks computed in
    background-class device dispatches on the caller's affinity chip;
    returns (cuts per blob, path).  Any degradation (offload
    disabled, chip lost, queue full, mid-dispatch failure — which
    poisons THIS chip) lands on the numpy reference, which computes
    the identical masks."""
    blobs = list(blobs)
    if not blobs:
        return [], "host"
    rt = DeviceRuntime.get()
    target = rt.route(chip)
    if target is None or not target.available \
            or not device_dedup_enabled():
        return [chunk_host(b) for b in blobs], "host"
    segs, ns = _segments(blobs)
    if not segs:
        return [[] for _ in blobs], "host"
    masks: list[np.ndarray | None] = [None] * len(segs)
    path = "device"
    width = MARGIN + SEG
    for lo in range(0, len(segs), _MAX_LANES):
        segs_c = segs[lo:lo + _MAX_LANES]
        lanes = min(_pow2_lanes(len(segs_c)), _MAX_LANES)
        total = sum(body.size for _bi, _m, body in segs_c)
        ticket = target.open_ticket(klass, lanes, total)
        try:
            await target.admit(ticket)
        except DeviceBusy:
            st = np.zeros((len(segs_c), width), np.uint8)
            lens = _stage_segments(segs_c, len(segs_c), st)
            m = _mask_lanes_host(st, lens)
            for i in range(len(segs_c)):
                masks[lo + i] = m[i]
            target.host_fallbacks += 1
            path = "host"
            continue
        stage = target.pool.lease((lanes, width), np.uint8)
        try:
            import jax.numpy as jnp
            lens = _stage_segments(segs_c, lanes, stage)
            target.launch(ticket)       # injected-fault hook
            m = np.asarray(_kernel(lanes)(
                target.place(jnp.asarray(stage)),
                target.place(jnp.asarray(lens))))
            target.note_program("cdc", (lanes, width))
            target.finish(ticket, ok=True)
            target.note_staging(total // 4, (lanes * width) // 4)
            for i in range(len(segs_c)):
                masks[lo + i] = m[i]
        except Exception as e:
            # device loss mid-chunk: poison THIS chip (per-chip
            # DEVICE_FALLBACK + probe heal), mask the rest on host
            target.finish(ticket, ok=False, error=e)
            target.poison(e)
            for i, seg in enumerate(segs[lo:]):
                st = np.zeros((1, width), np.uint8)
                lens = _stage_segments([seg], 1, st)
                masks[lo + i] = _mask_lanes_host(st, lens)[0]
            target.host_fallbacks += 1
            path = "host"
            break
        finally:
            target.pool.release(stage)
    cuts: list[list[int]] = []
    si = 0
    for n in ns:
        parts: list[np.ndarray] = []
        rem = n
        while rem > 0:
            body_len = min(SEG, rem)
            parts.append(masks[si][:body_len])
            si += 1
            rem -= body_len
        mask = (np.concatenate(parts) if parts
                else np.zeros(0, bool))
        cuts.append(resolve_cuts(mask, n))
    return cuts, path


async def fingerprint_batch(chunks, chip: int | None = None,
                            klass: str = K_BACKGROUND
                            ) -> tuple[list[str], str]:
    """Content fingerprints for a chunk batch through the digest
    plane's CRC lanes (one dispatch; host zlib.crc32 fallback):
    ``"%08x-%x" % (crc32, len)`` — the chunk store's address space.
    Chip-labeled fingerprint gauges account the device path."""
    from ..device import digest
    chunks = list(chunks)
    if not chunks:
        return [], "host"
    crcs, path = await digest.crc32_batch(chunks, chip=chip,
                                          klass=klass)
    if path == "device":
        rt = DeviceRuntime.get()
        target = rt.route(chip)
        if target is not None:
            target.note_fingerprint(
                len(chunks), sum(len(c) for c in chunks))
    return [fingerprint(c, len(b))
            for c, b in zip(crcs, chunks)], path
