"""Shared erasure-code behavior: padding, chunk mapping, read planning.

Re-derivation of the reference base class (src/erasure-code/
ErasureCode.cc): encode_prepare zero-pads the object tail so every data
chunk is exactly get_chunk_size(len) bytes (:150-185), encode trims to
want_to_encode (:187-203), _decode passes surviving chunks through and
fills the rest via decode_chunks (:205-241), minimum_to_decode returns
want_to_read when fully available else the first k available (:102-119),
and the "mapping" profile string (D=data) permutes chunk positions
(:260-279).
"""

from __future__ import annotations

from typing import Mapping, Sequence

from ..trace.span import span
from .interface import ErasureCodeInterface, ErasureCodeProfile


class ErasureCode(ErasureCodeInterface):
    """Base class: subclasses set self.k / self.m in init() and implement
    encode_chunks / decode_chunks and get_chunk_size."""

    def __init__(self):
        self.k = 0
        self.m = 0
        self.chunk_mapping: list[int] = []
        self._profile: ErasureCodeProfile = {}

    # -- profile helpers ---------------------------------------------------

    @staticmethod
    def _to_int(profile: dict, name: str, default: int) -> int:
        v = profile.get(name)
        if v is None or v == "":
            profile[name] = str(default)
            return default
        try:
            return int(v)
        except (TypeError, ValueError):
            raise ValueError("profile %s=%r is not an integer" % (name, v))

    @staticmethod
    def _to_bool(profile: dict, name: str, default: str) -> bool:
        v = profile.get(name)
        if v is None or v == "":
            profile[name] = default
            v = default
        return str(v) in ("yes", "true", "True", "1")

    def _parse_mapping(self, profile: dict) -> None:
        mapping = profile.get("mapping")
        if not mapping:
            return
        data_pos = [i for i, c in enumerate(mapping) if c == "D"]
        coding_pos = [i for i, c in enumerate(mapping) if c != "D"]
        self.chunk_mapping = data_pos + coding_pos

    def sanity_check_k_m(self) -> None:
        if self.k < 2:
            raise ValueError("k=%d must be >= 2" % self.k)
        if self.m < 1:
            raise ValueError("m=%d must be >= 1" % self.m)

    # -- placement ---------------------------------------------------------

    def _rule_steps(self) -> list[tuple[str, str, int]]:
        """(op, type name, n) of the rule's choose steps, "choose" or
        "chooseleaf", all indep."""
        return [("chooseleaf",
                 self._profile.get("crush-failure-domain") or "host", 0)]

    def _rule_prologue(self) -> list[tuple[int, int, int]]:
        """The set_* steps that open the rule."""
        return []

    def create_rule(self, name: str, crush) -> int:
        """`take <crush-root>; chooseleaf indep 0 type
        <crush-failure-domain>; emit` (ErasureCode::create_rule's
        add_simple_rule(..., "indep", TYPE_ERASURE)); subclasses give
        other steps.  A name that no type or bucket of the map carries
        is an error, never a default."""
        from ..models.crushmap import (CHOOSE_INDEP, CHOOSELEAF_INDEP,
                                       EMIT, TAKE)
        for rule in crush.rules.values():
            if rule.name == name:
                return rule.id
        root = self._profile.get("crush-root") or "default"
        roots = [b.id for b in crush.buckets.values() if b.name == root]
        if not roots:
            raise ValueError("crush-root %r: the map has no bucket of "
                             "that name" % root)
        type_ids = {tname: tid for tid, tname in crush.types.items()}
        steps = self._rule_prologue() + [(TAKE, roots[0], 0)]
        for op, tname, n in self._rule_steps():
            if tname not in type_ids:
                raise ValueError("the map has no type %r" % tname)
            steps.append((CHOOSE_INDEP if op == "choose"
                          else CHOOSELEAF_INDEP, n, type_ids[tname]))
        steps.append((EMIT, 0, 0))
        return crush.add_rule(steps, name=name).id

    # -- interface basics --------------------------------------------------

    def get_profile(self) -> ErasureCodeProfile:
        return self._profile

    def get_chunk_count(self) -> int:
        return self.k + self.m

    def get_data_chunk_count(self) -> int:
        return self.k

    def get_chunk_mapping(self) -> Sequence[int]:
        return self.chunk_mapping

    def chunk_index(self, i: int) -> int:
        return self.chunk_mapping[i] if i < len(self.chunk_mapping) else i

    def _to_logical(self, chunks: Mapping[int, bytes]) -> dict[int, bytes]:
        """Translate physical chunk ids back to generator-row (logical)
        ids so codec math is mapping-transparent."""
        if not self.chunk_mapping:
            return dict(chunks)
        inv = {p: l for l, p in enumerate(self.chunk_mapping)}
        return {inv.get(i, i): v for i, v in chunks.items()}

    def _from_logical(self, chunks: dict[int, bytes]) -> dict[int, bytes]:
        if not self.chunk_mapping:
            return chunks
        return {self.chunk_index(i): v for i, v in chunks.items()}

    def _logical_ids(self, ids) -> set[int]:
        if not self.chunk_mapping:
            return set(ids)
        inv = {p: l for l, p in enumerate(self.chunk_mapping)}
        return {inv.get(i, i) for i in ids}

    # -- object-level encode/decode ---------------------------------------

    def encode_prepare(self, data: bytes) -> dict[int, bytes]:
        """Split into k chunks of get_chunk_size(len), zero-padding the
        tail chunks."""
        k = self.get_data_chunk_count()
        blocksize = self.get_chunk_size(len(data))
        if blocksize == 0:  # zero-length object: k+m empty chunks
            return {self.chunk_index(i): b"" for i in range(k)}
        chunks: dict[int, bytes] = {}
        full = len(data) // blocksize
        for i in range(full):
            chunks[self.chunk_index(i)] = data[i * blocksize:(i + 1) * blocksize]
        if full < k:
            rest = data[full * blocksize:]
            chunks[self.chunk_index(full)] = rest.ljust(blocksize, b"\0")
            zero = bytes(blocksize)
            for i in range(full + 1, k):
                chunks[self.chunk_index(i)] = zero
        return chunks

    def encode(self, want_to_encode: set[int], data: bytes) -> dict[int, bytes]:
        if len(data) == 0:
            return {i: b"" for i in want_to_encode}
        prepared = self.encode_prepare(data)
        encoded = self.encode_chunks(prepared)
        return {i: encoded[i] for i in want_to_encode}

    # -- device offload (TPU path) ------------------------------------

    def _device_matrix(self):
        """(matrix, w) when this codec is a plain GF(2^w) matrix code
        whose encode is a region matmul — the shape the device batcher
        offloads.  None keeps the sync host path for the base
        encode/decode routing (layered/shingled codes override the
        async entry points instead and dispatch their own step
        matrices through `_device_matmul`)."""
        return None

    def device_families(self) -> list[tuple]:
        """The (matrix, w) program families this codec's device
        dispatches ride — what `warmup_ec` should pre-compile at OSD
        boot so the first flush/repair after boot hits the compile
        cache.  Plain matrix codecs have exactly their coding matrix;
        layered/shingled codecs override with their per-step matrices
        (LRC layers, SHEC single-failure decode, CLAY MDS rows)."""
        dm = self._device_matrix()
        return [dm] if dm is not None else []

    async def _device_matmul(self, matrix, w: int, data,
                             klass: str | None = None,
                             on_ticket=None, chip: int | None = None,
                             tenant: str | None = None):
        """One batched GF(2^w) region matmul on the caller's affinity
        chip via the device batcher ([rows, k] x [k, n] words ->
        [rows, n]), or None when the device plane is unavailable
        (offload disabled / chip poisoned) so the caller takes its
        bit-identical host path.  Once admitted, DeviceBusy and
        mid-dispatch chip loss degrade INSIDE the batcher (host
        re-encode, futures retired exactly once), exactly like the RS
        flush path."""
        from ..device.runtime import DeviceRuntime, K_CLIENT_EC
        from .batcher import DeviceBatcher, device_offload_enabled
        if not device_offload_enabled() \
                or not DeviceRuntime.get().chip_available(chip):
            return None
        return await DeviceBatcher.get().encode(
            [list(r) for r in matrix], int(w), data,
            klass=klass or K_CLIENT_EC, on_ticket=on_ticket,
            chip=chip, tenant=tenant)

    @staticmethod
    def _word_dtype(w: int):
        import numpy as np
        return {8: np.uint8, 16: "<u2", 32: "<u4"}[w]

    async def encode_async(self, want_to_encode: set[int],
                           data: bytes, klass: str | None = None,
                           on_ticket=None, chip: int | None = None,
                           tenant: str | None = None
                           ) -> dict[int, bytes]:
        """encode() with the GF matmul batched onto the device across
        concurrent callers (ECBackend's hot call,
        src/osd/ECTransaction.cc:56 -> encode_chunks).  Falls back to
        the sync host path when offload is disabled, the codec has no
        plain matrix form, or the caller's mesh chip is in fallback.

        klass selects the device dispatch class (client-EC vs
        recovery-EC admission weights); chip is the caller's mesh
        affinity (OSDs pass their bound chip — a poisoned chip
        degrades only its own OSDs); on_ticket receives the flush's
        DispatchTicket for exact per-op attribution."""
        from ..device.runtime import DeviceRuntime, K_CLIENT_EC
        from .batcher import DeviceBatcher, device_offload_enabled
        dm = self._device_matrix()
        if dm is None or len(data) == 0 or not device_offload_enabled() \
                or not DeviceRuntime.get().chip_available(chip):
            return self.encode(want_to_encode, data)
        import numpy as np
        matrix, w = dm
        with span("ec.prepare"):
            prepared = self.encode_prepare(data)
            arr = np.stack([
                np.frombuffer(prepared[self.chunk_index(i)],
                              dtype=self._word_dtype(w))
                for i in range(self.get_data_chunk_count())])
        parity = await DeviceBatcher.get().encode(
            matrix, w, arr, klass=klass or K_CLIENT_EC,
            on_ticket=on_ticket, chip=chip, tenant=tenant)
        with span("ec.collect"):
            out = dict(prepared)
            for i in range(len(matrix)):
                out[self.chunk_index(
                    self.get_data_chunk_count() + i)] = \
                    parity[i].tobytes()
            return {i: out[i] for i in want_to_encode}

    def parity_delta(self, deltas: Mapping[int, bytes]
                     ) -> dict[int, bytes]:
        """Host parity updates for a partial overwrite (the
        XOR-delta formulation of arXiv:2108.02692): given
        ``delta_j = new_j XOR old_j`` for each touched data chunk j
        (logical/generator-row index; all values the same length),
        returns {parity row i: XOR-delta to apply to parity chunk i}:

            new_parity_i = old_parity_i XOR sum_j gfmul(M[i][j],
                                                        delta_j)

        Exact under GF linearity for any matrix codec.  This is the
        scalar numpy path — `delta_async` routes the same math through
        the device batcher and falls back here.

        Sub-word-aligned regions (w=16/32, length not a word
        multiple): the tail is zero-padded to the word boundary and
        the returned parity deltas carry the word-aligned length — a
        sub-word overwrite dirties its whole containing parity word
        (GF(2^w) products mix bits across the word), so callers must
        apply the delta over the word-aligned envelope of the region
        (the region's START must already be word-aligned; the OSD
        delta path floors/ceils its column intervals)."""
        dm = self._device_matrix()
        if dm is None:
            raise ValueError(
                "codec has no plain matrix form for parity deltas")
        import numpy as np

        from . import gf
        matrix, w = dm
        m = len(matrix)
        dtype = np.dtype(self._word_dtype(w))
        lengths = {len(d) for d in deltas.values()}
        if len(lengths) > 1:
            raise ValueError(
                "delta regions have differing lengths %s" % lengths)
        word = dtype.itemsize
        pad = (-(lengths.pop() if lengths else 0)) % word
        arrs = {int(j): np.frombuffer(
                    bytes(d) + b"\0" * pad if pad else d, dtype=dtype)
                for j, d in deltas.items()}
        n = next(iter(arrs.values())).shape[0] if arrs else 0
        out: dict[int, bytes] = {}
        for i in range(m):
            acc = np.zeros(n, dtype=dtype)
            for j, darr in arrs.items():
                c = int(matrix[i][j])
                if int(w) == 8:
                    gf.region_mad_u8(acc, darr, c)
                else:
                    gf.region_mad_words(acc, darr, c, int(w))
            out[i] = acc.tobytes()
        return out

    async def delta_async(self, deltas: Mapping[int, bytes],
                          klass: str | None = None,
                          on_ticket=None, chip: int | None = None,
                          tenant: str | None = None
                          ) -> dict[int, bytes]:
        """`parity_delta` with the GF products batched onto the device
        (the OSD partial-write hot call, osd/ecbackend.py
        `_try_delta_write`): concurrent small overwrites across
        PGs/objects aggregate their (coefficient column, delta words)
        products into one dispatch on the caller's affinity chip.

        The delta rides the codec's FULL coding matrix with zero rows
        for untouched data chunks — zero rows contribute nothing under
        GF linearity, so delta flushes share the encode streams and
        compiled bucket programs, and batch with ordinary full writes
        into the same device dispatch.  Sub-word-aligned regions on
        w=16/32 codecs are zero-padded to the word boundary and
        dispatch on device like any other delta (they used to fall
        back to host): the returned parity deltas carry the
        word-aligned length, identical to `parity_delta`'s host
        semantics, and callers apply them over the aligned envelope.
        Host fallback (offload off, chip poisoned) is `parity_delta`'s
        numpy path; DeviceBusy and mid-flush device loss degrade
        inside the batcher the same way encode flushes do.  `on_ticket`
        receives the flush's DispatchTicket (exact per-op
        `op_ec_device_dispatch` attribution); host-served deltas
        deliver none."""
        from ..device.runtime import DeviceRuntime, K_CLIENT_EC
        from .batcher import DeviceBatcher, device_offload_enabled
        if not deltas:
            return {}
        dm = self._device_matrix()
        if dm is None:
            raise ValueError(
                "codec has no plain matrix form for parity deltas")
        import numpy as np
        matrix, w = dm
        word = np.dtype(self._word_dtype(w)).itemsize
        lengths = {len(d) for d in deltas.values()}
        if len(lengths) != 1:
            raise ValueError(
                "delta regions have differing lengths %s" % lengths)
        nbytes = lengths.pop()
        if (nbytes == 0 or not device_offload_enabled()
                or not DeviceRuntime.get().chip_available(chip)):
            return self.parity_delta(deltas)
        with span("ec.delta_prepare"):
            pad = (-nbytes) % word
            k = self.get_data_chunk_count()
            arr = np.zeros((k, (nbytes + pad) // word),
                           dtype=self._word_dtype(w))
            for j, d in deltas.items():
                arr[int(j)] = np.frombuffer(
                    bytes(d) + b"\0" * pad if pad else d,
                    dtype=self._word_dtype(w))
        parity = await DeviceBatcher.get().encode(
            matrix, w, arr, klass=klass or K_CLIENT_EC,
            on_ticket=on_ticket, chip=chip, tenant=tenant)
        with span("ec.delta_collect"):
            return {i: parity[i].tobytes() for i in range(len(matrix))}

    async def decode_async(self, want_to_read: set[int],
                           chunks: Mapping[int, bytes],
                           klass: str | None = None,
                           on_ticket=None,
                           chip: int | None = None) -> dict[int, bytes]:
        """decode() with the reconstruction matmul batched onto the
        device (the ECBackend degraded-read/recovery call,
        src/osd/ECUtil.cc:12-121).  Reconstruction is an encode with
        the inverted-survivor matrix, so it shares the encode queue
        (and the caller's chip affinity)."""
        from ..device.runtime import DeviceRuntime, K_CLIENT_EC
        from .batcher import (DeviceBatcher, device_offload_enabled,
                              reconstruct_matrix)
        dm = self._device_matrix()
        if (dm is None or not device_offload_enabled()
                or not DeviceRuntime.get().chip_available(chip)
                or self.chunk_mapping
                or want_to_read <= set(chunks)
                or any(len(c) == 0 for c in chunks.values())):
            return self.decode(want_to_read, chunks)
        if len(chunks) < self.get_data_chunk_count():
            raise IOError(
                "cannot decode: %d chunks available, %d needed"
                % (len(chunks), self.get_data_chunk_count()))
        lengths = {len(c) for c in chunks.values()}
        if len(lengths) != 1:
            raise ValueError(
                "surviving chunks have differing sizes %s" % lengths)
        import numpy as np
        matrix, w = dm
        with span("ec.decode_prepare"):
            k = self.get_data_chunk_count()
            have = tuple(sorted(chunks))
            erased = tuple(i for i in sorted(want_to_read)
                           if i not in chunks)
            rows, chosen = reconstruct_matrix(k, w, matrix, erased,
                                              have)
            arr = np.stack([
                np.frombuffer(chunks[c], dtype=self._word_dtype(w))
                for c in chosen])
        words = await DeviceBatcher.get().encode(
            rows, w, arr, klass=klass or K_CLIENT_EC,
            on_ticket=on_ticket, chip=chip)
        with span("ec.decode_collect"):
            out = {}
            for j, e in enumerate(erased):
                out[e] = words[j].tobytes()
            for i in want_to_read:
                if i in chunks:
                    out[i] = bytes(chunks[i])
            return out

    async def decode_concat_async(self, chunks: Mapping[int, bytes],
                                  klass: str | None = None,
                                  on_ticket=None,
                                  chip: int | None = None) -> bytes:
        k = self.get_data_chunk_count()
        want = {self.chunk_index(i) for i in range(k)}
        decoded = await self.decode_async(want, chunks, klass=klass,
                                          on_ticket=on_ticket,
                                          chip=chip)
        with span("ec.decode_collect"):
            return b"".join(decoded[self.chunk_index(i)]
                            for i in range(k))

    # Locality-aware codes (LRC, SHEC) can repair from FEWER than k
    # chunks (a local group / shingle window); they clear this flag so
    # _decode skips the k-chunk floor while keeping the size check.
    REQUIRES_K_CHUNKS = True

    def _decode(
        self, want_to_read: set[int], chunks: Mapping[int, bytes],
    ) -> dict[int, bytes]:
        if want_to_read <= set(chunks):
            return {i: bytes(chunks[i]) for i in want_to_read}
        if self.REQUIRES_K_CHUNKS and \
                len(chunks) < self.get_data_chunk_count():
            raise IOError(
                "cannot decode: %d chunks available, %d needed"
                % (len(chunks), self.get_data_chunk_count()))
        lengths = {len(c) for c in chunks.values()}
        if len(lengths) != 1:
            raise ValueError("surviving chunks have differing sizes %s" % lengths)
        decoded = self.decode_chunks(want_to_read, chunks)
        out = {}
        for i in want_to_read:
            out[i] = bytes(chunks[i]) if i in chunks else decoded[i]
        return out

    def decode(
        self, want_to_read: set[int], chunks: Mapping[int, bytes],
        chunk_size: int = 0,
    ) -> dict[int, bytes]:
        return self._decode(want_to_read, chunks)

    def decode_concat(self, chunks: Mapping[int, bytes]) -> bytes:
        k = self.get_data_chunk_count()
        want = {self.chunk_index(i) for i in range(k)}
        decoded = self._decode(want, chunks)
        return b"".join(decoded[self.chunk_index(i)] for i in range(k))

    # -- read planning -----------------------------------------------------

    def _minimum_to_decode(
        self, want_to_read: set[int], available: set[int],
    ) -> set[int]:
        if want_to_read <= available:
            return set(want_to_read)
        k = self.get_data_chunk_count()
        if len(available) < k:
            raise IOError("cannot decode: only %d of %d chunks available"
                          % (len(available), k))
        return set(sorted(available)[:k])

    def minimum_to_decode(
        self, want_to_read: set[int], available: set[int],
    ) -> dict[int, list[tuple[int, int]]]:
        ids = self._minimum_to_decode(want_to_read, available)
        whole = [(0, self.get_sub_chunk_count())]
        return {i: list(whole) for i in ids}

    def minimum_to_decode_with_cost(
        self, want_to_read: set[int], available: Mapping[int, int],
    ) -> set[int]:
        return self._minimum_to_decode(want_to_read, set(available))
