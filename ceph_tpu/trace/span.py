"""Host spans of the program on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``rados.<name>``: while a profiler session runs (``jax.profiler.
start_trace``, which the benchmark's ``--trace 1`` does) the span lands
in the profiler's own trace file, on the clock of the device events,
with its keyword arguments as the event's stats.  With no session it is
an inactive TraceMe (about half a microsecond).  There is no switch of
its own: tracing is on when a profiler is.

One thread runs every coroutine of a cluster, so a span held across an
``await`` would nest with other requests' spans and self times would
subtract the wrong children.  **A span wraps a synchronous section
only**: no ``await``, ``yield`` or ``async with`` inside its body
(tests/test_spans.py walks the AST).  Arguments are integers the caller
already holds.

``SPANS`` is the one registry: name -> (layer of PERF.md section 3, what
the span covers).  A name that is not in it raises at the emit site, and
tests/test_spans.py holds the table and the emit sites to each other.
Never name a span ``bench.*``: the benchmark finds its window by that
prefix.

``scope(name)`` is the device's side of the same idea: JAX's name scope
``rados.<name>`` around a stage of a jitted program, from the second
registry ``SCOPES``.  It is metadata of the instructions traced inside
it and nothing else (no operation is added: tests/test_scopes.py and the
crush tests compare the lowered modules), so it is always on.  Scopes
nest, and an instruction's path is the list of registered scopes around
it, outermost first; the device trace carries it and
benchmark/harness/device_scopes.py reads it.  No name scope is written
under ceph_tpu/ but through it.
"""

from __future__ import annotations

import gc

import jax
from jax.profiler import TraceAnnotation

PREFIX = "rados."

CLIENT = "client"
HOST = "host path"
BATCHER = "batcher and device runtime"
MAPPING = "bulk mapping"
KERNELS = "kernels"

SPANS: dict[str, tuple[str, str]] = {
    # -- the served write path, by what the loop's one thread is doing ----
    "client.calc_target": (CLIENT, "object -> pg -> acting primary on the "
                           "client's map (host CRUSH the first time a "
                           "pg is asked for in an epoch)"),
    "client.target_hit": (CLIENT, "mark: a pg's target was answered from "
                          "the client's per-epoch table, no CRUSH descent"),
    "client.submit": (CLIENT, "submit_op: tid, tracked op, first send"),
    "client.send_op": (CLIENT, "_send_op: target, build MOSDOp, queue it"),
    "client.handle_reply": (CLIENT, "_handle_reply: retire, resolve the "
                            "caller's future"),
    "client.resend": (CLIENT, "mark: the resend ticker sent an op again; "
                      "age_us since its first send, rto_us the deadline "
                      "that passed (at the constants' floor: a lost "
                      "frame; above it: the estimator fell short; 0: a "
                      "backoff's release)"),
    "msgr.encode": (HOST, "Connection.send: message -> wire bytes"),
    "msgr.write": (HOST, "frame header, crc and payload handed to the "
                   "transport; bytes of payload"),
    "msgr.recv": (HOST, "_Wire.buffer_updated: what one recv_into "
                  "delivered cut into frames (the recv_into itself runs "
                  "inside asyncio, before the span); bytes arrived, "
                  "direct of them landed in a large frame's own buffer"),
    "msgr.read_decode": (HOST, "a message frame's crc check, then wire "
                         "bytes -> message; bytes of payload"),
    "msgr.dispatch": (HOST, "a dispatcher's synchronous ms_dispatch; the "
                      "handlers' own spans nest inside"),
    "osd.dequeue": (HOST, "op queue: pick, bookkeeping, hand-over to the "
                    "handler (nests inside)"),
    "osd.handle_op": (HOST, "OSD._handle_op: checks, dup lookup, spawn "
                      "the backend's op"),
    "osd.ec.op": (HOST, "ECPGBackend._do_op: a write's last stretch, "
                  "after submit_write: reply, counters, retire"),
    "osd.ec.submit": (HOST, "submit_write after the encode: shard "
                      "transactions, local apply, sub-op encode and send"),
    "osd.ec.sub_write": (HOST, "handle_sub_write: decode, log, apply, "
                         "reply"),
    "osd.ec.sub_reply": (HOST, "handle_sub_write_reply"),
    "osd.ec.subop_timeout": (HOST, "mark: a write stopped waiting for "
                             "sub-op acks at osd_ec_subop_timeout"),
    "osd.ec.read": (HOST, "read_object_attrs: the primary's read plan "
                    "(members, minimum_to_decode) and, after each "
                    "round of sub-reads, the version vote"),
    "osd.ec.sub_read": (HOST, "handle_sub_read: the local shard off "
                        "the store, the reply queued; bytes of shard"),
    "osd.ec.sub_read_reply": (HOST, "handle_sub_read_reply; bytes of "
                              "shard received"),
    "osd.ec.reconstruct": (HOST, "mark: a client read rebuilt wanted "
                           "positions from the survivors; erased "
                           "positions, bytes of the object"),
    "osd.ec.delta_plan": (HOST, "_try_delta_write: whether a partial "
                          "write may take the parity-delta path, its "
                          "parts per data chunk, the merged column "
                          "intervals; bytes overwritten"),
    "osd.ec.delta_xor": (HOST, "_try_delta_write after the ranged "
                         "reads: old xor new, the touched data chunks' "
                         "crcs, the rows handed to delta_async; bytes "
                         "overwritten"),
    "osd.ec.delta_apply": (HOST, "_try_delta_write after the device: "
                           "old parity xor delta and the parity crcs "
                           "(shards = m), then the shard transactions "
                           "(shards = all)"),
    "osd.ec.delta_write": (HOST, "mark: the parity-delta path committed "
                           "a partial write; bytes overwritten, data "
                           "chunks touched, column intervals"),
    "osd.ec.rmw_fallback": (HOST, "mark: a partial write fell to the "
                            "whole-object read-modify-write; why, an "
                            "index into ecbackend.RMW_FALLBACK_WHY"),
    "osd.advance_pgs": (HOST, "OSD._advance_pgs: one new map epoch"),
    "heartbeat": (HOST, "one tick of OSD._heartbeat_loop: watchdogs, "
                  "reports, pings, failure reports"),
    "ec.prepare": (BATCHER, "encode_async: payload bytes -> k rows of "
                   "words"),
    "ec.stage": (BATCHER, "_encode_shard: lease the ladder's buffers and "
                 "pack the items; words packed, padded words staged"),
    "ec.dispatch": (BATCHER, "one ladder segment's blocking upload + "
                    "kernel + readback; bytes_in, bytes_out"),
    "ec.deliver": (BATCHER, "parity slices to the waiting ops; items"),
    "ec.collect": (BATCHER, "encode_async: parity rows -> shard bytes"),
    "ec.delta_prepare": (BATCHER, "delta_async: the touched chunks' "
                         "deltas into a k x words array, zero rows for "
                         "the rest"),
    "ec.delta_collect": (BATCHER, "delta_async: parity-delta rows -> "
                         "bytes"),
    "ec.decode_prepare": (BATCHER, "decode_async: the reconstruction "
                          "matrix (cached), k survivors stacked into "
                          "rows of words"),
    "ec.decode_collect": (BATCHER, "decode_async: rebuilt rows -> chunk "
                          "bytes; decode_concat_async: the k data "
                          "chunks joined into the object"),
    "store.apply": (HOST, "MemStore.queue_transactions; txns applied"),
    "gc": (HOST, "one garbage collection, start to stop; generation"),
    "op.retired": (HOST, "mark: an op left its tracker; stage waits in "
                   "us from the stamps it carried (queue_us, "
                   "ec_batch_us, subop_us; a read's sub_read_us, "
                   "decode_us; a partial write's delta_lock_us, "
                   "delta_read_us; total_us), client=1 for the "
                   "client's own op"),
    "rbd.write": (CLIENT, "Image.write: a block write cut into object "
                  "extents, grouped per object; bytes"),
    "rbd.read": (CLIENT, "Image.read: a block read cut into object "
                 "extents, and the extents' bytes joined; bytes"),
    # -- the bulk remap ----------------------------------------------------
    "crush.build": (MAPPING, "OSDMapMapping._build, whole; pools"),
    "crush.upload": (MAPPING, "weights and state vectors to the device; "
                     "bytes"),
    "crush.launch": (MAPPING, "the call into a compiled program (returns "
                     "before the device ends); on the pool program lanes, "
                     "and pallas_lanes whose descent was built in Pallas "
                     "(0 on the call that first traces it), steps: the "
                     "rule's choose steps that ran on the device, over "
                     "its blocks, blocks: its TAKE/EMIT blocks"),
    "crush.lanes": (MAPPING, "mark: one whole-pool pass counted, after "
                    "its one blocking read: lanes of the dense pass, "
                    "tail_lanes of them left unplaced by the first "
                    "optimistic rounds and replayed on the compacted "
                    "tail, resolve_lanes flagged to the resolve chain, "
                    "retry_lanes an indep rule's first full-width round "
                    "left with an undefined slot (0 for firstn), "
                    "indep_tail_lanes the take-lanes of an indep rule's "
                    "choose steps that ran their later rounds on the "
                    "step's compacted tail (0 for firstn and without a "
                    "tail), none_slots of the up table that end "
                    "ITEM_NONE; of resolve_lanes, fast_lanes the dense "
                    "pass left short (every draw proven, its rounds "
                    "run out: stage F's), fast_settled of them stage F "
                    "settled in the fast form, b_lanes those that "
                    "reached stage B; blocks: the rule's TAKE/EMIT "
                    "blocks (tail_lanes and indep_tail_lanes count a "
                    "lane once per block that seated it)"),
    "crush.wait": (MAPPING, "the first blocking read: the host waits, "
                   "the device works"),
    "crush.readback": (MAPPING, "up/acting tables device -> host; bytes"),
    "crush.tables": (MAPPING, "numpy on the host: exceptions, primaries, "
                     "PoolMapping"),
}

# stages of the jitted programs, by what a change to them has to price
SCOPES: dict[str, tuple[str, str]] = {
    "crush.seeds": (KERNELS, "pg number -> placement seed x (the dense "
                    "pass's chunk, a tail's lanes, the resolve chain's)"),
    "crush.first": (KERNELS, "the optimistic rounds that run over all of "
                    "a chunk's lanes: firstn's first round of each "
                    "replica (every round where the pass has no tail), "
                    "an indep step's round 0 (and 1, 2 without a tail)"),
    "crush.tail.move": (KERNELS, "lanes into and out of a compacted "
                        "tail: rowcompact, rowgather, rowexpand, and the "
                        "concatenations and slices around them"),
    "crush.tail.rounds": (KERNELS, "the retry rounds at a tail's width"),
    "crush.step": (KERNELS, "between rounds and steps: a later step's "
                   "takes and windows, the working vector's assembly, "
                   "the start vector, the per-chunk counts"),
    "crush.descend": (KERNELS, "one descent bucket -> item of a type, "
                      "the Pallas kernel or XLA's exact form, wherever "
                      "it runs"),
    "crush.is_out": (KERNELS, "the reweight test of a drawn device"),
    "crush.post": (KERNELS, "raw rows -> up rows and primaries"),
    "crush.emit": (KERNELS, "a rule of several TAKE/EMIT blocks: the "
                   "blocks' stacked lanes apart, each block's rows "
                   "shifted behind the entries of the blocks before it, "
                   "EMIT's cap, the kinds joined"),
    "crush.resolve.compact": (KERNELS, "flagged lanes -> the resolve "
                              "chain's K1 slots"),
    "crush.resolve.fast": (KERNELS, "stage F: the short lanes through "
                           "the rule's full retry loops in the fast "
                           "form, KF slots"),
    "crush.resolve.a": (KERNELS, "stage A: the exact attempt structure "
                        "over the uncertain lanes and what stage F "
                        "could not prove, KA slots (all K1 without F)"),
    "crush.resolve.b": (KERNELS, "stage B: what A left, through the "
                        "full retry loops, K2 slots"),
    "crush.resolve.c": (KERNELS, "stage C: top-3-ambiguous dust, the "
                        "all-integer draw, K3 slots"),
    "crush.settle.draw": (KERNELS, "inside a stage: seeds and the "
                          "rule's draws for the stage's lanes"),
    "crush.settle.post": (KERNELS, "inside a stage: raw -> up, primary "
                          "for the stage's lanes"),
    "crush.settle.scatter": (KERNELS, "inside a stage: the three "
                             "tables' rows put back by XLA's scatter"),
    "crush.resolve.counts": (KERNELS, "the up table's NONE slots "
                             "counted, the pass's counters joined"),
    "ec.encode": (KERNELS, "the fused encoder's program: pad, the "
                  "Pallas kernel ec_encode_fused, slice"),
}

_FULL = {name: PREFIX + name for name in SPANS}
_FULL_SCOPE = {name: PREFIX + name for name in SCOPES}


def span(name: str, **args) -> TraceAnnotation:
    """``with span("osd.handle_op"): ...`` around a synchronous
    section."""
    return TraceAnnotation(_FULL[name], **args)


def scope(name: str):
    """``with scope("crush.first"): ...`` around a stage while a jitted
    program is traced (also a decorator)."""
    return jax.named_scope(_FULL_SCOPE[name])


def mark(name: str, **args) -> None:
    """A counted event: a span entered and left at once."""
    with TraceAnnotation(_FULL[name], **args):
        pass


_gc_open: list[TraceAnnotation] = []


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        s = span("gc", generation=info["generation"])
        s.__enter__()
        _gc_open.append(s)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


def watch_gc() -> None:
    """Span every garbage collection from here on (idempotent; called
    where a daemon starts, never at import)."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
