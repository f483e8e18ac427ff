"""What both drivers read from the program's own counters."""


def host_fallbacks(rt, batcher=None) -> int:
    """Device work that a host fallback served, by any of the program's
    counts: 0 on a sound run.  `batcher` is the EC batcher where the cell
    has EC traffic (it lives on an event loop; the crush cell has none)."""
    return (rt.host_fallbacks + rt.fallback_count
            + (batcher.host_flushes if batcher is not None else 0)
            + sum(1 for ch in rt.chips if ch.fallback))
