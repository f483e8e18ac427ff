"""The least work a cell's shapes demand of the device, counted from the
cell's own sizes and never from a kernel's name or arguments: a later PR
that replaces a kernel is still bounded by the same count.

Both counts are bytes through HBM; a roofline reader divides them by the
chip's HBM peak to get the least time the window's work could take."""


def ec_encode_bytes(payload_bytes: int, k: int, m: int) -> int:
    """Reed-Solomon encode of ``payload_bytes`` of client data: the k data
    chunks are read once and the m parity chunks written once, so
    payload * (k + m) / k bytes cross HBM at the least."""
    return payload_bytes * (k + m) // k


def crush_map_bytes(pg_num: int, size: int, remaps: int) -> int:
    """A full-pool remap writes the up table and the acting table, each
    pg_num * size int32 entries, at the least."""
    return pg_num * size * 4 * 2 * remaps
