"""Pool layer: page grants of the slot allocator (``alloc.grant`` spans:
one compiled compare, drain and claim on the page file, for a seating or
a bank's top-up) per decode chunk (``pool.decode_chunk`` spans), both
counted by start inside the window."""

import hostspans


def read(run):
    grants = hostspans.events("alloc.grant")
    chunks = hostspans.in_window(hostspans.events("pool.decode_chunk"), run)
    if not grants or not chunks:
        return None
    return hostspans.in_window(grants, run) / chunks
