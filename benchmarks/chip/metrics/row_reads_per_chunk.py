"""Pool layer: host reads of a session's token row (``pool.read_row``
spans: page ids up, one bank gather, the pages down) per decode chunk
(``pool.decode_chunk`` spans), both counted by start inside the window."""

import hostspans


def read(run):
    reads = hostspans.events("pool.read_row")
    chunks = hostspans.in_window(hostspans.events("pool.decode_chunk"), run)
    if not reads or not chunks:
        return None
    return hostspans.in_window(reads, run) / chunks
