"""Growth of the durable snapshot, in bytes per 1,000 events: the
program's ``snapshot_bytes`` counter on the window's last snapshot less
its first, over the events between them.  A snapshot of a fixed size reads
0.  Unlike a mean size, it does not grow when a faster program gets
further into the stream within the window."""
import program_spans as PS


def read(r):
    sizes = PS.counts(r, "snapshot_bytes")
    if not sizes or len(sizes) < 2:
        return None
    (n0, b0), (n1, b1) = sizes[0], sizes[-1]
    return (b1 - b0) * 1e3 / (n1 - n0)
