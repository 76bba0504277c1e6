"""Sum of the program's ``ChunkStats.refresh_wall_s`` over the window's
chunks, in ms per 1,000 events."""


def read(r):
    if not r.events or not r.chunks:
        return None
    return sum(c.refresh_wall_s for c in r.chunks) * 1e6 / r.events
