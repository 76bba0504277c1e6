"""Sum of the program's ``ChunkStats.wall_s`` over the window's chunks,
in ms per 1,000 events: dispatch to the stats transfer, and the match
decode where matches are emitted."""


def read(r):
    if not r.events or not r.chunks:
        return None
    return sum(c.wall_s for c in r.chunks) * 1e6 / r.events
