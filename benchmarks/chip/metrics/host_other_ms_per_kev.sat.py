"""The benchmark's push spans less chunk wall and refresh wall, in ms per
1,000 events: WAL, snapshots, guard, admission and slicing together."""


def read(r):
    if not r.events or not r.chunks:
        return None
    push = sum(b - a for a, b in r.push_spans)
    inner = sum(c.wall_s + c.refresh_wall_s for c in r.chunks)
    return (push - inner) * 1e6 / r.events
