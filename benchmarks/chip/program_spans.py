"""Sums of the program's own host spans and counters over the window's
chunks: ``ChunkStats.spans`` (self seconds by span name) and
``ChunkStats.counts``, which ``repro.runtime.telemetry`` charges inside
``StreamRuntime.push``.  A program without them (no ``spans`` on its
chunks) reads as nothing, never as zero."""


def _has_spans(r) -> bool:
    return bool(r.events and r.chunks) and hasattr(r.chunks[0], "spans")


def _named(key: str, names) -> bool:
    """``rt.snapshot`` names ``rt.snapshot`` and every ``rt.snapshot.*``."""
    return any(key == n or key.startswith(n + ".") for n in names)


def seconds(r, *names):
    """Self seconds of the named spans over the window."""
    if not _has_spans(r):
        return None
    return sum(v for c in r.chunks for k, v in c.spans.items()
               if _named(k, names))


def ms_per_kev(r, *names):
    """Self time of the named spans, in ms per 1,000 window events."""
    s = seconds(r, *names)
    return None if s is None else s * 1e6 / r.events


def counts(r, name: str):
    """``(window events up to and including the chunk, value)`` for each
    window chunk that has the counter, in order."""
    if not _has_spans(r):
        return None
    out, n = [], 0
    for c in r.chunks:
        n += c.n_events
        if name in c.counts:
            out.append((n, c.counts[name]))
    return out
