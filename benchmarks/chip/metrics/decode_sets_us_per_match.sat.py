"""Host match decode per match found: the program's ``rt.decode.sets``
span over its ``matches_decoded`` counter, in microseconds.  Far above the
cost of one set insertion while the decode scans every emitted match
column; near it once only the matches reach the host."""
import program_spans as PS


def read(r):
    s = PS.seconds(r, "rt.decode.sets")
    found = PS.counts(r, "matches_decoded")
    n = sum(v for _, v in found or [])
    if s is None or not n:
        return None
    return s * 1e6 / n
