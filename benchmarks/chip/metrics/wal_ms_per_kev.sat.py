"""The program's ``rt.wal.*`` spans: write-ahead log append and fsync, in
ms per 1,000 events."""
import program_spans as PS


def read(r):
    return PS.ms_per_kev(r, "rt.wal")
