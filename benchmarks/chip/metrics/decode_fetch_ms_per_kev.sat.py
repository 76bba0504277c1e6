"""The program's ``rt.decode.fetch`` span: the dense match columns to the
host, in ms per 1,000 events."""
import program_spans as PS


def read(r):
    return PS.ms_per_kev(r, "rt.decode.fetch")
