"""The program's ``rt.decode.sets`` span: the host's nonzero scan and set
loop over the match columns, in ms per 1,000 events."""
import program_spans as PS


def read(r):
    return PS.ms_per_kev(r, "rt.decode.sets")
