"""The program's ``rt.guard.*`` spans: the carry guard's invariant check
and checkpoint, in ms per 1,000 events."""
import program_spans as PS


def read(r):
    return PS.ms_per_kev(r, "rt.guard")
