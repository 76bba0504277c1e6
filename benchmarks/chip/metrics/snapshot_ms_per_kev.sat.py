"""The program's ``rt.snapshot.*`` spans: control state and its JSON,
encoding, and the fsynced write of each durable snapshot, in ms per 1,000
events."""
import program_spans as PS


def read(r):
    return PS.ms_per_kev(r, "rt.snapshot")
