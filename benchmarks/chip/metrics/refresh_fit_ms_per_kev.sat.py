"""The program's ``rt.refresh.fit`` span: chains, drift test, utility
tables and latency refit of the model refresh, in ms per 1,000 events."""
import program_spans as PS


def read(r):
    return PS.ms_per_kev(r, "rt.refresh.fit")
