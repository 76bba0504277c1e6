"""The program's ``rt.device_wait`` span: the host blocked on the chunk's
outputs before its first read, in ms per 1,000 events."""
import program_spans as PS


def read(r):
    return PS.ms_per_kev(r, "rt.device_wait")
