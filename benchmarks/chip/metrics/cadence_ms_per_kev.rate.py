"""The host work the every-4-chunk cadence adds under the open loop: the
program's ``rt.refresh.*``, ``rt.guard.checkpoint`` and ``rt.snapshot.*``
spans, in ms per 1,000 events."""
import program_spans as PS


def read(r):
    return PS.ms_per_kev(r, "rt.refresh", "rt.guard.checkpoint",
                         "rt.snapshot")
