"""99th percentile of the emission latency of every match completed in
the window, as ``emit_p50_ms`` takes it, in ms.  A tail, not bounded: its
runs fall at about 230 or about 270 ms, so its spread is too wide for an
end-to-end bound."""


import numpy as np


def read(r):
    if not r.latencies:
        return None
    return float(np.percentile(r.latencies, 99)) * 1e3
