"""99th percentile of how late each push started against its due time on
the generator's schedule, in ms."""


import numpy as np


def read(r):
    if not r.gen_lags:
        return None
    return float(np.percentile(r.gen_lags, 99)) * 1e3
