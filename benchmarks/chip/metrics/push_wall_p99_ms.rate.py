"""99th percentile of the benchmark's push spans, in ms."""


import numpy as np


def read(r):
    if not r.push_spans:
        return None
    return float(np.percentile([b - a for a, b in r.push_spans], 99)) * 1e3
