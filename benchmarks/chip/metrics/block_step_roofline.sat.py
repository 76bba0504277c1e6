"""The block kernel's share of its memory roofline: the least time HBM
needs for the bytes counted from the shapes (``work.py``), over the
kernel's device time in the trace."""


def read(r):
    if r.roofline is None:
        return None
    return r.roofline["share_pct"]
