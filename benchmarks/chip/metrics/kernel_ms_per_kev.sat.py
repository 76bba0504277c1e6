"""Device time of the block kernel (``cep_block_step``) in the traced
window, in ms per 1,000 events."""


def read(r):
    if r.trace is None or not r.events:
        return None
    return r.trace["kernel_s"] * 1e6 / r.events
