"""The decode segment's share of its roofline (%): the least HBM bytes its
iterations need (the weights once an iteration, the live K/V of the active
slots, not padded pages) over the peak bandwidth, divided by the segment's
device time in the traced window."""


def read(r):
    t = r.get("device.program._decode_segment")
    b = r.get("traced.decode_bytes")
    if not t or not b:
        return None
    return 100.0 * (b / r["peak.hbm_bytes_per_s"]) / t
