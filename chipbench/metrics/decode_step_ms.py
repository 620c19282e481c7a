"""Device time of the decode-segment program in the traced window, per
decode iteration (ms)."""


def read(r):
    t = r.get("device.program._decode_segment")
    it = r.get("traced.decode_iterations")
    if not t or not it:
        return None
    return 1e3 * t / it
