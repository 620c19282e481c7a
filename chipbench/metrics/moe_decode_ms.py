"""Device time of the ``moe`` named scope (routing, the held experts'
grouped product and the shared experts, every expert layer) inside the
decode-segment program in the traced window, per decode iteration (ms)."""


def read(r):
    t = r.get("device.scope._decode_segment.moe")
    it = r.get("traced.decode_iterations")
    if not t or not it:
        return None
    return 1e3 * t / it
