"""Model FLOPs of the decoded tokens in the traced window over the decode
segment's device time, as a share of the chip's bf16 peak (%)."""


def read(r):
    t = r.get("device.program._decode_segment")
    f = r.get("traced.decode_flops")
    if not t or not f:
        return None
    return 100.0 * f / t / r["peak.bf16_flops_per_s"]
