"""Model FLOPs of the prompts prefilled in the traced window (true prompt
lengths, not bucket padding) over the prefill program's device time, as a
share of the chip's bf16 peak (%)."""


def read(r):
    t = r.get("device.program._prefill_one")
    f = r.get("traced.prefill_flops")
    if not t or not f:
        return None
    return 100.0 * f / t / r["peak.bf16_flops_per_s"]
