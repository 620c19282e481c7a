"""The ``mla`` scope's share of its roofline inside the decode segment (%):
the larger of its least HBM bytes over the peak bandwidth and its FLOPs
over the bf16 peak (``mla_counts.mla_decode``: the weights once an
iteration, the active slots' live latent rows, absorbed arithmetic),
divided by the scope's device time in the traced window."""


def read(r):
    t = r.get("device.scope._decode_segment.mla")
    b, f = r.get("traced.mla_bytes"), r.get("traced.mla_flops")
    if not t or not b or not f:
        return None
    least = max(b / r["peak.hbm_bytes_per_s"],
                f / r["peak.bf16_flops_per_s"])
    return 100.0 * least / t
