"""Model FLOPs of the train steps that ran wholly inside the traced window
(forward and backward, no recomputation: ``counts.train_flops``) over their
device time, as a share of the chip's bf16 peak (%)."""


def read(r):
    n = r.get("device.calls.train_step")
    t = r.get("device.calls_s.train_step")
    if not n or not t:
        return None
    return 100.0 * r["train.step_flops"] * n / t / r["peak.bf16_flops_per_s"]
