"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps on
the synthetic corpus, with checkpoint/restart and the production train step
(remat, chunked-vocab CE, WSD schedule, straggler watchdog).

Run:  PYTHONPATH=src python examples/train_lm.py --steps 300
      PYTHONPATH=src python examples/train_lm.py --arch qwen3_moe_235b --steps 50
      PYTHONPATH=src python examples/train_lm.py --use-fusion --steps 100
(named archs run their reduced config on CPU; the default is a ~100M dense
model with the minicpm recipe).  ``--use-fusion`` builds the MLP / gated-MLP
/ attention-output (+block residual) / MoE-expert projections through the
TPP-chain fusion compiler with ``compile_with_vjp``: both the forward layers
AND their backward passes run as derived TppGraphs (fused kernels on the
Pallas backends) instead of XLA differentiating the composition."""
import argparse
import dataclasses

from repro.configs import get_config
from repro.data import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.train import TrainConfig, TrainerConfig, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--use-fusion", action="store_true",
                    help="build layers as TppGraphs with fused fwd+bwd "
                         "(fusion.compile_with_vjp)")
    args = ap.parse_args()

    if args.arch:
        cfg = get_config(args.arch).reduced()
    else:
        # ~100M params: the minicpm family scaled to laptop size
        cfg = dataclasses.replace(
            get_config("minicpm_2b"),
            name="minicpm-100m", num_layers=8, d_model=512,
            num_heads=8, num_kv_heads=8, head_dim=64, d_ff=1536,
            vocab_size=32768, dtype="float32")
    if args.use_fusion:
        cfg = dataclasses.replace(cfg, use_fusion=True)
    print(f"arch={cfg.name}  params≈{cfg.param_count()/1e6:.1f}M"
          f"  use_fusion={cfg.use_fusion}")

    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch, seed=0)
    tcfg = TrainConfig(peak_lr=3e-3, warmup_steps=20,
                       total_steps=args.steps, schedule="wsd",
                       loss_chunk=min(128, args.seq))
    rcfg = TrainerConfig(num_steps=args.steps, ckpt_every=100,
                         ckpt_dir=args.ckpt_dir, log_every=20)
    _, _, hist = train(cfg, tcfg, dcfg, rcfg, seed=0)
    print(f"\nloss: {hist['loss'][0]:.3f} → {hist['loss'][-1]:.3f} "
          f"({args.steps} steps); median step "
          f"{sorted(hist['step_time'])[len(hist['step_time'])//2]*1e3:.0f}ms")


if __name__ == "__main__":
    enable_compile_cache()
    main()
