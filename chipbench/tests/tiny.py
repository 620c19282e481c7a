"""A tiny copy of the benchmark, for CPU rehearsals: a 2-layer model at
toy widths and short mixes, written into a scratch checkout beside a copy
of ``chipbench/``."""
from __future__ import annotations

import json
import pathlib
import shutil

ROOT = pathlib.Path(__file__).resolve().parents[2]

TINY = {
    "name": "tiny", "source": "test", "repo_config": "minicpm_2b",
    "repo_overrides": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                       "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                       "vocab_size": 300},
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 300, "tie_word_embeddings": True, "rope_theta": 10000.0,
    "rope_fraction": 1.0, "rms_norm_eps": 1e-6,
    "engine": {"num_slots": 3, "page_size": 8, "max_seq": 64,
               "min_bucket": 8},
    "correct": {"logit_gap_max": 0.01, "min_tokens": 20},
}
CLOSED = {"kind": "serve", "loop": "closed", "clients_per_slot": 2,
          "start": "mid-request", "requests": 16,
          "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.8,
                     "min": 4, "max": 32},
          "output": {"dist": "uniform", "min": 8, "max": 24},
          "sampled": {"every": 3, "temperature": 0.8, "top_p": 0.95}}
POISSON = dict({k: v for k, v in CLOSED.items() if k != "start"},
               loop="poisson", rate_per_s=20.0, preroll_s=0.2,
               requests=200,
               output={"dist": "lognormal", "median": 8, "sigma": 0.5,
                       "min": 4, "max": 16})

TINY_TRAIN = dict(TINY, name="tiny-train", correct={
    "loss_gap": 8e-4, "grad_norm_gap": 6e-3, "update_norm_gap": 4e-3})
del TINY_TRAIN["engine"]
TRAIN = {"kind": "train", "batch": 4, "seq": 32, "checked_steps": 3,
         "peak_lr": 1e-3,
         "schedule": {"kind": "cosine", "warmup_steps": 0,
                      "total_steps": 100, "final_frac": 0.1},
         "adamw": {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
                   "clip_norm": 1.0}}


def make_checkout(dest: pathlib.Path) -> pathlib.Path:
    """``dest`` holds a copy of ``chipbench/`` and a ``BENCHMARK.json``
    with two tiny cells; ``src`` is found through the path."""
    shutil.copytree(ROOT / "chipbench", dest / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (dest / "chipbench" / "configs" / "tiny.json").write_text(
        json.dumps(TINY))
    (dest / "chipbench" / "traffic" / "tiny-closed.json").write_text(
        json.dumps(CLOSED))
    (dest / "chipbench" / "traffic" / "tiny-poisson.json").write_text(
        json.dumps(POISSON))
    (dest / "chipbench" / "configs" / "tiny-train.json").write_text(
        json.dumps(TINY_TRAIN))
    (dest / "chipbench" / "traffic" / "tiny-train.json").write_text(
        json.dumps(TRAIN))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] += [
        {"name": "tiny.closed", "config": "tiny", "traffic": "tiny-closed",
         "chips": 1, "why": "test"},
        {"name": "tiny.poisson", "config": "tiny", "traffic": "tiny-poisson",
         "chips": 1, "why": "test"},
        {"name": "tiny.train", "config": "tiny-train",
         "traffic": "tiny-train", "chips": 1, "why": "test"}]
    # each tiny cell reports what the real cell of its kind reports
    twin = {"minicpm-2b.decode-long": "tiny.closed",
            "chatglm3-6b.chat-poisson": "tiny.poisson",
            "minicpm-2b.train": "tiny.train"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [twin[w] for w in m["workloads"] if w in twin]
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest
