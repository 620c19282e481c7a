"""Architecture configuration system.

One ``ModelConfig`` describes any of the assigned architectures (dense / GQA /
MLA / MoE / SSM / hybrid / enc-dec / VLM-backbone).  Each ``configs/<id>.py``
exports ``CONFIG`` with the exact published numbers and the registry maps
``--arch <id>`` to it.  ``reduced()`` derives the small-family config used by
the per-arch CPU smoke tests (same block kinds and wiring, tiny dims).
"""
from __future__ import annotations

import dataclasses
import importlib
import math
from typing import NamedTuple, Optional

__all__ = ["ModelConfig", "Yarn", "get_config", "list_archs", "ARCH_IDS"]


class Yarn(NamedTuple):
    """YaRN rotary scaling (arXiv:2309.00071) as DeepSeek-V2 states it:
    the frequencies of pairs that turn fewer than ``beta_slow`` times over
    ``original_max_pos`` positions are divided by ``factor``, those that
    turn more than ``beta_fast`` times are kept, a linear ramp between."""
    factor: float
    original_max_pos: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0               # 0 → d_model // num_heads

    # attention details
    rope_theta: float = 1e4
    rope_fraction: float = 1.0      # partial rotary (chatglm/glm4 "2d" RoPE = 0.5)
    sliding_window: Optional[int] = None
    layer_pattern: tuple[str, ...] = ("attn",)   # repeating block kinds
    attn_logit_softcap: Optional[float] = None
    rope_yarn: Optional[Yarn] = None    # None → plain RoPE

    # MoE
    num_experts: int = 0
    experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0               # routed-expert hidden dim
    first_k_dense: int = 0          # leading dense-FFN layers (deepseek-v2: 1)
    moe_period: int = 1             # MoE every Nth layer (jamba: 2)
    # routing: softmax over all ``num_experts`` router outputs; with
    # n_group > 1 the experts form n_group groups, each scored by its best
    # member, and the top-k experts are taken among the topk_group best
    # groups (deepseek-v2 "group_limited_greedy"); the k weights are
    # renormalised to sum to 1 or else scaled by routed_scaling_factor
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # the expert share this device holds: experts [first_expert,
    # first_expert + experts_held) of the num_experts the router scores
    # (0 → all of them); under an EP shard_map each shard holds its slice
    experts_held: int = 0
    first_expert: int = 0

    # MLA (deepseek-v2)
    use_mla: bool = False
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    rope_head_dim: int = 64

    # SSM (mamba1)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: int = 0            # 0 → ceil(d_model / 16)

    # encoder-decoder (whisper)
    is_encdec: bool = False
    encoder_layers: int = 0
    encoder_seq: int = 0            # frame count the audio stub produces

    # modality frontend stubs
    frontend: Optional[str] = None  # audio_stub | vision_stub
    num_patches: int = 0

    # misc
    norm: str = "rmsnorm"           # rmsnorm | layernorm
    use_fusion: bool = False        # build layers via repro.fusion TppGraphs
    dropout_rate: float = 0.0       # attention-output-projection dropout
    #                                 (train only; the counter-PRNG draw
    #                                 needs a dropout_seed threaded from the
    #                                 train step — MLP sublayers currently
    #                                 take no dropout)
    gated_mlp: bool = True
    mlp_activation: str = "silu"
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    source: str = ""                # provenance tag from the assignment table

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.ssm_state and not self.ssm_dt_rank:
            object.__setattr__(self, "ssm_dt_rank", math.ceil(self.d_model / 16))

    # ------------------------------------------------------------------
    @property
    def padded_vocab(self) -> int:
        """Embedding/LM-head rows padded to a TP-shardable multiple
        (Megatron-style vocab padding; padded logits are masked to -inf in
        the loss/decode).  256 = lcm-friendly for a 16-way model axis."""
        return (self.vocab_size + 255) // 256 * 256

    @property
    def pattern_period(self) -> int:
        return len(self.layer_pattern)

    @property
    def num_periods(self) -> int:
        assert self.num_layers % self.pattern_period == 0, (
            self.name, self.num_layers, self.layer_pattern)
        return self.num_layers // self.pattern_period

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def held_experts(self) -> int:
        """Routed experts whose weights this device holds."""
        return self.experts_held or self.num_experts

    @property
    def attention_free(self) -> bool:
        return all(k == "mamba" for k in self.layer_pattern)

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for the long_500k shape: SSM / hybrid / sliding-window."""
        return self.attention_free or "mamba" in self.layer_pattern or (
            self.sliding_window is not None
        )

    def param_count(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS = 6·N·D)."""
        d, v = self.d_model, self.vocab_size
        total = v * d  # embeddings
        if not self.tie_embeddings:
            total += v * d
        for kind in self._layer_kinds():
            total += self._block_params(kind)
        total += d  # final norm
        if self.is_encdec:
            total += self.encoder_layers * (
                self._attn_params() + self._mlp_params(self.d_ff) + 2 * d
            ) + d
        return total

    def active_param_count(self) -> int:
        """Active params per token (MoE: top-k experts only)."""
        d, v = self.d_model, self.vocab_size
        total = v * d + (0 if self.tie_embeddings else v * d) + d
        for kind in self._layer_kinds():
            total += self._block_params(kind, active_only=True)
        if self.is_encdec:
            total += self.encoder_layers * (
                self._attn_params() + self._mlp_params(self.d_ff) + 2 * d
            ) + d
        return total

    def _layer_kinds(self):
        kinds = []
        for i in range(self.num_layers):
            kind = self.layer_pattern[i % self.pattern_period]
            moe_here = (
                self.is_moe
                and i >= self.first_k_dense
                and (i % self.moe_period == self.moe_period - 1
                     or self.moe_period == 1)
            )
            kinds.append((kind, moe_here))
        return kinds

    def _attn_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        if self.use_mla:
            q = (d * self.q_lora_rank
                 + self.q_lora_rank * self.num_heads * (hd + self.rope_head_dim))
            kv = (d * (self.kv_lora_rank + self.rope_head_dim)
                  + self.kv_lora_rank * self.num_heads * (hd + hd))
            o = self.num_heads * hd * d
            return q + kv + o
        q = d * self.num_heads * hd
        kv = 2 * d * self.num_kv_heads * hd
        o = self.num_heads * hd * d
        return q + kv + o

    def _mamba_params(self) -> int:
        d, di, n = self.d_model, self.d_inner, self.ssm_state
        return (d * 2 * di + self.ssm_conv * di + di       # in_proj, conv w+b
                + di * (self.ssm_dt_rank + 2 * n)          # x_proj
                + self.ssm_dt_rank * di + di                # dt proj + bias
                + di * n + di + di * d)                     # A, D, out_proj

    def _mlp_params(self, ff: int) -> int:
        d = self.d_model
        return d * ff * (3 if self.gated_mlp else 2)

    def _block_params(self, kind_moe, active_only=False) -> int:
        kind, moe_here = kind_moe
        d = self.d_model
        has_ffn = moe_here or self.d_ff > 0
        total = d * (2 if has_ffn else 1)  # pre-norms
        if kind == "mamba":
            total += self._mamba_params()
            if has_ffn:
                total += self._mlp_params(self.moe_d_ff if moe_here
                                          else self.d_ff) if not moe_here else 0
            if moe_here:
                e = self.experts_per_tok if active_only else self.held_experts
                total += e * self._mlp_params(self.moe_d_ff)
                total += self.num_shared_experts * self._mlp_params(self.moe_d_ff)
                total += d * self.num_experts
            return total
        total += self._attn_params()
        if moe_here:
            e = self.experts_per_tok if active_only else self.held_experts
            total += e * self._mlp_params(self.moe_d_ff)
            total += self.num_shared_experts * self._mlp_params(self.moe_d_ff)
            total += d * self.num_experts  # router
        else:
            total += self._mlp_params(self.d_ff)
        return total

    # ------------------------------------------------------------------
    def reduced(self) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests."""
        period = self.pattern_period
        n_layers = max(period, 2 if period == 1 else period)
        grouped = self.n_group > 1
        return dataclasses.replace(
            self,
            name=self.name + "-reduced",
            num_layers=n_layers,
            d_model=64,
            num_heads=4,
            num_kv_heads=min(self.num_kv_heads, 2) if self.num_kv_heads < self.num_heads else 4,
            head_dim=16,
            d_ff=128,
            vocab_size=256,
            # grouped routing keeps 4 groups of 2 (2 kept) and a share of
            # 5 experts from the second: every mechanism, at toy sizes
            num_experts=8 if grouped else min(self.num_experts, 4),
            experts_per_tok=min(self.experts_per_tok, 2),
            n_group=4 if grouped else 1,
            topk_group=2 if grouped else 1,
            experts_held=5 if grouped else 0,
            first_expert=1 if grouped else 0,
            moe_d_ff=64 if self.is_moe else 0,
            kv_lora_rank=32 if self.use_mla else 0,
            q_lora_rank=32 if self.q_lora_rank else 0,
            rope_head_dim=8 if self.use_mla else 64,
            ssm_state=min(self.ssm_state, 8),
            ssm_dt_rank=4 if self.ssm_state else 0,
            sliding_window=32 if self.sliding_window else None,
            encoder_layers=2 if self.is_encdec else 0,
            encoder_seq=16 if self.is_encdec else 0,
            num_patches=8 if self.frontend == "vision_stub" else 0,
            first_k_dense=min(self.first_k_dense, 1),
            dtype="float32",
        )


ARCH_IDS = [
    "falcon_mamba_7b", "deepseek_v2_236b", "qwen3_moe_235b", "whisper_small",
    "chatglm3_6b", "gemma3_12b", "minicpm_2b", "glm4_9b",
    "jamba_1_5_large", "llava_next_34b",
    # the paper's own end-to-end workloads
    "bert_large", "gptj_6b", "llama2_13b",
]


def get_config(arch: str) -> ModelConfig:
    arch = arch.replace("-", "_")
    if arch not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch!r}; known: {ARCH_IDS}")
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.CONFIG


def list_archs() -> list[str]:
    return list(ARCH_IDS)
