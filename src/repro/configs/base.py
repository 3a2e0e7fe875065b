"""Configuration dataclasses for the repro framework.

One ``ModelConfig`` describes any architecture in the assigned pool (dense /
MoE+MLA / Mamba2-hybrid / xLSTM / enc-dec audio / VLM).  ``reduced()``
shrinks a config for CPU smoke tests while keeping the family topology (MoE
stays MoE, hybrid stays hybrid, ...).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Tuple


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts sub-config (DeepSeek-style)."""

    num_experts: int = 0              # routed experts
    num_shared_experts: int = 0       # always-on shared experts
    top_k: int = 0                    # routed experts per token
    d_ff: int = 0                     # per-expert FFN hidden size
    first_k_dense: int = 0            # leading dense layers (DeepSeek)
    dense_d_ff: int = 0               # FFN size of those dense layers
    router_aux_loss: float = 0.001    # load-balance loss coefficient
    norm_topk_prob: bool = True       # renormalise the top-k gates to sum 1
    routed_scaling_factor: float = 1.0  # scales the routed experts' sum
    # expert parallelism: this device holds the routed experts
    # [first_expert, first_expert + num_experts_held) and computes their
    # part of the result; the router still spans all num_experts.
    # 0 holds every expert.
    num_experts_held: int = 0
    first_expert: int = 0

    @property
    def enabled(self) -> bool:
        return self.num_experts > 0

    @property
    def held(self) -> int:
        """Routed experts whose weights this device holds."""
        return self.num_experts_held or self.num_experts


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rope scaling (DeepSeek v2): NTK-by-parts rope frequencies and
    an attention temperature.  ``factor`` 1 is plain rope."""

    factor: float = 1.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @property
    def enabled(self) -> bool:
        return self.factor > 1.0


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention sub-config (DeepSeek v2/v3)."""

    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    yarn: YarnConfig = field(default_factory=YarnConfig)

    @property
    def enabled(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 / xLSTM sub-config."""

    state_size: int = 0               # N: SSM state dimension per group
    conv_kernel: int = 4
    head_dim: int = 64                # P: channels per SSM head
    expand: int = 2                   # d_inner = expand * d_model
    ngroups: int = 1                  # B/C groups (shared across heads)
    chunk_size: int = 256             # chunked-scan block length
    # hybrid (Zamba2): a shared attention block applied every N ssm blocks
    attn_every: int = 0               # 0 = no interleaved attention
    # xLSTM: which block indices are sLSTM (rest mLSTM)
    slstm_layers: Tuple[int, ...] = ()

    @property
    def enabled(self) -> bool:
        return self.state_size > 0


@dataclass(frozen=True)
class EncDecConfig:
    """Encoder-decoder sub-config (Whisper)."""

    encoder_layers: int = 0
    source_positions: int = 1500      # post-conv audio frames
    frontend: str = "stub"            # modality frontend is a STUB per spec

    @property
    def enabled(self) -> bool:
        return self.encoder_layers > 0


@dataclass(frozen=True)
class VLMConfig:
    """Cross-attention VLM sub-config (Llama-3.2-Vision)."""

    cross_attn_every: int = 0         # cross-attn layer every N layers
    vision_tokens: int = 1601         # patch embeddings per image (stub)
    vision_dim: int = 0               # dim of the (stub) vision embeddings

    @property
    def enabled(self) -> bool:
        return self.cross_attn_every > 0


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"             # dense | moe | hybrid | ssm | audio | vlm
    num_layers: int = 2
    d_model: int = 128
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 0                 # 0 -> d_model // num_heads
    d_ff: int = 256
    vocab_size: int = 512
    qkv_bias: bool = False
    gated_mlp: bool = True            # SwiGLU (3 mats) vs GELU (2 mats)
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    max_seq_len: int = 1 << 20
    # sub-configs
    moe: MoEConfig = field(default_factory=MoEConfig)
    mla: MLAConfig = field(default_factory=MLAConfig)
    ssm: SSMConfig = field(default_factory=SSMConfig)
    encdec: EncDecConfig = field(default_factory=EncDecConfig)
    vlm: VLMConfig = field(default_factory=VLMConfig)
    # numerics
    dtype: str = "bfloat16"
    # attention implementation for train/prefill: "reference" materializes
    # the full score matrix; "chunked" is the flash-pattern online-softmax
    # scan over KV blocks (§Perf iteration 1 — memory-roofline fix)
    attn_impl: str = "reference"
    # sub-quadratic? (a hybrid's attention takes a sliding window at
    # long context: lm._window_for)
    subquadratic: bool = False

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def is_encoder_decoder(self) -> bool:
        return self.encdec.enabled

    def param_count(self) -> int:
        """Analytic parameter count (the constructed pytree but the final
        norm's d scales; used for model FLOPs and the perf model).  Of a
        MoE layer it counts the routed experts held here."""
        d, L, V = self.d_model, self.num_layers, self.vocab_size
        n = V * d  # embedding
        if not self.tie_embeddings:
            n += V * d  # lm head
        for layer in range(L):
            n += self._attn_params(layer)
            n += self._ffn_params(layer)
            n += 2 * d  # norms
        if self.ssm.enabled and self.ssm.attn_every > 0:
            # hybrid: ONE weight-shared attention+MLP block (Zamba2)
            n += self._dense_attn_params() + self._mlp_params(self.d_ff) + 2 * d
        if self.encdec.enabled:
            # encoder stack (self-attn + FFN + norms per layer)
            n += self.encdec.encoder_layers * (
                self._dense_attn_params() + self._mlp_params(self.d_ff) + 2 * d)
        return n

    def active_param_count(self) -> int:
        """Active (per-token) parameters — for a MoE model's FLOPs.
        With a share of the experts held (``moe.num_experts_held``) a
        token's routed experts lie on this device top_k · held / E times
        on average, and only those count."""
        if not self.moe.enabled:
            return self.param_count()
        m = self.moe
        d, L, V = self.d_model, self.num_layers, self.vocab_size
        n = V * d * (1 if self.tie_embeddings else 2)
        routed = m.top_k * m.held / m.num_experts
        for layer in range(L):
            n += self._attn_params(layer) + 2 * d
            if layer < m.first_k_dense:
                n += 3 * d * m.dense_d_ff
            else:
                n += round(3 * d * m.d_ff * (routed + m.num_shared_experts)) \
                    + d * m.num_experts                     # + router
        return n

    def _dense_attn_params(self) -> int:
        d, hd = self.d_model, self.resolved_head_dim
        q = d * self.num_heads * hd
        kv = 2 * d * self.num_kv_heads * hd
        o = self.num_heads * hd * d
        bias = (self.num_heads + 2 * self.num_kv_heads) * hd if self.qkv_bias else 0
        return q + kv + o + bias

    def _mlp_params(self, d_ff: int) -> int:
        if d_ff == 0:
            return 0
        mats = 3 if self.gated_mlp else 2
        return mats * self.d_model * d_ff

    def _attn_params(self, layer: int) -> int:
        d = self.d_model
        if self.ssm.enabled:
            # Mamba2 / xLSTM block parameters (hybrid shared-attn counted
            # separately, once, in param_count)
            di = self.ssm.expand * d
            nheads = max(di // max(self.ssm.head_dim, 1), 1)
            if self.family == "ssm" and layer in self.ssm.slstm_layers:
                # sLSTM block: 4 gates (i,f,z,o) recurrent + input proj + out
                return d * 4 * d + 4 * d * self.num_heads * 0 + 2 * d * di
            if self.family == "ssm":   # xLSTM mLSTM block
                return d * 3 * di + di * d + di * self.ssm.conv_kernel
            # mamba2: in_proj (z,x,B,C,dt) + conv(x,B,C) + out_proj
            bc = 2 * self.ssm.ngroups * self.ssm.state_size
            return d * (2 * di + bc + nheads) \
                + self.ssm.conv_kernel * (di + bc) + di * d
        if self.mla.enabled:
            m = self.mla
            nh = self.num_heads
            if m.q_lora_rank:                                                # q path
                p = (d * m.q_lora_rank + m.q_lora_rank
                     + m.q_lora_rank * nh * m.qk_head_dim)
            else:
                p = d * nh * m.qk_head_dim
            p += d * (m.kv_lora_rank + m.qk_rope_head_dim) + m.kv_lora_rank  # kv down
            p += m.kv_lora_rank * nh * (m.qk_nope_head_dim + m.v_head_dim)   # kv up
            p += nh * m.v_head_dim * d                                       # o proj
            return p
        p = self._dense_attn_params()
        if self.vlm.enabled and self._is_cross_attn_layer(layer):
            p *= 2  # cross-attn layer adds a parallel attention block
        if self.encdec.enabled:
            p += self._dense_attn_params()  # decoder cross-attention
        return p

    def _ffn_params(self, layer: int) -> int:
        d = self.d_model
        if self.ssm.enabled:
            return 0  # folded into the block
        if self.moe.enabled:
            if layer < self.moe.first_k_dense:
                return 3 * d * self.moe.dense_d_ff
            total = self.moe.held + self.moe.num_shared_experts
            return 3 * d * self.moe.d_ff * total + d * self.moe.num_experts
        return self._mlp_params(self.d_ff)

    def _is_cross_attn_layer(self, layer: int) -> bool:
        return self.vlm.enabled and (layer % self.vlm.cross_attn_every == 0)


def reduced(cfg: ModelConfig, *, layers: int = 2, d_model: int = 64,
            vocab: int = 256) -> ModelConfig:
    """Shrink a config for CPU smoke tests, preserving family topology."""
    heads = 4
    kv = min(cfg.num_kv_heads, heads) if cfg.num_kv_heads < cfg.num_heads else heads
    kv = max(1, min(kv, heads))
    changes = dict(
        num_layers=layers, d_model=d_model, num_heads=heads, num_kv_heads=kv,
        head_dim=d_model // heads, d_ff=(128 if cfg.d_ff else 0),
        vocab_size=vocab, max_seq_len=4096, dtype="float32",
    )
    if cfg.moe.enabled:
        m = cfg.moe
        # a held share keeps its fraction of the experts, at least one
        held = max(1, 4 * m.num_experts_held // m.num_experts) \
            if m.num_experts_held else 0
        changes["moe"] = dataclasses.replace(
            m, num_experts=4, top_k=2,
            num_shared_experts=min(m.num_shared_experts, 1),
            d_ff=64, first_k_dense=min(m.first_k_dense, 1), dense_d_ff=128,
            num_experts_held=held,
            first_expert=min(m.first_expert * 4 // m.num_experts, 4 - held))
    if cfg.mla.enabled:
        changes["mla"] = dataclasses.replace(
            cfg.mla, kv_lora_rank=32,
            q_lora_rank=32 if cfg.mla.q_lora_rank else 0,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
        changes["head_dim"] = 16
    if cfg.ssm.enabled:
        changes["ssm"] = dataclasses.replace(
            cfg.ssm, state_size=16, head_dim=16, chunk_size=32,
            slstm_layers=tuple(i for i in cfg.ssm.slstm_layers if i < layers))
    if cfg.encdec.enabled:
        changes["encdec"] = dataclasses.replace(
            cfg.encdec, encoder_layers=layers, source_positions=16)
    if cfg.vlm.enabled:
        changes["vlm"] = dataclasses.replace(
            cfg.vlm, cross_attn_every=2, vision_tokens=8, vision_dim=d_model)
    return dataclasses.replace(cfg, **changes)
