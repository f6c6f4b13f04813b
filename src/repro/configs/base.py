"""Configuration system for the SemiSFL framework.

Every architecture (the paper's own CNN/VGG family and the ten assigned
backbones) is described by one ``ArchConfig``.  The model builder
(`repro.models.build_model`) consumes nothing else, so a config file is the
single source of truth for an architecture.

Configs are registered by id (``--arch <id>`` on every launcher) via
:func:`register`; :func:`get_config` resolves ids, and
:func:`smoke_config` derives the reduced variant used by CPU smoke tests
(2 layers, d_model <= 512, <= 4 experts, tiny vocab).
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass, field, replace
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block configuration."""

    num_experts: int
    top_k: int
    d_ff_expert: int
    # Dense residual MLP computed in parallel with the routed experts
    # (Snowflake Arctic style).  0 disables it.
    d_ff_dense_residual: int = 0
    # Experts always applied to every token (DeepSeek-V2 "shared experts").
    num_shared_experts: int = 0
    # Which layers are MoE layers: every layer with index >= first_moe_layer
    # and (index - first_moe_layer) % period == 0.
    first_moe_layer: int = 0
    period: int = 1
    # Token-dropping capacity factor for the expert-parallel path.
    capacity_factor: float = 1.25
    router_aux_loss_coef: float = 0.001

    def is_moe_layer(self, idx: int) -> bool:
        return (idx >= self.first_moe_layer
                and (idx - self.first_moe_layer) % self.period == 0)


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2-style selective state space configuration."""

    state_dim: int = 64
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    chunk_size: int = 256

    def num_heads(self, d_model: int) -> int:
        return (self.expand * d_model) // self.head_dim


@dataclass(frozen=True)
class XLSTMConfig:
    """xLSTM block-stack configuration (mLSTM + periodic sLSTM)."""

    # one sLSTM block every `slstm_period` blocks (the rest are mLSTM);
    # xLSTM[7:1] from the paper -> period 8.
    slstm_period: int = 8
    mlstm_proj_factor: float = 2.0
    slstm_ff_factor: float = 4.0 / 3.0
    mlstm_head_dim: int = 512  # qk head dim after expansion / num_heads


@dataclass(frozen=True)
class SemiSFLConfig:
    """Paper-technique hyperparameters (Section III-V defaults)."""

    split_layer: int = 0                # 0 -> num_layers // 4 at build time
    proj_dim: int = 128                 # projection-head output dim
    proj_hidden: int = 256              # MLP projection head hidden width
    proj_head: str = "mlp"              # none | linear | mlp  (Table V)
    queue_len: int = 4096               # |Q| two-level memory queue
    temperature: float = 0.1            # kappa in Eq.(3)/(5)
    confidence_threshold: float = 0.95  # tau
    ema_decay: float = 0.99             # gamma
    k_s_init: int = 100                 # initial global updating frequency
    k_u: int = 10                       # cross-entity updating frequency
    alpha: float = 1.5                  # K_s decay factor, Eq.(10)
    beta: float = 8.0                   # K_min = floor(beta * |Dl|/|D| * K_u)
    observation_period: int = 10        # rounds per observation period
    adaptation_window: int = 10         # periods in R_h
    # LM-task adaptation knobs (DESIGN.md §4): number of tokens per sequence
    # whose projected features participate in clustering regularization.
    tokens_per_seq_clustering: int = 8


# ---------------------------------------------------------------------------
# Main architecture config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ArchConfig:
    name: str
    arch_type: str                      # dense | moe | ssm | hybrid | vlm | audio | cnn
    source: str                         # citation from the assignment pool
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                   # 0 -> d_model // num_heads

    # --- attention options -------------------------------------------------
    attn_bias: bool = False             # qwen2-style QKV bias
    qk_norm: bool = False               # qwen3-style per-head RMSNorm on q,k
    rope_kind: str = "rope"             # rope | mrope | none
    rope_theta: float = 1_000_000.0
    rope_pct: float = 1.0               # partial rotary (stablelm: 0.25)
    mrope_sections: Tuple[int, ...] = ()
    sliding_window: int = 0             # 0 -> full attention
    # sliding window applied only in long-context serving mode (zamba2 shared
    # attention adaptation, DESIGN.md §5):
    long_context_window: int = 0

    # --- MLA (DeepSeek-V2) --------------------------------------------------
    use_mla: bool = False
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- block-stack structure ----------------------------------------------
    block_kind: str = "attn"            # attn | mamba2 | xlstm
    # hybrid (zamba2): one weight-shared attention block applied after every
    # `shared_attn_period` mamba blocks.
    shared_attn_period: int = 0
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    xlstm: Optional[XLSTMConfig] = None

    # --- encoder-decoder ----------------------------------------------------
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0

    # --- modality frontend (stubbed per spec) --------------------------------
    modality: str = "text"              # text | vision | audio | image
    frontend_tokens: int = 0            # patch/frame embeds provided as input

    # --- misc ----------------------------------------------------------------
    norm: str = "rmsnorm"               # rmsnorm | layernorm
    act: str = "silu"                   # silu | gelu | relu
    mlp_gated: bool = True              # SwiGLU-style gate
    tie_embeddings: bool = False
    # CNN family (the paper's own models)
    cnn_channels: Tuple[int, ...] = ()
    cnn_fc: Tuple[int, ...] = ()
    # dropout on the FC-stack activations (AlexNet/VGG convention); active
    # only in train-mode forwards that supply per-sample dropout keys —
    # eval-mode forwards are deterministic by construction.
    cnn_dropout: float = 0.0
    # 2x2 max-pools after these convs, counted from 1; () pools where the
    # width changes and after the last conv
    cnn_pool_after: Tuple[int, ...] = ()
    # adaptive average pool of the last conv's grid to this side before
    # the FC stack (torchvision's AdaptiveAvgPool2d); 0: none
    cnn_pool_to: int = 0
    image_size: int = 32
    num_classes: int = 0                # classification task head (paper task)

    semisfl: SemiSFLConfig = field(default_factory=SemiSFLConfig)
    dtype: str = "bfloat16"

    # ------------------------------------------------------------------
    # Derived helpers
    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.use_mla:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def split_layer(self) -> int:
        s = self.semisfl.split_layer
        if s <= 0:
            s = max(1, self.num_layers // 4)
        # a CNN may put every conv on the client (the paper's CNN@2,
        # AlexNet@5, VGG13@10, VGG16@13): its top still holds the FC stack
        return min(s, self.num_layers - (self.arch_type != "cnn"))

    @property
    def cnn_pooled(self) -> Tuple[bool, ...]:
        """Per conv, whether a 2x2 max-pool follows it."""
        ch = self.cnn_channels
        if self.cnn_pool_after:
            return tuple(i + 1 in self.cnn_pool_after for i in range(len(ch)))
        return tuple(i == len(ch) - 1 or ch[i + 1] != c
                     for i, c in enumerate(ch))

    @property
    def cnn_fc_in(self) -> int:
        """Inputs of the first FC layer: the last conv's grid, max-pooled,
        then average-pooled to ``cnn_pool_to`` where that is set."""
        hw = self.cnn_pool_to or \
            self.image_size // 2 ** sum(self.cnn_pooled)
        return hw * hw * self.cnn_channels[-1]

    def param_count(self) -> int:
        """Analytic total parameter count (used by roofline + comm model)."""
        d, ff, V, L = self.d_model, self.d_ff, self.vocab_size, self.num_layers
        if self.arch_type == "cnn":
            total, cin = 0, 3
            for cout in self.cnn_channels:
                total += cin * cout * 9 + cout
                cin = cout
            feat = self.cnn_fc_in
            for fc in self.cnn_fc:
                total += feat * fc + fc
                feat = fc
            total += feat * self.num_classes + self.num_classes
            return total

        def attn_params() -> int:
            hd = self.resolved_head_dim
            if self.use_mla:
                q = (d * self.q_lora_rank + self.q_lora_rank * self.num_heads * hd
                     if self.q_lora_rank else d * self.num_heads * hd)
                kv = (d * (self.kv_lora_rank + self.qk_rope_head_dim)
                      + self.kv_lora_rank * self.num_heads
                      * (self.qk_nope_head_dim + self.v_head_dim))
                o = self.num_heads * self.v_head_dim * d
                return q + kv + o
            q = d * self.num_heads * hd
            kv = 2 * d * self.num_kv_heads * hd
            o = self.num_heads * hd * d
            return q + kv + o

        def mlp_params(width: int) -> int:
            return d * width * (3 if self.mlp_gated else 2)

        def moe_params(idx: int) -> int:
            m = self.moe
            assert m is not None
            p = d * m.num_experts  # router
            p += m.num_experts * mlp_params(m.d_ff_expert)
            p += m.num_shared_experts * mlp_params(m.d_ff_expert)
            p += mlp_params(m.d_ff_dense_residual) if m.d_ff_dense_residual else 0
            return p

        def ssm_params() -> int:
            s = self.ssm or SSMConfig()
            d_in = s.expand * d
            nh = s.num_heads(d)
            p = d * (2 * d_in + 2 * s.state_dim * (d_in // s.head_dim) + nh)
            p += s.conv_width * (d_in + 2 * s.state_dim * nh)
            p += d_in * d  # out proj
            return p

        total = V * d * (1 if self.tie_embeddings else 2)
        layers = L + self.num_encoder_layers
        for i in range(layers):
            if self.block_kind == "mamba2":
                total += ssm_params()
            elif self.block_kind == "xlstm":
                x = self.xlstm or XLSTMConfig()
                if (i + 1) % x.slstm_period == 0:
                    total += 4 * d * d + int(x.slstm_ff_factor * d) * d * 2
                else:
                    di = int(x.mlstm_proj_factor * d)
                    total += d * di * 2 + 3 * di * di // 4 + di * d
            else:
                total += attn_params()
                if self.moe is not None and self.moe.is_moe_layer(i):
                    total += moe_params(i)
                else:
                    total += mlp_params(ff)
            total += 2 * d  # norms
        if self.shared_attn_period:
            total += attn_params() + mlp_params(ff) + 2 * d
        if self.is_encoder_decoder:
            total += L * attn_params()  # cross attention
        if self.num_classes:
            total += d * self.num_classes
        return total

    def active_param_count(self) -> int:
        """Active parameters per token (MoE-aware) for MODEL_FLOPS = 6*N*D."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        full = self.param_count()
        per_expert = self.d_model * m.d_ff_expert * (3 if self.mlp_gated else 2)
        n_moe_layers = sum(1 for i in range(self.num_layers) if m.is_moe_layer(i))
        inactive = n_moe_layers * (m.num_experts - m.top_k) * per_expert
        return full - inactive


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, ArchConfig] = {}

# arch id -> config module (lazy import to keep `import repro` cheap)
_MODULES = {
    "qwen2.5-14b": "repro.configs.qwen2_5_14b",
    "qwen2-vl-7b": "repro.configs.qwen2_vl_7b",
    "stablelm-1.6b": "repro.configs.stablelm_1_6b",
    "zamba2-7b": "repro.configs.zamba2_7b",
    "seamless-m4t-medium": "repro.configs.seamless_m4t_medium",
    "qwen3-14b": "repro.configs.qwen3_14b",
    "arctic-480b": "repro.configs.arctic_480b",
    "xlstm-1.3b": "repro.configs.xlstm_1_3b",
    "h2o-danube-1.8b": "repro.configs.h2o_danube_1_8b",
    "deepseek-v2-236b": "repro.configs.deepseek_v2_236b",
    "paper-cnn": "repro.configs.paper_models",
    "paper-alexnet": "repro.configs.paper_models",
    "paper-vgg13": "repro.configs.paper_models",
    "paper-vgg16": "repro.configs.paper_models",
    "vgg16-image100": "repro.configs.paper_models",
}

PAPER_ARCHS = [k for k, m in _MODULES.items()
               if m == "repro.configs.paper_models"]
ASSIGNED_ARCHS = [k for k in _MODULES if k not in PAPER_ARCHS]


def register(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ArchConfig:
    if name not in _REGISTRY:
        mod = _MODULES.get(name)
        if mod is None:
            raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULES)}")
        importlib.import_module(mod)
    return _REGISTRY[name]


def list_archs() -> list[str]:
    return list(_MODULES)


# ---------------------------------------------------------------------------
# Smoke-test reduction
# ---------------------------------------------------------------------------


def smoke_config(name: str, *, seq_len: int = 32, batch: int = 2) -> ArchConfig:
    """Reduced same-family variant: 2 layers, d_model<=512, <=4 experts."""
    cfg = get_config(name)
    if cfg.arch_type == "cnn":
        return replace(
            cfg,
            name=cfg.name + "-smoke",
            cnn_channels=cfg.cnn_channels[:2] or (8, 16),
            cnn_fc=(32,),
            image_size=16,
            semisfl=replace(cfg.semisfl, split_layer=1, queue_len=64,
                            proj_dim=16, proj_hidden=32, k_s_init=2, k_u=2),
        )
    d_model = min(cfg.d_model, 256)
    n_heads = max(2, min(cfg.num_heads, 4))
    n_kv = max(1, min(cfg.num_kv_heads, n_heads))
    if n_heads % n_kv:
        n_kv = 1
    head_dim = max(8, d_model // n_heads)
    kw = dict(
        name=cfg.name + "-smoke",
        num_layers=2,
        d_model=d_model,
        num_heads=n_heads,
        num_kv_heads=n_kv,
        head_dim=head_dim,
        d_ff=min(cfg.d_ff, 512) if cfg.d_ff else 0,
        vocab_size=min(cfg.vocab_size, 512),
        frontend_tokens=min(cfg.frontend_tokens, 8) if cfg.frontend_tokens else 0,
        semisfl=replace(cfg.semisfl, split_layer=1, queue_len=64, proj_dim=16,
                        proj_hidden=32, k_s_init=2, k_u=2,
                        tokens_per_seq_clustering=4),
    )
    if cfg.moe is not None:
        kw["moe"] = replace(
            cfg.moe,
            num_experts=4,
            top_k=min(cfg.moe.top_k, 2),
            d_ff_expert=64,
            d_ff_dense_residual=64 if cfg.moe.d_ff_dense_residual else 0,
            num_shared_experts=min(cfg.moe.num_shared_experts, 1),
        )
    if cfg.ssm is not None:
        kw["ssm"] = replace(cfg.ssm, state_dim=16, head_dim=32, chunk_size=8)
        if cfg.shared_attn_period:
            kw["num_layers"] = 4
            kw["shared_attn_period"] = 2
            kw["semisfl"] = replace(kw["semisfl"], split_layer=2)
    if cfg.xlstm is not None:
        kw["xlstm"] = replace(cfg.xlstm, slstm_period=2, mlstm_head_dim=64)
        kw["num_layers"] = 4  # one full mLSTM/sLSTM group
    if cfg.use_mla:
        kw.update(kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=32,
                  qk_rope_head_dim=16, v_head_dim=32)
    if cfg.is_encoder_decoder:
        kw["num_encoder_layers"] = 2
    if cfg.mrope_sections:
        kw["mrope_sections"] = (head_dim // 4, head_dim // 8, head_dim // 8)
    if cfg.num_classes:
        kw["num_classes"] = min(cfg.num_classes, 10)
    return replace(cfg, **kw)
