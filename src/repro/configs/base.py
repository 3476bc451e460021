"""Config system: model configuration + registry.

Every assigned architecture registers a `ModelConfig` (exact paper/model-card
hyperparameters) plus a reduced smoke variant (<=2 layers, d_model <= 512,
<= 4 experts) used by CPU tests.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

# ---------------------------------------------------------------------------
# Block kinds understood by the model builder (models/registry.py):
#   "attn"        full causal self-attention + MLP (pre-norm)
#   "swa"         sliding-window causal self-attention + MLP
#   "moe"         full attention + mixture-of-experts MLP
#   "swa_moe"     sliding-window attention + MoE MLP
#   "mamba2"      Mamba2 SSD block
#   "shared_attn" attention+MLP block whose weights are SHARED across all
#                 occurrences (zamba2-style)
#   "mlstm"       xLSTM matrix-memory block
#   "slstm"       xLSTM scalar-memory block
# Encoder-decoder archs additionally use encoder_layers of bidirectional
# "attn" blocks and decoder blocks with cross-attention.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                      # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    block_unit: Tuple[str, ...]         # repeating unit of block kinds
    head_dim: Optional[int] = None
    # attention
    sliding_window: Optional[int] = None
    rope_theta: float = 10000.0
    mrope_sections: Optional[Tuple[int, int, int]] = None  # qwen2-vl M-RoPE
    # MoE
    num_experts: int = 0
    experts_per_token: int = 0
    capacity_factor: float = 1.25
    # SSM (mamba2)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    # xLSTM
    lstm_heads: int = 4
    # encoder-decoder (audio)
    encoder_layers: int = 0
    source_positions: int = 0           # encoder sequence length (stub frames)
    # vlm stub
    vision_tokens: int = 0              # patch embeddings provided per sample
    # misc
    pos_embedding: str = "rope"         # rope | sinusoidal
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    source: str = ""                    # provenance citation

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def block_kinds(self) -> Tuple[str, ...]:
        """Per-layer kinds: block_unit tiled/truncated to num_layers."""
        unit = self.block_unit
        reps = -(-self.num_layers // len(unit))
        return (unit * reps)[: self.num_layers]

    @property
    def is_encoder_decoder(self) -> bool:
        return self.encoder_layers > 0

    def with_overrides(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def cut_depth(self, layers: int) -> "ModelConfig":
        """The same model ``layers`` deep: only ``num_layers`` changes,
        in whole periods of ``block_unit``, never beyond the published
        depth; every width stays as published."""
        period = len(self.block_unit)
        whole = layers % period == 0 or layers == self.num_layers
        if not (0 < layers <= self.num_layers and whole):
            raise ValueError(
                f"{self.name}: {layers} layers is not a whole number of "
                f"{period}-block units {self.block_unit} within the "
                f"published {self.num_layers}")
        return dataclasses.replace(self, num_layers=layers)

    # ---- analytic parameter / FLOP counts (roofline §) -------------------
    def param_count(self) -> int:
        from repro.models import registry
        return registry.count_params(self)

    def active_param_count(self) -> int:
        from repro.models import registry
        return registry.count_params(self, active_only=True)


_REGISTRY: Dict[str, Callable[[], ModelConfig]] = {}
_SMOKE_REGISTRY: Dict[str, Callable[[], ModelConfig]] = {}


def register(name: str, full: Callable[[], ModelConfig],
             smoke: Callable[[], ModelConfig]) -> None:
    _REGISTRY[name] = full
    _SMOKE_REGISTRY[name] = smoke


def get_config(name: str) -> ModelConfig:
    _ensure_imported()
    return _REGISTRY[name]()


def get_smoke_config(name: str) -> ModelConfig:
    _ensure_imported()
    return _SMOKE_REGISTRY[name]()


def cli_config(arch: str, *, smoke: bool, layers: Optional[int],
               tag: str) -> ModelConfig:
    """The config a launcher runs: published (or smoke) widths, cut to
    ``layers`` when given. Prints the cut as ``reduced: num_layers a->b``
    and turns an impossible cut into a usage error."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    if layers is None:
        return cfg
    try:
        cut = cfg.cut_depth(layers)
    except ValueError as e:
        raise SystemExit(f"[{tag}] bad --layers: {e}") from e
    print(f"[{tag}] reduced: num_layers {cfg.num_layers}->{cut.num_layers}")
    return cut


def list_architectures():
    _ensure_imported()
    return sorted(_REGISTRY)


def _ensure_imported() -> None:
    # importing the package registers every config module
    from repro import configs as _  # noqa: F401
    import importlib
    for mod in ("zamba2_7b", "gemma3_4b", "tinyllama_1_1b", "xlstm_125m",
                "grok_1_314b", "mistral_large_123b", "qwen2_vl_7b",
                "h2o_danube_1_8b", "olmoe_1b_7b", "whisper_small"):
        importlib.import_module(f"repro.configs.{mod}")
