"""Model configuration dataclasses.

A model is a stack of *groups*; each group is a repeating *pattern* of layer
specs, scanned over the repeat axis (`lax.scan` with stacked params).  This
uniformly expresses dense stacks (pattern of 1), gemma2's local/global
alternation (pattern of 2), zamba2's Mamba-with-shared-attention hybrid
(pattern of 6 with a weight-shared slot), xLSTM's mLSTM/sLSTM mix and the
VLM's periodic cross-attention layers.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    d_expert: int = 0                 # expert hidden dim (0 -> use d_ff)
    num_shared: int = 0               # dense "shared" experts (DeepSeek-MoE)
    capacity_factor: float = 1.25
    group_size: int = 2048            # dispatch-group tokens (see moe.py)
    router: str = "softmax"           # 'softmax' | 'sigmoid' (DeepSeek-V3)
    router_bias: bool = False         # aux-loss-free bias update (DSv3)
    aux_loss_weight: float = 0.0


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    rope_head_dim: int = 64
    nope_head_dim: int = 128
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    d_state: int = 64
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64                # mamba2 SSD head dim
    chunk: int = 256


@dataclasses.dataclass(frozen=True)
class XLSTMConfig:
    proj_factor: float = 2.0          # mLSTM up-projection
    slstm_proj_factor: float = 1.3334
    chunk: int = 256


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One slot in a group pattern.

    kind: 'attn' | 'mla' | 'mamba2' | 'mlstm' | 'slstm' | 'cross_attn'
          | 'none' (pure-MLP layer)
    mlp:  'glu' | 'moe' | 'none'
    """
    kind: str = "attn"
    mlp: str = "glu"
    window: int = 0                   # >0 -> sliding-window attention
    shared: bool = False              # weight-shared across group repeats
    post_norms: bool = False          # gemma2-style post-block RMSNorm
    qk_norm: bool = False


@dataclasses.dataclass(frozen=True)
class GroupSpec:
    pattern: tuple[LayerSpec, ...]
    repeat: int


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    groups: tuple[GroupSpec, ...]
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    # attention extras
    rope_theta: float = 10000.0
    attn_softcap: float = 0.0
    final_softcap: float = 0.0
    attn_scale: float = 0.0           # 0 -> 1/sqrt(head_dim)
    # sub-configs
    moe: MoEConfig | None = None
    mla: MLAConfig | None = None
    mamba: MambaConfig | None = None
    xlstm: XLSTMConfig | None = None
    # embedding / head
    tie_embeddings: bool = True
    scale_embed: bool = False         # gemma multiplies embeds by sqrt(d)
    num_codebooks: int = 0            # musicgen: parallel codebook streams
    # modality frontend stubs
    vision_dim: int = 0               # >0 -> expects precomputed image embeds
    num_image_tokens: int = 0
    # numerics / training
    activation: str = "silu"
    gated_mlp: bool = True            # GLU (False -> plain 2-matrix MLP)
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    remat: str = "full"               # 'none' | 'full' | 'dots'
    # distribution policy (consumed by repro.parallel.sharding)
    fsdp: bool = False                # shard big weight dims over 'data' too
    # sub-quadratic? (controls long_500k applicability)
    subquadratic: bool = False

    @property
    def num_layers(self) -> int:
        return sum(len(g.pattern) * g.repeat for g in self.groups)

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def param_count(self) -> int:
        """Analytic parameter count (embeddings included once if tied)."""
        import numpy as np
        from repro.models import model as model_lib
        shapes = model_lib.abstract_params(self)
        import jax
        return int(sum(np.prod(x.shape) for x in jax.tree.leaves(shapes)))

    def active_param_count(self) -> int:
        """Params active per token (MoE counts top_k+shared experts only)."""
        total = self.param_count()
        if self.moe is None:
            return total
        import numpy as np
        import jax
        from repro.models import model as model_lib
        shapes = model_lib.abstract_params(self)
        flat = jax.tree.flatten_with_path(shapes)[0]
        inactive = 0
        for path, leaf in flat:
            keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
            if "experts" in keys:
                frac = 1.0 - (self.moe.top_k / self.moe.num_experts)
                inactive += int(np.prod(leaf.shape) * frac)
        return total - inactive


def uniform_groups(n_layers: int, spec: LayerSpec) -> tuple[GroupSpec, ...]:
    return (GroupSpec(pattern=(spec,), repeat=n_layers),)
