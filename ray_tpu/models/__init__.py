"""ray_tpu.models — flagship model families, TPU-shaped.

Decoder-only LMs (GPT-2, Llama), MoE (Mixtral, OLMoE) and ViT/CLIP follow
one pattern: pytree params + logical-axis tree + scan-stacked layers.
`model_family(config)` is the one place that maps a configuration's type to
the functions that build and run its model (train/lm.py reads it).
"""

from typing import Callable, NamedTuple, Optional

from . import mixed_stack as _mixed, moe as _moe, transformer as _dense

from .configs import PRESETS, get_config  # noqa: F401
from .mixed_stack import MixedStackConfig  # noqa: F401
from .moe import (  # noqa: F401
    MoEConfig,
    mixtral_8x7b,
    moe_tiny,
    olmoe_1b_7b,
)
from .transformer import (  # noqa: F401
    TransformerConfig,
    count_params,
    decode_step,
    forward,
    init_cache,
    init_params,
    logical_axes,
    prefill,
)
from .vit import (  # noqa: F401
    CLIPConfig,
    ViTConfig,
    clip_forward,
    clip_loss,
    clip_tiny,
    init_clip_params,
    vit_b16,
    vit_l16,
    vit_tiny,
)


class ModelFamily(NamedTuple):
    init_params: Callable     # (config, key) -> params
    logical_axes: Callable    # (config) -> logical-axis tree of the params
    # (params, tokens, config, remat_saved=()) -> (hidden (B, S, E) before the
    # LM head, the routers' scalars: `router_aux_loss`,
    # `moe_load_max_over_mean`; {} for a dense model). `remat_saved` names what
    # a model with `config.remat` keeps of each block across the forward pass
    forward_hidden: Callable
    # (config, S, split, a device's tokens a step) -> what the stack's blocks cost a
    # token and which of their values a checkpoint may keep (transformer.block_costs);
    # None: a family whose blocks name none, and are recomputed whole
    block_costs: Optional[Callable]
    # (config, batch, seq of a step) -> what the family's own layers resolve to, for
    # callers that report it (LMTrainer's `train.init.step_fn` span)
    plan: Callable
    # (params, hidden, next_tokens, config, routers, remat_saved=()) -> (the hidden
    # states of the family's multi-token prediction module for the shared head,
    # `forward_hidden`'s scalars with the module's block counted in); None: a
    # family without such a module (train/lm.lm_loss asks where `config.mtp_modules`)
    mtp_hidden: Optional[Callable] = None


def _dense_hidden(params, tokens, config, remat_saved=()):
    return _dense.forward_hidden(params, tokens, config, remat_saved=remat_saved), {}


# most derived first: a MixedStackConfig is a MoEConfig is a TransformerConfig
_FAMILIES = (
    (MixedStackConfig, ModelFamily(_mixed.init_params, _mixed.logical_axes, _mixed.forward_hidden,
                                   _mixed.block_costs, _mixed.plan, _mixed.mtp_hidden)),
    (MoEConfig, ModelFamily(_moe.init_params, _moe.logical_axes, _moe.forward_hidden,
                            None, _moe.moe_plan)),
    (TransformerConfig, ModelFamily(init_params, logical_axes, _dense_hidden,
                                    _dense.block_costs, _dense.plan)),
)


def model_family(config) -> ModelFamily:
    for config_type, family in _FAMILIES:
        if isinstance(config, config_type):
            return family
    raise TypeError(f"no model family for a {type(config).__name__}")
