"""Public model facade: ``build_model(cfg)`` binds a config to the
functional model in ``lm``."""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax

from repro.configs.base import ModelConfig
from repro.models import lm

Params = Dict[str, Any]


class Model(NamedTuple):
    cfg: ModelConfig
    init: Callable[[jax.Array], Params]
    apply: Callable[..., Tuple[jax.Array, jax.Array, Optional[Params]]]
    prefill: Callable[..., Tuple[jax.Array, Params]]
    decode_step: Callable[..., Tuple[jax.Array, Params]]
    init_cache: Callable[[int, int], Params]


def build_model(cfg: ModelConfig) -> Model:
    def init(key):
        return lm.init_params(key, cfg)

    def apply(params, batch, *, mode="train", cache=None):
        return lm.apply(params, cfg, batch, mode=mode, cache=cache)

    def prefill(params, batch, cache):
        logits, _, new_cache = lm.apply(params, cfg, batch, mode="prefill",
                                        cache=cache)
        return logits, new_cache

    def decode_step(params, tokens, cache, extras=None):
        batch = {"tokens": tokens}
        if extras:
            batch.update(extras)
        logits, _, new_cache = lm.apply(params, cfg, batch, mode="decode",
                                        cache=cache)
        return logits[:, -1], new_cache

    def init_cache(batch_size, max_len):
        return lm.init_cache(cfg, batch_size, max_len)

    return Model(cfg, init, apply, prefill, decode_step, init_cache)

