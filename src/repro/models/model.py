"""Public model API: ``build_model(cfg)`` -> Model with init/loss/prefill/decode."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import ShardingRules, is_box, unbox_values
from repro.models import transformer


@dataclass
class Model:
    cfg: ArchConfig
    ep_size: Optional[int] = None

    # ---- params ----
    def init(self, key) -> Any:
        """Boxed param tree (values + logical axes)."""
        return transformer.init_lm(self.cfg, key, self.ep_size)

    def init_values(self, key) -> Any:
        return unbox_values(self.init(key))

    def abstract_params(self) -> Any:
        """Box tree of ShapeDtypeStructs (for dry-run in_shardings), fp32."""
        boxed = jax.eval_shape(lambda k: self.init(k),
                               jax.ShapeDtypeStruct((2,), jnp.uint32))
        return boxed

    # ---- steps ----
    def loss(self, params, batch, rules: ShardingRules):
        return transformer.loss_fn(self.cfg, params, batch, rules)

    def prefill(self, params, batch, rules: ShardingRules):
        return transformer.forward_prefill(self.cfg, params, batch, rules)

    def decode_step(self, params, cache, tokens, pos, rules: ShardingRules):
        return transformer.decode_step(self.cfg, params, cache, tokens, pos, rules)

    def greedy_step(self, params, cache, tokens, pos, rules: ShardingRules):
        return transformer.greedy_step(self.cfg, params, cache, tokens, pos, rules)

    def cache_specs(self, batch: int, max_seq: int):
        return transformer.cache_specs(self.cfg, batch, max_seq)

    def init_cache(self, batch: int, max_seq: int):
        """Zero-initialized concrete cache (for serving from scratch)."""
        specs = self.cache_specs(batch, max_seq)
        return jax.tree.map(lambda b: jnp.zeros(b.value.shape, b.value.dtype),
                            specs, is_leaf=is_box)


def build_model(cfg: ArchConfig, ep_size: Optional[int] = None) -> Model:
    return Model(cfg, ep_size)
