"""Step-function builders shared by the trainer, the server, and the dry-run.

Everything here is mesh-agnostic: the callables close over an ArchConfig and a
ShardingRules; jit in/out shardings are derived from the logical axes of the
abstract param/cache trees.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec
from repro.distributed.sharding import ShardingRules, unbox_values
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.optim import adamw


def batch_sharding(rules: ShardingRules, specs: dict):
    """NamedSharding tree for an input-spec dict: dim0 = batch, rest replicated."""
    if rules.mesh is None:
        return None
    out = {}
    for k, v in specs.items():
        if v.shape == ():
            out[k] = NamedSharding(rules.mesh, P())
        else:
            out[k] = rules.sharding_for(("batch",) + (None,) * (len(v.shape) - 1),
                                        v.shape)
    return out


def cast_tree(tree, dtype):
    def one(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            if isinstance(x, jax.ShapeDtypeStruct):
                return jax.ShapeDtypeStruct(x.shape, jnp.dtype(dtype))
            return x.astype(dtype)
        return x
    return jax.tree.map(one, tree)


class StepBuilder:
    def __init__(self, cfg: ArchConfig, rules: ShardingRules,
                 n_microbatches: int = 1, opt: Optional[AdamWConfig] = None):
        self.cfg = cfg
        self.rules = rules
        self.model = build_model(cfg, ep_size=self._ep_size())
        self.n_microbatches = n_microbatches
        self.opt = opt or AdamWConfig()

    def _ep_size(self) -> Optional[int]:
        if self.rules.mesh is None:
            return None
        sizes = dict(zip(self.rules.mesh.axis_names, self.rules.mesh.devices.shape))
        return sizes.get("model")

    # -------------------- abstract trees + shardings --------------------
    def abstract_params(self, dtype=None):
        boxed = self.model.abstract_params()
        vals = unbox_values(boxed)
        if dtype is not None:
            vals = cast_tree(vals, dtype)
        return vals, boxed

    def param_shardings(self, boxed):
        return self.rules.tree_shardings(boxed)

    def abstract_opt_state(self, params_abs):
        return jax.eval_shape(adamw.init, params_abs)

    def opt_shardings(self, param_shardings):
        zero = NamedSharding(self.rules.mesh, P()) if self.rules.mesh else None
        return adamw.AdamWState(step=zero, mu=param_shardings, nu=param_shardings)

    def cache_abstract(self, shape: ShapeSpec):
        boxed = self.model.cache_specs(shape.global_batch, shape.seq_len)
        return unbox_values(boxed), boxed

    def cache_shardings(self, boxed):
        return self.rules.tree_shardings(boxed)

    # -------------------------- step functions --------------------------
    def train_step_fn(self):
        cfg, rules, model = self.cfg, self.rules, self.model
        from repro.optim.grad_accum import microbatched_value_and_grad

        def loss(params, batch):
            l, metrics = model.loss(params, batch, rules)
            return l, metrics

        vg = microbatched_value_and_grad(loss, self.n_microbatches)
        optc = self.opt

        def train_step(params, opt_state, batch):
            (l, metrics), grads = vg(params, batch)
            new_params, new_opt, om = adamw.update(optc, grads, opt_state, params)
            return new_params, new_opt, dict(metrics, loss=l, **om)

        return train_step

    def prefill_fn(self):
        cfg, rules, model = self.cfg, self.rules, self.model

        def prefill(params, batch):
            return model.prefill(params, batch, rules)

        return prefill

    def decode_fn(self, greedy: bool = False):
        cfg, rules, model = self.cfg, self.rules, self.model
        step = model.greedy_step if greedy else model.decode_step

        def decode(params, cache, tokens, pos):
            return step(params, cache, tokens, pos, rules)

        return decode

    # ------------------------ jitted + sharded forms --------------------
    def jit_train_step(self, donate: bool = True):
        _, boxed = self.abstract_params()
        ps = self.param_shardings(boxed)
        os_ = self.opt_shardings(ps)
        rep = NamedSharding(self.rules.mesh, P()) if self.rules.mesh else None
        metrics_sh = None if rep is None else jax.tree.map(
            lambda _: rep, {"nll": 0, "z_loss": 0, "moe_aux": 0, "loss": 0,
                            "grad_norm": 0, "lr": 0})
        kw = {}
        if self.rules.mesh is not None:
            kw = dict(in_shardings=(ps, os_, None),
                      out_shardings=(ps, os_, metrics_sh))
        return jax.jit(self.train_step_fn(),
                       donate_argnums=(0, 1) if donate else (), **kw)

    def jit_grad_step(self):
        """value_and_grad only (no optimizer) — used by the dry-run cost probes."""
        _, boxed = self.abstract_params()
        ps = self.param_shardings(boxed)
        model, rules = self.model, self.rules

        def grad_step(params, batch):
            def loss(p, b):
                return model.loss(p, b, rules)
            (l, metrics), grads = jax.value_and_grad(loss, has_aux=True)(params, batch)
            return grads, l

        kw = {}
        if self.rules.mesh is not None:
            rep = NamedSharding(self.rules.mesh, P())
            kw = dict(in_shardings=(ps, None), out_shardings=(ps, rep))
        return jax.jit(grad_step, **kw)

    def jit_decode_step(self, shape: ShapeSpec, donate: bool = True,
                        greedy: bool = False):
        """The jitted decode step, the cache donated by default. With
        ``greedy`` it is ``Model.greedy_step``: it also returns the next
        tokens, picked on the device, and a report for the host."""
        _, cboxed = self.cache_abstract(shape)
        cs = self.cache_shardings(cboxed)
        _, pboxed = self.abstract_params()
        ps = self.param_shardings(pboxed)
        kw = {}
        if self.rules.mesh is not None:
            rep = NamedSharding(self.rules.mesh, P())
            logits_sh = self.rules.sharding_for(
                ("batch", None, "act_vocab"),
                (shape.global_batch, 1, self.cfg.vocab_size))
            kw = dict(in_shardings=(ps, cs, None, rep),
                      out_shardings=(cs, logits_sh) + ((rep, rep) if greedy
                                                       else ()))
        return jax.jit(self.decode_fn(greedy),
                       donate_argnums=(1,) if donate else (), **kw)

    def jit_prefill(self, shape: ShapeSpec):
        _, pboxed = self.abstract_params()
        ps = self.param_shardings(pboxed)
        kw = {}
        if self.rules.mesh is not None:
            # cache out-shardings resolved from the PREFILL-length cache tree
            pre_len = shape.seq_len
            cboxed = self.model.cache_specs(shape.global_batch, pre_len)
            cs = self.rules.tree_shardings(cboxed)
            logits_sh = self.rules.sharding_for(
                ("batch", None, "act_vocab"),
                (shape.global_batch, 1, self.cfg.vocab_size))
            kw = dict(in_shardings=(ps, None), out_shardings=(cs, logits_sh))
        return jax.jit(self.prefill_fn(), **kw)
