"""MoE dispatch: gather path exactness, EP shard_map path equivalence (8 fake
devices, subprocess), capacity/dropping semantics."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed import make_rules
from repro.models import moe

RULES = make_rules(None)


def _setup(E=8, k=2, T=32, d=16, ff=32, cap=64.0):
    cfg = get_config("olmoe-1b-7b", smoke=True).replace(
        n_experts=E, n_experts_per_tok=k, moe_d_ff=ff, d_model=d,
        capacity_factor=cap)
    p = moe.init_moe(cfg, jax.random.PRNGKey(0))
    from repro.distributed.sharding import unbox_values
    return cfg, unbox_values(p)


def _dense_reference(cfg, p, x):
    """Compute-every-expert reference (exact, no dropping)."""
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    logits = xf @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    topv, topi = jax.lax.top_k(probs, cfg.n_experts_per_tok)
    topv = topv / topv.sum(-1, keepdims=True)
    y = jnp.zeros_like(xf)
    for e in range(cfg.n_experts):
        h = xf @ p["w_up"][e]
        g = xf @ p["w_gate"][e]
        o = (jax.nn.silu(g) * h) @ p["w_down"][e]
        w_e = jnp.where(topi == e, topv, 0.0).sum(-1)
        y = y + o * w_e[:, None]
    return y.reshape(B, S, d)


def test_gather_path_matches_dense_reference():
    cfg, p = _setup()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y, aux = moe._moe_gather(cfg, p, x, RULES)
    ref = _dense_reference(cfg, p, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5, rtol=1e-4)
    assert float(aux) > 0


def test_gather_path_drops_over_capacity():
    cfg, p = _setup(cap=0.25)  # tiny capacity -> drops
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y, _ = moe._moe_gather(cfg, p, x, RULES)
    ref = _dense_reference(cfg, p, x)
    # some tokens dropped -> outputs differ, but remain finite
    assert np.isfinite(np.asarray(y)).all()
    assert not np.allclose(np.asarray(y), np.asarray(ref))


def test_expert_padding():
    cfg = get_config("granite-moe-3b-a800m", smoke=True).replace(n_experts=10)
    assert moe.padded_experts(cfg, 4) == 12
    assert moe.padded_experts(cfg, None) == 10
    p = moe.init_moe(cfg, jax.random.PRNGKey(0), ep_size=4)
    from repro.distributed.sharding import unbox_values
    pv = unbox_values(p)
    assert pv["w_up"].shape[0] == 12
    assert pv["router"].shape[1] == 10       # router never selects pads


EP_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.distributed import make_rules
from repro.distributed.sharding import unbox_values
from repro.models import moe

mesh = jax.make_mesh((2, 4), ("data", "model"))
rules = make_rules(mesh)
cfg = get_config("olmoe-1b-7b", smoke=True).replace(
    n_experts=8, n_experts_per_tok=2, moe_d_ff=32, d_model=16,
    capacity_factor=64.0)
p = unbox_values(moe.init_moe(cfg, jax.random.PRNGKey(0), ep_size=4))
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16))
with mesh:
    y_ep, aux_ep = jax.jit(lambda p, x: moe._moe_ep(cfg, p, x, rules))(p, x)
y_ref, aux_ref = moe._moe_gather(cfg, p, x, make_rules(None))
err = float(jnp.max(jnp.abs(y_ep - y_ref)))
print("ERR", err)
assert err < 1e-4, err
"""


@pytest.mark.slow
def test_ep_shard_map_matches_gather_subprocess():
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", EP_SNIPPET], capture_output=True,
                         text=True, env=env, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ERR" in out.stdout
