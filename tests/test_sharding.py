"""Sharding rules resolution + an 8-fake-device dry-run in a subprocess."""
import json
import os
import subprocess
import sys

import pytest

from repro.distributed.sharding import ShardingRules


class FakeMesh:
    """Duck-typed mesh for rule resolution (no jax devices needed)."""
    def __init__(self, sizes: dict):
        self.axis_names = tuple(sizes)
        import numpy as np
        self.devices = np.zeros(tuple(sizes.values()))


def rules(sizes):
    return ShardingRules(FakeMesh(sizes))


def test_basic_resolution():
    r = rules({"data": 4, "model": 4})
    spec = r.spec_for(("embed", "mlp"), (512, 2048))
    assert tuple(spec) == ("data", "model")


def test_indivisible_dim_falls_back_to_replicated():
    r = rules({"data": 4, "model": 16})
    # kv_heads=1 can't shard over model=16 -> replicated
    spec = r.spec_for(("embed", "kv_heads", "head_dim"), (512, 1, 128))
    assert tuple(spec) == ("data",)


def test_mesh_axis_used_once():
    r = rules({"data": 4, "model": 4})
    spec = r.spec_for(("heads", "mlp"), (16, 2048))  # both map to model
    assert tuple(spec) == ("model",)


def test_pod_axis_tuple():
    r = rules({"pod": 2, "data": 4, "model": 4})
    spec = r.spec_for(("batch", None, None), (64, 128, 256))
    assert spec[0] == ("pod", "data")


def test_missing_pod_axis_dropped():
    r = rules({"data": 4, "model": 4})
    spec = r.spec_for(("batch",), (64,))
    assert spec[0] == "data"


def test_constrain_noop_without_mesh():
    import jax.numpy as jnp
    r = ShardingRules(None)
    x = jnp.ones((4, 4))
    assert r.constrain(x, ("batch", None)) is x


DRYRUN_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, json
from repro.launch.dryrun import lower_cell
from repro.core.hlo_analysis import analyze_compiled
from repro.core.catalog import auto_mesh
mesh = auto_mesh((2, 4), ("data", "model"))
from repro.configs import get_config
from repro.configs import base as cfgbase
cfgbase.SHAPES["train_4k"] = cfgbase.ShapeSpec("train_4k", "train", 256, 8)
with mesh:
    lowered, aux = lower_cell("{arch}", "train_4k", mesh, n_microbatches=2,
                              cfg_base=get_config("{arch}", smoke=True))
    compiled = lowered.compile()
    cost = analyze_compiled(compiled, n_devices=8)
    print(json.dumps({{"flops": cost.flops, "coll": cost.collective_bytes,
                       "mem": cost.peak_memory_per_device,
                       "kinds": cost.collectives.bytes_by_kind}}))
"""


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["minitron-4b", "olmoe-1b-7b", "mamba2-130m"])
def test_dryrun_8device_subprocess(arch):
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", DRYRUN_SNIPPET.format(arch=arch)],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)), timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["flops"] > 0
    assert rec["coll"] > 0, "SPMD lowering must produce collectives"
    assert rec["mem"] > 0
