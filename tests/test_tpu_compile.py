"""Compiles of the main path's device programs for one described TPU v5e chip:
the MSET similarity kernel and SPRT, the flash-attention kernel and the fleet
simulator core, each at a real width. Nothing runs; the chip's compiler refuses here what
it would refuse on the chip (interpret mode cannot show that).

The topology is described inside a fixture, never at import: only one process
at a time may load the TPU library, and every test worker imports this file.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.fleet import Objective, PredictivePolicy, evaluate_candidates, jaxsim
from repro.kernels.attention import flash_attention
from repro.kernels.similarity import similarity_pallas


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("m,n,b", [(4096, 1024, 512),    # mset_scenario width
                                   (1024, 64, 4096),
                                   (300, 20, 100)])      # pads m and b
def test_similarity_kernel_compiles(one_chip, m, n, b):
    x = _spec((m, n), jnp.float32, one_chip)
    y = _spec((b, n), jnp.float32, one_chip)
    compiled = jax.jit(similarity_pallas).lower(x, y).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("T", [512, 509])             # whole blocks; a tail
def test_sprt_compiles_to_one_blocked_loop(one_chip, T):
    import re

    from repro.mset import SPRTParams
    from repro.mset.sprt import SPRT_BLOCK, _sprt_jit

    r = _spec((T, 1024), jnp.float32, one_chip)
    v = _spec((1024,), jnp.float32, one_chip)
    hlo = _sprt_jit.lower(r, v, v, p=SPRTParams()).compile().as_text()
    # the loop that carries the (T, 1024) alarms, built a block at a time
    assert re.search(rf"%while[\w.-]* = \(.*?\bpred\[{T},1024\]", hlo)
    assert re.search(rf"= pred\[{SPRT_BLOCK},1024\]", hlo)


def test_flash_attention_compiles(one_chip):
    q = _spec((1, 4096, 8, 128), jnp.bfloat16, one_chip)
    compiled = jax.jit(flash_attention).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


class _Compiled(Exception):
    """Stops a dispatch once its program has compiled."""


@pytest.mark.parametrize("fine", [False, True], ids=["coarse", "substep4"])
def test_fleet_core_compiles(one_chip, monkeypatch, fine):
    """The float64 fleet core at the simulator benchmark's cells: the
    24 x 12 x 720 headline round, and the 8 x 8 x 720 preemptive cell at
    four substeps. The core's arguments are taken from a real
    ``evaluate_candidates`` call and compiled for the described chip."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "benchmarks"))
    import sim_perf
    n_cands, n_seeds, duration_s = (sim_perf.SUBSTEP_CELL if fine
                                    else sim_perf.HEADLINE)
    ts = sim_perf.build_scenario(
        n_seeds, duration_s, "jax",
        n_substeps=sim_perf.N_SUBSTEPS if fine else 1, preemptive=fine)
    build, compiled = jaxsim._core_for, []

    def compile_only(kernel, **statics):
        core = build(kernel, **statics)

        def call(*args):
            specs = jax.tree.map(
                lambda a: _spec(np.shape(a), np.asarray(a).dtype, one_chip),
                args)
            compiled.append(core.lower(*specs).compile())
            raise _Compiled
        return call

    monkeypatch.setattr(jaxsim, "_core_for", compile_only)
    cands = PredictivePolicy.param_space().sample_lhs(n_cands,
                                                      seed=sim_perf.SEED)
    with pytest.raises(_Compiled):
        evaluate_candidates(ts, cands, Objective())
    assert len(compiled) == 1
    assert "f64" in compiled[0].as_text()
