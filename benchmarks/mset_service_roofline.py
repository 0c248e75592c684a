"""Roofline dry-run of the MSET2 surveillance SERVICE on the production pod —
the paper's own workload as a pjit'd cloud service (DESIGN.md §2).

Run inside a 512-fake-device process (the dry-run owns XLA_FLAGS):
    PYTHONPATH=src python -m benchmarks.mset_service_roofline
"""
from __future__ import annotations

import os


def main():
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
    import jax
    from repro import compile_cache
    from repro.core.cost_model import roofline
    from repro.core.hlo_analysis import analyze_compiled
    from repro.launch.mesh import make_production_mesh
    from repro.mset.service import _estimate_sharded
    from jax.sharding import NamedSharding, PartitionSpec as P
    import jax.numpy as jnp
    from functools import partial

    compile_cache.place()
    mesh = make_production_mesh()
    chips = mesh.devices.size
    print("name,us_per_call,derived")
    # customer-B-like service shard: 4096 signals, 8192 memvecs, 16384-obs window
    for (n_sig, n_mv, batch) in [(64, 512, 4096), (1024, 4096, 8192),
                                 (4096, 8192, 16384)]:
        s_D = NamedSharding(mesh, P("model", None))
        s_G = NamedSharding(mesh, P("model", None))
        s_v = NamedSharding(mesh, P(None))
        s_X = NamedSharding(mesh, P("data", None))
        fn = jax.jit(partial(_estimate_sharded, gamma=1.0, kind="inverse_distance"),
                     in_shardings=(s_D, s_G, s_v, s_v, s_X),
                     out_shardings=(s_X, s_X))
        args = (jax.ShapeDtypeStruct((n_mv, n_sig), jnp.float32),
                jax.ShapeDtypeStruct((n_mv, n_mv), jnp.float32),
                jax.ShapeDtypeStruct((n_sig,), jnp.float32),
                jax.ShapeDtypeStruct((n_sig,), jnp.float32),
                jax.ShapeDtypeStruct((batch, n_sig), jnp.float32))
        with mesh:
            compiled = fn.lower(*args).compile()
        cost = analyze_compiled(compiled, n_devices=chips)
        t = roofline(cost.flops, cost.bytes_accessed, cost.collective_bytes, chips)
        print(f"mset_service_{n_sig}sig_{n_mv}mv_{batch}obs,"
              f"{t.t_step*1e6:.1f},dom={t.dominant};"
              f"mem={cost.peak_memory_per_device/2**30:.2f}GiB;"
              f"coll={cost.collective_bytes/1e9:.2f}GB")


if __name__ == "__main__":
    main()
