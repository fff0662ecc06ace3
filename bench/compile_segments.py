#!/usr/bin/env python3
"""Compile the Executor's segment programs of each configuration for a
described TPU v5e (no chip needed) and print their memory and XLA counts:

    JAX_PLATFORMS=cpu python3 bench/compile_segments.py

One line per (configuration, role, bucket): parameters, compile seconds,
argument and temporary bytes from ``memory_analysis()``, and XLA's FLOPs
and bytes for one step (the ladder slice is one step long) beside the
benchmark's own FLOP count. Whether weights plus a bucket's temporaries fit
one chip's HBM decides the buckets a configuration may use.
"""
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402

CASES = (("sd35-relay-512", "large", 1), ("sd35-relay-512", "large", 8),
         ("sd35-relay-512", "small", 1), ("sd35-relay-512", "small", 8),
         ("sd35-relay-1024", "large", 4), ("sd35-relay-1024", "large", 8),
         ("sd35-relay-1024", "small", 4))


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    model = harness.load_module(BENCH / "configs" / "mmdit_relay.py")
    for name, role, bucket in CASES:
        cfg = json.loads((BENCH / "configs" / "sd35-relay-1024.json")
                         .read_text())
        if name == "sd35-relay-512":  # the same models at 1024 image tokens
            cfg["latent_hw"] = 32
        shapes = model.param_shapes(cfg)
        params = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one),
            shapes, is_leaf=lambda x: isinstance(x, tuple))
        system = model.System(cfg, params)
        seg = system.executor._segment_fn("F3", role, 1.0)
        lat = model.latent_shape(cfg)
        x = jax.ShapeDtypeStruct((bucket,) + lat, jnp.float32, sharding=one)
        cond = jax.ShapeDtypeStruct((bucket, cfg["cond_dim"]), jnp.float32,
                                    sharding=one)
        i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
        t0 = time.perf_counter()
        compiled = seg.lower(params[role], x, cond, i32, i32).compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        n_params = sum(int(jnp.prod(jnp.asarray(s))) for s in
                       jax.tree_util.tree_leaves(
                           shapes[role],
                           is_leaf=lambda x: isinstance(x, tuple)))
        print(json.dumps({
            "config": name, "role": role, "bucket": bucket,
            "params": n_params,
            "compile_s": round(time.perf_counter() - t0, 1),
            "args_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "xla_flops": cost.get("flops"),
            "xla_bytes": cost.get("bytes accessed"),
            "bench_flops": bucket * model.image_step_flops(cfg, role),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
