"""One MMDiT block of each benchmark configuration, at its real width and
token count and at the configuration's largest bucket, compiled for a
described TPU v5e with no chip attached: the compiler refuses here what it
would refuse on the chip. The whole-segment memory compiles are
``bench/compile_segments.py``.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test-runner worker imports
this file.
"""
import bench_paths  # noqa: F401  (the benchmark and src on the path)
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import harness

BENCH = Path(__file__).resolve().parents[1]
MODEL = harness.load_module(BENCH / "configs" / "mmdit_relay.py")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.mark.parametrize("name", ["sd35-relay-512", "sd35-relay-1024"])
@pytest.mark.parametrize("role", ["large", "small"])
def test_one_block_compiles(one_chip, name, role):
    from repro.models.diffusion_nets import mmdit_apply

    cfg = json.loads((BENCH / "configs" / "sd35-relay-1024.json").read_text())
    if name == "sd35-relay-512":  # the same models at 1024 image tokens
        cfg.update(latent_hw=32, buckets=[1, 2, 4, 8])
    shapes = MODEL.param_shapes(cfg)[role]
    shapes["layers"] = shapes["layers"][:1]
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip),
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    b = max(cfg["buckets"])
    x = jax.ShapeDtypeStruct((b,) + MODEL.latent_shape(cfg), jnp.float32,
                             sharding=one_chip)
    cond = jax.ShapeDtypeStruct((b, cfg["cond_dim"]), jnp.float32,
                                sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = jax.jit(mmdit_apply).lower(params, x, t, cond).compile()
    mem = compiled.memory_analysis()
    width = shapes["patch"][1]
    assert compiled.as_text()
    # the block's weights are arguments, less the text stream's output
    # projection and MLP, which reach no output in a last block
    assert mem.argument_size_in_bytes > 27 * width * width * 4
    # the block's temporaries fit beside all weights of both roles
    assert mem.temp_size_in_bytes < 16 * 2 ** 30 - 10e9
