"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts: blocks that overflow
VMEM, slices not aligned to the tiling.  Each test lowers a served program
for one chip of a described ``v5e:2x2`` and compiles it, so those faults
show here instead of on the chip.  Nothing runs; nothing is timed.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library, and every test-runner worker imports
this file.  All such compiles stay in this one file for the same reason.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


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


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kind,shape", [
    ("ddim", (1, 8, 8, 4)),
    ("ddim", (8, 8, 8, 4)),
    ("ddim", (8, 128, 128, 4)),
    ("rf", (8, 128, 128, 16)),
], ids=["8x8x4-b1", "8x8x4-b8", "128x128x4-b8", "128x128x16-b8"])
def test_boundary_kernels_compile(one_chip, kind, shape):
    """The Pallas emit and consume boundary kernels compile for the chip at
    the served 8x8 latents and at the 128x128 latents the latency model
    assumes (block rows sized from the VMEM budget)."""
    from repro.core import boundary

    b, h, w, c = shape
    lat = _sds(one_chip, shape)
    coeffs = _sds(one_chip, (2,))
    emit = boundary.emit_fn(kind, guidance=4.5, use_kernel=True)
    hlo = emit.lower(lat, lat, lat, coeffs).compile().as_text()
    assert "tpu_custom_call" in hlo

    q = _sds(one_chip, (b, c, h * w), jnp.int8)
    s = _sds(one_chip, (b, c, 1))
    consume = boundary.consume_fn(kind, guidance=4.5, use_kernel=True)
    hlo = consume.lower(q, s, lat, lat, coeffs,
                        latent_shape=(h, w, c)).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_quant_kernels_compile(one_chip):
    """The standalone int8 row kernels at the served wire layout (bucket 8
    of 8x8x4 latents: 32 rows of 64)."""
    from repro.kernels.quant.ops import dequant_int8, quant_int8

    rows = _sds(one_chip, (32, 64))
    hlo = quant_int8.lower(rows).compile().as_text()
    assert "tpu_custom_call" in hlo
    q = _sds(one_chip, (32, 64), jnp.int8)
    s = _sds(one_chip, (32, 1))
    hlo = dequant_int8.lower(q, s).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("arm_idx", [2, 8], ids=["XL-large", "F3-large"])
def test_executor_segment_fn_compiles(one_chip, arm_idx):
    """The edge segment of a relay arm (large role) as the Executor jits it
    for that arm, at bucket 8, with parameter shapes from the net's
    initializer."""
    from repro.diffusion.families import NET_CONFIGS, make_family
    from repro.models import diffusion_nets as dn
    from repro.serving.arms import ARMS
    from repro.serving.executor import Executor

    prog = ARMS[arm_idx].program
    seg, hop = prog.segments[0], prog.handoffs[0]
    assert seg.model == "large"
    name = prog.family

    def shapes(role):
        tree = jax.eval_shape(
            lambda: dn.init_net(jax.random.PRNGKey(0), NET_CONFIGS[(name, role)]))
        return jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    fam = make_family(name, shapes("large"), shapes("small"))
    ex = Executor({name: fam})
    fn = ex._segment_fn(name, "large", seg.guidance,
                        out_q=hop.quantizer if hop.compress else None)
    cfg = NET_CONFIGS[(name, "large")]
    x = _sds(one_chip, (8,) + tuple(fam.spec.latent_shape))
    cond = _sds(one_chip, (8, cfg.cond_dim))
    bound = _sds(one_chip, (), jnp.int32)
    compiled = fn.lower(fam.large_params, x, cond, bound, bound).compile()
    assert compiled.memory_analysis() is not None
