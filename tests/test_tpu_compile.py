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


def test_mmdit_attention_is_the_flash_kernel(one_chip):
    """Lowered for the chip, the F3 segment's joint attention is the Pallas
    flash kernel inside scope ``mmdit/attention``, and no (N, M) score
    tensor of the image (N = 64) or text (N = 4) queries over the 68 joint
    keys is left in the compiled program."""
    import re

    from repro.diffusion.families import NET_CONFIGS, make_family
    from repro.models import diffusion_nets as dn
    from repro.serving.executor import Executor

    def shapes(role):
        tree = jax.eval_shape(
            lambda: dn.init_net(jax.random.PRNGKey(0), NET_CONFIGS[("F3", role)]))
        return jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    fam = make_family("F3", shapes("large"), shapes("small"))
    fn = Executor({"F3": fam})._segment_fn("F3", "small", 1.0)
    cfg = NET_CONFIGS[("F3", "small")]
    n_img = cfg.latent_hw ** 2
    n_keys = n_img + cfg.text_tokens
    assert (n_img, n_keys) == (64, 68)
    x = _sds(one_chip, (2,) + tuple(fam.spec.latent_shape))
    cond = _sds(one_chip, (2, cfg.cond_dim))
    bound = _sds(one_chip, (), jnp.int32)
    hlo = fn.lower(fam.small_params, x, cond, bound, bound).compile().as_text()
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    # two blocks: image and text queries in the first, image in the last
    assert len(kernels) == 3
    for line in kernels:
        assert re.search(r"%flash_attention[.\d]* = ", line)
        assert "/mmdit/attention/" in line
    assert not re.search(rf"\[(\d+,)*({n_img}|{cfg.text_tokens}),{n_keys}\]",
                         hlo)


_SD35_BLOCKS = {  # role: (width, heads, depth, MMDiT-X layers)
    "large": (2432, 38, 1, ()),
    "medium": (1536, 24, 2, (0,)),
}


@pytest.fixture(scope="module")
def sd35_block(one_chip):
    """SD3.5's published block at 1024² and bucket 4 (2×2 patches on the
    128×128×16 latent: 4096 image + 333 text tokens; heads of 64 with
    qk-RMSNorm), compiled once per role for the chip: the compiled HLO text
    and the grids of the flash kernel's ``pallas_call``s."""
    from repro.kernels.flash_attention import kernel as km
    from repro.models import diffusion_nets as dn

    done = {}

    def compiled(role):
        if role in done:
            return done[role]
        width, heads, depth, dual = _SD35_BLOCKS[role]
        grids = []
        real = km.pl.pallas_call

        def spy(*a, **kw):
            grids.append(kw["grid"])
            return real(*a, **kw)

        km.pl.pallas_call = spy
        try:
            jax.clear_caches()  # trace the kernel call again, past jit caches
            cfg = dn.DiffNetConfig("mmdit", width=width, depth=depth,
                                   heads=heads, latent_hw=128, latent_ch=16,
                                   text_tokens=333, patch=2, qk_norm=True,
                                   dual_layers=dual)
            tree = jax.eval_shape(
                lambda: dn.init_net(jax.random.PRNGKey(0), cfg))
            params = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                                  tree)
            x = _sds(one_chip, (4, 128, 128, 16))
            cond = _sds(one_chip, (4, 16))
            t = _sds(one_chip, ())
            fn = jax.jit(lambda p, x, t, c: dn.mmdit_apply(p, x, t, c, cfg))
            hlo = fn.lower(params, x, t, cond).compile().as_text()
        finally:
            km.pl.pallas_call = real
        done[role] = hlo, grids
        return done[role]

    return compiled


@pytest.mark.parametrize("role,width,heads,depth,dual,n_kernels", [
    ("large", 2432, 38, 1, (), 1),
    ("medium", 1536, 24, 2, (0,), 4),
])
def test_sd35_block_compiles_head_grouped(sd35_block, role, width, heads,
                                          depth, dual, n_kernels):
    """SD3.5's published block at 1024² and bucket 4 (Medium's with an
    MMDiT-X layer): the joint attention is the flash kernel in scope
    ``mmdit/attention`` and the image-only attention the flash kernel in
    ``mmdit/attention_x``, each on the head-group grid, and no (N, M) score
    tensor is left.  A one-block model's text queries reach no output; the
    medium case's first block has all three calls."""
    import re

    assert _SD35_BLOCKS[role] == (width, heads, depth, dual)
    hlo, grids = sd35_block(role)
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == n_kernels
    scopes = set()
    for line in kernels:
        assert re.search(r"%flash_attention[.\d]* = ", line)
        scopes.add(re.search(r"/mmdit/(attention\w*)/", line).group(1))
    assert scopes == ({"attention", "attention_x"} if dual else {"attention"})
    assert grids and all(len(g) == 4 and g[:2] == (4, heads // 2)
                         for g in grids)
    assert not re.search(r"\[(\d+,)*(4096|333),(4429|4096)\]", hlo)


_BYTES = {"f32": 4, "s32": 4, "bf16": 2, "pred": 1}


def _entry_ops(hlo, scope):
    """(opcode, [(dtype, dims)] of its outputs) of each instruction of the
    entry computation whose ``op_name`` lies in ``scope``."""
    import re

    ops = []
    for line in hlo[hlo.index("\nENTRY"):].splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ([a-z][\w-]*)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if m and name and f"/{scope}/" in name.group(1):
            outs = [(t, [int(d) for d in dims.split(",") if d])
                    for t, dims in re.findall(r"(\w+)\[([\d,]*)\]",
                                              m.group(1))]
            ops.append((m.group(2), outs))
    return ops


@pytest.mark.parametrize("role", ["large", "medium"])
def test_sd35_qk_norm_is_one_lane_dense_pass(sd35_block, role):
    """qk-RMSNorm of the published block (heads of 64) never makes a head's
    64 values the minor dimension of a tensor: no relayout ``copy`` and no
    (…, heads, 64) tensor under ``mmdit/qk_norm``, and what its operations
    write is the bf16 operand of the attention kernel for each q and k it
    normalises plus per-head statistics (tensors no larger than one row
    aside: the scale tiled over the heads)."""
    import numpy as np

    width, heads, depth, dual = _SD35_BLOCKS[role]
    hlo, _ = sd35_block(role)
    ops = _entry_ops(hlo, "mmdit/qk_norm")
    assert ops
    assert not [op for op, _ in ops if op == "copy"]
    for _, outs in ops:
        for _, dims in outs:
            assert dims[-2:] != [heads, 64] or len(dims) < 3, dims
    written = sum(_BYTES[t] * int(np.prod(dims))
                  for op, outs in ops if op not in ("bitcast",
                                                    "get-tuple-element")
                  for t, dims in outs if np.prod(dims) > width)
    # rows of every q and k normalised: image and text streams in every
    # block and the image-only stream in MMDiT-X blocks, but the last
    # block's text queries, which reach no output
    rows = 0
    for i in range(depth):
        rows += 4 * 4096 * (4 if i in dual else 2)
        rows += 4 * 333 * (1 if i == depth - 1 else 2)
    assert rows * 2 * width <= written <= rows * (2 * width + 4 * heads)
