"""Names on the device path, as the JAX profiler's trace records them.

``Executor.generate_bucketed`` writes three host spans that tile each call
(``executor.prepare``, ``executor.dispatch``, ``executor.fetch``) and one
``executor.segment`` per segment call; each segment program is a jit named
``segment_<role>``; the denoisers, the sampler update and the boundary
tails carry named scopes in the compiled program's metadata. None of this
may change an output bit (the executor's outputs are locked by
``tests/test_program_ir.py`` and ``tests/test_fused_boundary.py``).
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.diffusion.families import NET_CONFIGS, make_family
from repro.models.diffusion_nets import init_net
from repro.serving import obs
from repro.serving.arms import ARMS, cascade_action_space
from repro.serving.executor import Executor, request_keys
from repro.serving.obs import spans

F3_RELAY = next(a for a in ARMS if a.label == "sd35L+M@s=15")
XL_RELAY = next(a for a in ARMS if a.family == "XL" and a.relay_step == 15)
SEEDS = np.asarray([3, 11, 7])


def _family(name, with_mid=False):
    key = jax.random.PRNGKey(0)
    params = {role: init_net(jax.random.fold_in(key, i),
                             NET_CONFIGS[(name, role)])
              for i, role in enumerate(("large", "small", "mid"))}
    return make_family(name, params["large"], params["small"],
                       params["mid"] if with_mid else None)


@pytest.fixture(scope="module")
def executor():
    return Executor({"F3": _family("F3"), "XL": _family("XL", True)},
                    arms=cascade_action_space())


def _host_events(trace_dir):
    """(name, start_ns, end_ns, stats) of every host event of the trace."""
    from pathlib import Path

    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_span_is_a_shared_no_op_without_a_profiler():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    a = spans.span(spans.SEGMENT, role="large", steps=15)
    assert a is spans.span(spans.PREPARE) is obs.span(spans.FETCH)
    with a:
        pass


@pytest.mark.parametrize("arm", [F3_RELAY, XL_RELAY],
                         ids=["f3_relay", "xl_relay"])
def test_spans_tile_generate_bucketed(executor, arm, tmp_path):
    executor.generate_bucketed(arm, SEEDS, buckets=(4,))  # compile first
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test.call"):
            out = executor.generate_bucketed(arm, SEEDS, buckets=(4,))
    assert out.shape[0] == len(SEEDS)
    events = _host_events(tmp_path)
    (call,) = [e for e in events if e[0] == "test.call"]
    phases = sorted((e for e in events if e[0] in
                     (spans.PREPARE, spans.DISPATCH, spans.FETCH)),
                    key=lambda e: e[1])
    assert [e[0] for e in phases] == [spans.PREPARE, spans.DISPATCH,
                                      spans.FETCH]
    for (_, _, end, _), (_, start, _, _) in zip(phases, phases[1:]):
        assert end <= start
    assert call[1] <= phases[0][1] and phases[-1][2] <= call[2]
    length = call[2] - call[1]
    assert sum(e[2] - e[1] for e in phases) >= 0.99 * length

    segments = sorted((e for e in events if e[0] == spans.SEGMENT),
                      key=lambda e: e[1])
    assert [(s[3]["role"], s[3]["steps"]) for s in segments] == [
        (seg.model, seg.stop - seg.start) for seg in arm.program.segments]
    dispatch = phases[1]
    assert all(dispatch[1] <= s[1] and s[2] <= dispatch[2]
               for s in segments)


def test_segment_spans_of_a_cascade(executor, tmp_path):
    arm = next(a for a in executor.arms if a.family == "XL"
               and len(a.program.segments) == 3)
    executor.generate_bucketed(arm, SEEDS, buckets=(4,))
    with jax.profiler.trace(str(tmp_path)):
        executor.generate_bucketed(arm, SEEDS, buckets=(4,))
    segments = sorted((e for e in _host_events(tmp_path)
                       if e[0] == spans.SEGMENT), key=lambda e: e[1])
    assert [(s[3]["role"], s[3]["steps"]) for s in segments] == [
        (seg.model, seg.stop - seg.start) for seg in arm.program.segments]
    assert [s[3]["role"] for s in segments] == ["large", "mid", "small"]


def _op_names(executor, family, role):
    """The ``op_name`` paths in the lowered text of a segment program."""
    fam = executor.families[family]
    params = getattr(fam, f"{role}_params")
    x = jnp.zeros((2,) + tuple(fam.spec.latent_shape), jnp.float32)
    cond = jnp.zeros((2, 16), jnp.float32)
    fn = executor._segment_fn(family, role, 1.0)
    text = fn.lower(params, x, cond, jnp.int32(0), jnp.int32(3)).as_text(
        debug_info=True)
    return set(re.findall(r'loc\("(jit\([^"]+)"', text))


@pytest.mark.parametrize("role", ["large", "small"])
def test_segment_programs_are_named_by_role(executor, role):
    names = {n for n in _op_names(executor, "F3", role) if "/" in n}
    assert names
    assert all(n.startswith(f"jit(segment_{role})/") for n in names)
    for scope in ("mmdit/embed/", "mmdit/adaln/", "mmdit/qkv/",
                  "mmdit/attention/", "mmdit/attn_out/", "mmdit/mlp/",
                  "mmdit/final/", "sampler_update/"):
        assert any(scope in n for n in names), scope
    # the scores and values of the joint attention sit under its scope
    assert any("mmdit/attention/" in n and "dot_general" in n
               for n in names)


def test_unet_stages_are_scoped(executor):
    names = {n for n in _op_names(executor, "XL", "mid") if "/" in n}
    assert all(n.startswith("jit(segment_mid)/") for n in names)
    for scope in ("unet/embed/", "unet/stem/", "unet/down/", "unet/mid/",
                  "unet/up/", "unet/out/", "sampler_update/"):
        assert any(scope in n for n in names), scope


def test_noise_is_named(executor):
    fn = executor._noise_fn((8, 8, 4), True)
    keys = request_keys(7, jnp.asarray([1, 2], jnp.int32))
    assert fn.lower(keys, jnp.zeros((2, 16))).as_text(
        dialect="hlo").startswith("HloModule jit_noise,")


def test_request_keys_equal_the_eager_fold_in():
    seeds = np.asarray([0, 5, 2**31 - 1, 123456], np.int64)
    for base in (0, 8 * 7919, 14 * 7919):
        eager = jax.vmap(
            lambda s: jax.random.fold_in(jax.random.PRNGKey(base), s))(
                jnp.asarray(seeds, jnp.int32))
        np.testing.assert_array_equal(
            request_keys(base, jnp.asarray(seeds, jnp.int32)), eager)
