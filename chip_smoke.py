#!/usr/bin/env python3
"""Bring-up check of the relay serving path on a TPU.

Runs in one process and starts no child, so the chip has one owner.

  python chip_smoke.py            # one chip
  python chip_smoke.py --chips 4  # four chips: the sharded paths only

One chip, in order:

* device  — JAX must report a TPU; any other platform exits 1 before
  anything runs (there is no CPU fallback);
* serve   — ``repro.launch.serve.main`` answers 16 requests with the RISE
  policy on the committed XL and F3 checkpoints (``results/ckpts``; a
  missing checkpoint is an error, never a reason to train);
* shapes  — every distinct arm of the legacy 11-arm space, the cascade
  space and the DAG space generates once at bucket 8 through
  ``Executor.generate_bucketed``; outputs must be finite;
* bucket  — one seed generated in bucket 1 and in bucket 8; the largest
  difference is printed, not checked.  Generation is keyed per sample, but
  the rows are not bitwise equal: the backend compiles the convolutions and
  matmuls per batch size.  The trained families differ by up to ~5e-7 on
  the CPU and by up to ~7e-3 on a TPU v5e;
* kernels — the Pallas boundary kernels (``use_kernel=True``), compiled for
  the chip, against the jnp tails at the served 8x8x4 latents and at
  128x128x16.

Four chips: ``compressed_psum`` over ("pod",), ``pipeline_apply`` over
("stage",) and ``moe_fwd(mesh=)`` over a 2x2 ("data", "model") mesh, each
against its single-device reference at the tolerances of
``tests/test_distribution_parity.py``, each output spanning four devices.

Every phase prints one ``phase ...`` line (wall seconds, backend compile
seconds).  A failed check raises, and the script exits non-zero.  The last
line of stdout is the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
CKPTS = ROOT / "results" / "ckpts"

_compile_s = [0.0]


def _count_compiles(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += duration


def _phase(name: str, fn):
    """Run one phase; print its wall and backend-compile seconds."""
    c0, t0 = _compile_s[0], time.perf_counter()
    out = fn()
    print(f"phase {name}: wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={_compile_s[0] - c0:.3f}", flush=True)
    return out


def _require_ckpts(names):
    missing = [n for n in names if not (CKPTS / f"diffusion_{n}.ckpt").exists()]
    if missing:
        raise FileNotFoundError(
            f"missing checkpoints {missing} under {CKPTS}; the smoke test "
            "serves the committed families and does not train")


def _finite(x, what: str):
    x = np.asarray(x)
    if not np.all(np.isfinite(x)):
        raise AssertionError(f"{what}: non-finite values")
    return x


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

N_REQUESTS = 16


def phase_serve():
    from repro.launch import serve

    _require_ckpts(["XL", "F3"])
    os.chdir(ROOT)  # serve loads results/ckpts relative to the checkout
    summary = serve.main(["--requests", str(N_REQUESTS), "--policy", "rise"])
    answered = sum(summary["arm_histogram"])
    if answered != N_REQUESTS:
        raise AssertionError(f"{answered} of {N_REQUESTS} requests answered")
    for k in ("total_reward", "quality_reward", "mean_latency_s",
              "p95_latency_s", "clip", "ir", "pick", "aes", "ocr"):
        _finite(summary[k], f"summary[{k!r}]")
    print(f"serve: {answered} requests answered, "
          f"total_reward={summary['total_reward']!r}")


def _families():
    from repro.diffusion.train import get_or_train_families

    _require_ckpts(["XL", "F3", "XL_mid", "F3_mid"])
    return get_or_train_families(ckpt_dir=str(CKPTS), with_mid=True)


def phase_shapes(ex):
    from repro.serving.arms import (ARMS, cascade_action_space,
                                    dag_action_space)

    seeds = np.arange(8) + 1000
    seen = {}
    for space in (ARMS, cascade_action_space(), dag_action_space()):
        for arm in space:
            seen.setdefault((arm.idx, arm.label), arm)
    for arm in seen.values():
        out = _finite(ex.generate_bucketed(arm, seeds), arm.label)
        if out.shape[0] != len(seeds):
            raise AssertionError(f"{arm.label}: {out.shape[0]} rows")
    print(f"shapes: {len(seen)} arms at bucket 8, "
          f"{ex.cache_stats()['pipelines_compiled']} pipelines compiled")


def phase_bucket(ex):
    from repro.serving.arms import ARMS

    seeds = np.arange(8) + 2000
    worst = 0.0
    for arm in (ARMS[0], ARMS[2], ARMS[8]):  # standalone, XL relay, F3 relay
        one = ex.generate_bucketed(arm, seeds[:1])[0]
        eight = ex.generate_bucketed(arm, seeds)[0]
        diff = float(np.max(np.abs(one - eight)))
        print(f"bucket: {arm.label} bucket1-vs-bucket8 max_abs_diff={diff!r}")
        worst = max(worst, diff)
    print(f"bucket: max_abs_diff={worst!r}")


def phase_kernels():
    from repro.core import boundary, samplers
    from repro.core.schedules import karras_sigmas, rf_times

    key = jax.random.PRNGKey(0)
    for kind, shape, guidance in (("ddim", (8, 8, 8, 4), 1.0),
                                  ("rf", (8, 128, 128, 16), 4.5)):
        kx, kc, ku, key = jax.random.split(key, 4)
        x = jax.random.normal(kx, shape)
        ec = jax.random.normal(kc, shape)
        eu = jax.random.normal(ku, shape)
        sig = karras_sigmas(50) if kind == "ddim" else rf_times(50)
        coeffs = samplers.step_coeffs(kind, sig, 10)
        ref = boundary.emit_fn(kind, guidance=guidance)(x, ec, eu, coeffs)["wire"]
        got = boundary.emit_fn(kind, guidance=guidance, use_kernel=True)(
            x, ec, eu, coeffs)["wire"]
        q_ref, q_got = np.asarray(ref["q"], np.int32), np.asarray(got["q"], np.int32)
        s_ref, s_got = np.asarray(ref["s"]), _finite(got["s"], "kernel scales")
        n_diff = int(np.sum(q_ref != q_got))
        q_max = int(np.max(np.abs(q_ref - q_got)))
        s_rel = float(np.max(np.abs(s_got - s_ref) / s_ref))
        lat = shape[1:]
        y_ref = boundary.consume_fn(kind, guidance=guidance)(
            ref["q"], ref["s"], ec, eu, coeffs, lat)
        y_got = boundary.consume_fn(kind, guidance=guidance, use_kernel=True)(
            ref["q"], ref["s"], ec, eu, coeffs, lat)
        y_ref, y_got = np.asarray(y_ref), _finite(y_got, "kernel consume")
        y_diff = float(np.max(np.abs(y_got - y_ref)))
        y_scale = float(np.max(np.abs(y_ref)))
        print(f"kernels: {kind} {shape} int8_differ={n_diff}/{q_ref.size} "
              f"int8_max_step={q_max} scale_max_rel_diff={s_rel!r} "
              f"latent_max_abs_diff={y_diff!r} (latent max {y_scale!r})")
        if q_max > 1 or n_diff > q_ref.size // 1000 or s_rel > 1e-5:
            raise AssertionError(f"emit kernel disagrees with the jnp tail "
                                 f"({kind} {shape})")
        if y_diff > 1e-4 * max(1.0, y_scale):
            raise AssertionError(f"consume kernel disagrees with the jnp tail "
                                 f"({kind} {shape})")


def run_one_chip():
    from repro.serving.executor import Executor

    _phase("serve", phase_serve)
    ex = Executor(_phase("load", _families))
    _phase("shapes", lambda: phase_shapes(ex))
    _phase("bucket", lambda: phase_bucket(ex))
    _phase("kernels", phase_kernels)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def _spans_four(x, what: str):
    n = len(x.sharding.device_set)
    if n != 4:
        raise AssertionError(f"{what}: output spans {n} devices, not 4")


def phase_psum():
    from repro.distributed.compression import compressed_psum
    from repro.quantization import QUANTIZERS

    mesh = jax.make_mesh((4,), ("pod",))
    base = (jnp.ones((4, 64)) * 0.1 + jnp.arange(4)[:, None] * 0.01
            + jnp.linspace(-3, 3, 64)[None] * 0.05)
    for dtype in (jnp.float32, jnp.bfloat16):
        x = base.astype(dtype)
        exact = x.astype(jnp.float32)  # identical shards: the mean is x
        for qname, qz in sorted(QUANTIZERS.items()):
            step = float(jnp.abs(qz.error(exact)).max())
            reduced, err = compressed_psum({"g": x}, mesh, quantizer=qname)
            _spans_four(reduced["g"], "compressed_psum")
            e1 = float(jnp.abs(reduced["g"] - exact).max())
            acc = reduced["g"]
            for _ in range(3):
                reduced, err = compressed_psum({"g": x}, mesh, error_state=err,
                                               quantizer=qname)
                acc = acc + reduced["g"]
            ek = float(jnp.abs(acc / 4 - exact).max())
            print(f"psum: {dtype.__name__} {qname} err1={e1!r} "
                  f"bound={step * 1.01 + 1e-6!r} err4={ek!r}")
            if e1 > step * 1.01 + 1e-6 or not (ek <= e1 / 2 + 1e-6 or e1 < 1e-6):
                raise AssertionError(f"compressed_psum {dtype.__name__} {qname}")


def phase_pipeline():
    from repro.distributed.pipeline_parallel import pipeline_apply

    n_stages, layers_per, d = 4, 3, 16
    mesh = jax.make_mesh((n_stages,), ("stage",))
    w = jax.random.normal(jax.random.PRNGKey(0),
                          (n_stages, layers_per, d, d)) / jnp.sqrt(d)
    layer_fn = lambda wp, x: jnp.tanh(x @ wp)
    for dtype, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 3e-2)):
        x = jax.random.normal(jax.random.PRNGKey(1), (6, 8, d), dtype)
        ref = x
        for s in range(n_stages):
            for layer in range(layers_per):
                ref = jax.vmap(lambda mb: layer_fn(w[s, layer].astype(dtype), mb))(ref)
        out = pipeline_apply(layer_fn, w.astype(dtype), x, mesh)
        _spans_four(out, "pipeline_apply")
        err = float(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max())
        print(f"pipeline: {dtype.__name__} max_abs_err={err!r} tol={tol!r}")
        if err >= tol:
            raise AssertionError(f"pipeline_apply {dtype.__name__}: {err}")


def phase_moe():
    from repro import configs
    from repro.configs.base import make_reduced
    from repro.models import mlp as mlp_mod

    cfg = make_reduced(configs.get_config("deepseek-v3-671b"))
    key = jax.random.PRNGKey(0)
    p = mlp_mod.init_moe(key, cfg)
    x = jax.random.normal(key, (4, 16, cfg.d_model)) * 0.5
    local, _ = mlp_mod.moe_fwd(p, cfg, x)
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    sharded, _ = jax.jit(lambda p, x: mlp_mod.moe_fwd(p, cfg, x, mesh=mesh))(p, x)
    _spans_four(sharded, "moe_fwd")
    err = float(jnp.abs(local - sharded).max())
    print(f"moe: max_abs_err={err!r} tol=0.0001")
    if err >= 1e-4:
        raise AssertionError(f"moe_fwd(mesh=): {err}")


def run_four_chips():
    if len(jax.devices()) != 4:
        raise SystemExit(f"--chips 4 needs 4 devices, JAX sees "
                         f"{len(jax.devices())}")
    # both sides of every comparison in float32: the TPU's default matmul
    # precision would put bf16 rounding into the references
    with jax.default_matmul_precision("highest"):
        _phase("psum", phase_psum)
        _phase("pipeline", phase_pipeline)
        _phase("moe", phase_moe)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX's default device is {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}", flush=True)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    jax.monitoring.register_event_duration_secs_listener(_count_compiles)
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips()
    else:
        run_one_chip()
    stats = dev.memory_stats() or {}
    print(f"total: wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={_compile_s[0]:.3f} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
