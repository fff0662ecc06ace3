"""Fused int8 boundary micro-benchmark: the handoff tail, fused vs unfused.

Measures exactly the work a compressed segment boundary adds around the
samplers, per latent shape:

* **unfused** — four dispatches: the producer's last sampler-step tail,
  a standalone quantize (fp latent → int8 wire), a standalone dequantize
  (wire → fp latent), the consumer's first step tail.  The boundary latent
  is fully materialized twice.
* **fused** — two dispatches through :mod:`repro.core.boundary`: the emit
  tail (step + quantize in one program) and the consume tail (dequantize +
  step in one program).  The boundary latent never round-trips through a
  standalone dispatch.

Both paths are jitted and warmed; reps are wall-clocked with
``block_until_ready`` and the median is reported.  Three gates (the
``--quick`` run is the CI stage):

1. **parity** — the fused wire payload carries the *exact* int8 ints and
   byte count of the unfused quantize, and the post-boundary latents agree
   numerically (the contract in :mod:`repro.core.boundary`).
2. **no-regression** — median fused tail time ≤ 1.1× the unfused tail
   (fusing strictly removes dispatches; the 10% headroom absorbs timer
   noise on shared CI hosts).
3. **roofline** — in the calibrated latency model the fused boundary is
   priced at wire time alone: ``handoff_seconds(fused=True) ≤ 1.1×
   wire_seconds`` per family (the ISSUE acceptance line), while the
   unfused price adds the quant/dequant HBM term it no longer pays.

  PYTHONPATH=src:. python benchmarks/bench_handoff.py [--quick]
"""
from __future__ import annotations

import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, save_json
from repro.core import boundary, samplers
from repro.quantization import (dequant_latent, payload_bytes, quant_latent)
from repro.serving import latency as lat

# (label, batched latent shape, sampler kind) — C=4 mirrors the XL wire
# rows, C=16 the F3 rows; the 128×128 rows stress the row-reduction side
SHAPES = [
    ("edge_xl", (4, 8, 8, 4), "ddim"),
    ("edge_f3", (4, 8, 8, 16), "rf"),
    ("hires_xl", (1, 128, 128, 4), "ddim"),
    ("hires_f3", (1, 128, 128, 16), "rf"),
]


def _median_ms(fn, reps):
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1e3)
    return float(statistics.median(ts))


def bench_shape(label, shape, kind, reps):
    """Time one boundary crossing at one latent shape, both paths."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], shape)
    eps = jax.random.normal(ks[1], shape) * 0.3
    eps2 = jax.random.normal(ks[2], shape) * 0.3
    coeffs = jnp.asarray([0.5, 0.7] if kind == "ddim" else [-0.04, 0.0],
                         jnp.float32)
    latent_shape = shape[-3:]

    # ---- unfused: step | quant | dequant | step (4 dispatches) ----------
    step = jax.jit(lambda x, e, c: samplers.step_update(kind, x, e, c),
                   static_argnums=())
    quant = jax.jit(lambda x: quant_latent(x, "rowwise")[0])
    deq = jax.jit(lambda qs: dequant_latent(qs, latent_shape))

    def unfused():
        out = step(x, eps, coeffs)
        qs = quant(out)
        rec = deq(qs)
        return step(rec, eps2, coeffs), qs

    # ---- fused: emit | consume (2 dispatches) ---------------------------
    emit_t = boundary.emit_fn(kind)
    cons_t = boundary.consume_fn(kind)

    def fused():
        w = emit_t(x, eps, eps, coeffs)["wire"]
        return cons_t(w["q"], w["s"], eps2, eps2, coeffs, latent_shape), w

    # warm both, then lock parity before timing anything
    (xu, qs_u), (xf, w_f) = unfused(), fused()
    np.testing.assert_array_equal(np.asarray(w_f["q"]), np.asarray(qs_u["q"]))
    assert payload_bytes(w_f) == payload_bytes(qs_u)
    np.testing.assert_allclose(np.asarray(xf), np.asarray(xu),
                               rtol=3e-5, atol=3e-5)

    t_unf = _median_ms(lambda: jax.block_until_ready(unfused()[0]), reps)
    t_fus = _median_ms(lambda: jax.block_until_ready(fused()[0]), reps)
    row = {
        "label": label, "shape": list(shape), "kind": kind,
        "payload_bytes": payload_bytes(w_f),
        "unfused_ms": t_unf, "fused_ms": t_fus,
        "speedup": t_unf / t_fus if t_fus > 0 else float("inf"),
    }
    emit(f"handoff_{label}_unfused", t_unf * 1e3, f"{shape}")
    emit(f"handoff_{label}_fused", t_fus * 1e3,
         f"{shape} speedup={row['speedup']:.2f}x")
    return row


def roofline_rows():
    """The latency-model gate: a fused compressed boundary costs wire time
    alone, per family — deterministic, so CI noise can't flip it."""
    rows = []
    for fam in ("XL", "F3"):
        wire = lat.wire_seconds(fam, compressed=True)
        fused = lat.handoff_seconds(fam, 0.0, compressed=True, fused=True)
        unfused = lat.handoff_seconds(fam, 0.0, compressed=True, fused=False)
        rows.append({
            "family": fam, "wire_s": wire, "fused_s": fused,
            "unfused_s": unfused, "fused_over_wire": fused / wire,
        })
        assert fused <= 1.1 * wire, (
            f"{fam}: fused boundary {fused:.6f}s > 1.1x wire {wire:.6f}s"
        )
        assert unfused > fused  # the HBM term fusion removes
    return rows


def main(quick: bool):
    reps = 30 if quick else 200
    shapes = SHAPES[:2] if quick else SHAPES
    rows = [bench_shape(lb, sh, kd, reps) for lb, sh, kd in shapes]
    for r in rows:
        assert r["fused_ms"] <= 1.1 * r["unfused_ms"], (
            f"{r['label']}: fused tail {r['fused_ms']:.3f}ms regressed past "
            f"1.1x unfused {r['unfused_ms']:.3f}ms"
        )
    roof = roofline_rows()
    data = {"reps": reps, "tails": rows, "roofline": roof}
    path = save_json("bench_handoff_quick" if quick else "bench_handoff",
                     data)
    med = statistics.median([r["speedup"] for r in rows])
    print(f"handoff_summary,median_speedup={med:.2f}x,"
          f"roofline_max={max(r['fused_over_wire'] for r in roof):.3f},"
          f"saved={path}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main("--quick" in sys.argv[1:])
