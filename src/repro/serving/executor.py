"""Arm executor: runs the actual JAX relay programs for every arm and
produces per-(prompt, arm) quality measurements via the oracles.

Generation is batched over prompts and compiled through a **shape-keyed
program cache**.  Every arm's program, linear or DAG, becomes one
:class:`~repro.core.program.CompiledPlan` (``compile_plan(as_graph(p))``),
and one runner walks it: segment nodes call per-segment jitted samplers
whose ladder *bounds are traced inputs* (``lax.fori_loop``), so every arm
sharing a plan shape — same family, role sequence, guidance and per-hop
compression — shares one compiled pipeline regardless of its relay step.
The legacy 11-arm space compiles 3 pipelines instead of 11 (hit rates in
:meth:`Executor.cache_stats`).  Latent buffers are donated at segment
boundaries on backends that support donation (the handoff consumes the
upstream latent), and the hot path never materializes trajectory stacks
(``capture_traj=False``).

**Fused boundaries**: compressed handoffs flow as the int8+scales wire
payload *between* segment fns — the emitting segment's last step writes
``(q, s)`` directly (:mod:`repro.core.boundary`) and the consuming
segment's first step reads it, so no standalone quant/dequant dispatch
(or fp16 boundary latent) sits between segments.  Which edges fuse, and
what each emitter returns, is :func:`repro.core.program.fuse_boundaries`,
the plan the eager coordinators of :mod:`repro.core.relay` use too.
Donation covers the int8 payload leaves exactly as it covers the fp16
latent.  Only a plan with a Select node reads a hop's deviation on the
host; any other plan dispatches with no device-to-host transfer."""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import boundary, samplers
from repro.core.program import (MERGE_NODE, SEGMENT_NODE, SELECT_NODE,
                                as_graph, compile_plan, fuse_boundaries,
                                select_bound_pct)
from repro.diffusion import synth
from repro.diffusion.families import Family, role_fn, role_params
from repro.serving import metrics
from repro.serving.arms import ARMS, Arm
from repro.serving.obs import spans


@jax.jit
def request_keys(base_seed, seeds):
    """One PRNG key per request, ``fold_in(PRNGKey(base_seed), seed)``, in
    one device program."""
    base = jax.random.PRNGKey(base_seed)
    return jax.vmap(lambda s: jax.random.fold_in(base, s))(seeds)


def _donate_argnums():
    """Donate the latent at segment boundaries where the backend supports
    it (donation is a no-op warning on CPU)."""
    return (1,) if jax.default_backend() in ("gpu", "tpu") else ()


class Executor:
    """Compiled relay-program runner with shape-keyed compile caches.

    Segments, noise generators and latent handoff round-trips each jit
    once per shape signature (family/role/guidance, latent shape, bucket
    size), so serving any request mix costs a bounded number of XLA
    compiles.  Determinism contract: generation is keyed by request
    seeds (``PRNGKey(seed·7919 + arm.idx)``), so the same (seeds, arm)
    pair always yields the same images, independent of batch
    composition — the property the partial-batch re-execution path
    (``generate_bucketed(..., subset=...)``) relies on."""

    def __init__(self, families: Dict[str, Family],
                 arms: Optional[Sequence[Arm]] = None):
        self.families = families
        self.arms = tuple(arms) if arms is not None else ARMS
        self._plans = {}  # program -> (plan, fused boundaries, bounds, steps)
        self._pipelines = {}  # shape key -> composed program runner
        self._seg_fns = {}  # (family, role, guidance, in_q, out_q, flavor)
        self._noise_fns = {}  # (latent_shape, per_key) -> jitted noise fn
        self._hop_fns = {}  # merge and wire-roundtrip jits
        self._requests = 0  # pipeline lookups (cache-hit-rate telemetry)

    # ------------------------------------------------------------------
    # shape-keyed compile cache
    # ------------------------------------------------------------------

    def _noise_fn(self, shape, per_key: bool):
        key = (tuple(shape), per_key)
        if key not in self._noise_fns:
            if per_key:
                # per-sample PRNG keys: each sample's initial noise depends
                # only on its own key, so outputs are invariant to the
                # pad-to-bucket batch shape (a batched draw from one key
                # would change every sample whenever the bucket changes)
                def noise(keys, cond):
                    return jax.vmap(
                        lambda k: jax.random.normal(k, tuple(shape)))(keys)
            else:
                def noise(key, cond):
                    return jax.random.normal(
                        key, (cond.shape[0],) + tuple(shape))
            self._noise_fns[key] = jax.jit(noise)
        return self._noise_fns[key]

    def _segment_fn(self, family: str, role: str, guidance: float,
                    in_q: Optional[str] = None, out_q: Optional[str] = None,
                    out_flavor: str = "wire", donate: bool = True):
        """One jitted sampler per (family, role, guidance, boundary format):
        the ladder slice bounds are traced int32 inputs, so every relay
        step of a family reuses this single compiled segment.

        ``in_q`` / ``out_q`` name the wire quantizer of a fused boundary on
        the segment's input / output side (None = plain latent).  With
        ``in_q`` the latent argument is the ``(q, s)`` payload — donated
        exactly like the fp16 latent was, the int8 buffers are consumed by
        the boundary step — and the segment's first step reads it.  With
        ``out_q`` the segment's last step emits the payload; ``out_flavor``
        picks what rides along (``repro.core.boundary.EMIT_FLAVORS``):
        "wire" returns ``(q, s)``, "wire_dev" appends the Eq. 1 deviation,
        "wire_dev_latent" also the stepped latent (DAG nodes with mixed
        consumers).  ``donate=False`` keeps the input buffers alive — the
        runner uses it when a wire payload (or latent) fans out to more
        than one consumer, where donating would free buffers a later
        branch still reads.  The program is named ``segment_<role>``, so
        the profiler's trace tells the roles apart."""
        key = (family, role, guidance, in_q, out_q,
               out_flavor if out_q else None, donate)
        if key not in self._seg_fns:
            fam = self.families[family]
            net = role_fn(fam, role)
            kind = fam.spec.kind
            latent_shape = tuple(fam.spec.latent_shape)
            sigmas = fam.spec.ladder(role)
            sample = samplers.sampler_for(kind)

            def fn(params, x, cond, start, stop):
                if in_q:
                    q, s = x
                    x = boundary.dequant_step(
                        kind, net, params, {"q": q, "s": s}, latent_shape,
                        sigmas, start, cond, None, guidance, quantizer=in_q,
                    )
                    start = start + 1
                if out_q:
                    x, _ = sample(
                        net, params, x, sigmas, cond, start=start,
                        stop=stop - 1, guidance=guidance, capture_traj=False,
                    )
                    res = boundary.quant_step(
                        kind, net, params, x, sigmas, stop - 1, cond, None,
                        guidance, quantizer=out_q, flavor=out_flavor,
                    )
                    w = (res["wire"]["q"], res["wire"]["s"])
                    if out_flavor == "wire":
                        return w
                    if out_flavor == "wire_dev":
                        return w, res["dev_pct"]
                    return w, res["dev_pct"], res["latent"]
                out, _ = sample(
                    net, params, x, sigmas, cond, start=start, stop=stop,
                    guidance=guidance, capture_traj=False,
                )
                return out

            fn.__name__ = fn.__qualname__ = f"segment_{role}"
            self._seg_fns[key] = jax.jit(
                fn, donate_argnums=_donate_argnums() if donate else ()
            )
        return self._seg_fns[key]

    def _merge_fn(self, k: int):
        """Jitted latent average over ``k`` branch inputs (Merge nodes)."""
        key = ("merge", k)
        if key not in self._hop_fns:
            self._hop_fns[key] = jax.jit(
                lambda *xs: sum(xs[1:], xs[0]) / float(len(xs))
            )
        return self._hop_fns[key]

    def _hop_dev_fn(self, quantizer: str):
        """Jitted wire roundtrip that also returns the Eq. 1 deviation —
        for compressed edges that do not fuse; the runner reads the
        deviation only where a Select bound needs it."""
        key = ("hopdev", quantizer)
        if key not in self._hop_fns:
            from repro.quantization import latent_roundtrip, relative_deviation

            def fn(x):
                rec, _ = latent_roundtrip(x, quantizer)
                return rec, relative_deviation(x, rec) * 100.0

            self._hop_fns[key] = jax.jit(fn)
        return self._hop_fns[key]

    def _pipeline(self, plan, fb, latent_shape, per_key: bool):
        """Composed runner for a plan shape: noise, then the plan's nodes in
        canonical order.  Segment bounds arrive as call-time int32
        arguments, so plans sharing a shape share this runner *and* its
        compiled pieces; a chain graph keys as its linear program, so
        legacy arms and their chain twins share one runner.

        Segment nodes reuse the per-(family, role, guidance, boundary)
        jitted sampler, fused edges (``fb``, the plan's
        :func:`fuse_boundaries`) hand the wire payload from the
        emitter's last step to the consumer's first, other compressed edges
        take the jitted wire round trip, Merge nodes a jitted k-way latent
        average.  Select resolution is eager (the accept decision is Python
        control flow): the candidate branch's Eq. 1 deviation against the
        reference latent decides which handoff survives.  Only then does
        the runner read hop deviations on the host; otherwise fused
        emitters return the bare payload."""
        self._requests += 1
        shape = (plan.graph.shape_key(), tuple(latent_shape), per_key)
        if shape in self._pipelines:
            return self._pipelines[shape]
        family = plan.graph.family
        fam = self.families[family]
        segs = {n.nid: n.segment for n in plan.nodes
                if n.kind == SEGMENT_NODE}
        if (isinstance(fam, Family) and not fam.has_mid
                and any(s.model == "mid" for s in segs.values())):
            raise ValueError(
                f"family {family} has no trained mid-size stage — "
                f"load families with with_mid=True to run cascade programs"
            )
        noise = self._noise_fn(latent_shape, per_key)
        track = bool(plan.selects)  # path deviations feed a Select bound
        # what each fused emitter returns beside the payload
        flavor = {nid: "wire_dev_latent" if need_latent
                  else "wire_dev" if track else "wire"
                  for nid, (_, need_latent) in fb.emits.items()}
        seg_fns = {}
        for nid, seg in segs.items():
            pe = plan.preds[nid]
            seg_fns[nid] = self._segment_fn(
                family, seg.model, seg.guidance,
                in_q=(pe[0].handoff.quantizer
                      if pe and pe[0] in fb.edges else None),
                out_q=fb.emits[nid][0] if nid in fb.emits else None,
                out_flavor=flavor.get(nid, "wire"),
                # x0 feeds the one source; an upstream output may be
                # donated only if no other branch still reads it
                donate=not pe or len(plan.succs[pe[0].src]) == 1,
            )
        if track:
            from repro.quantization import relative_deviation

            dev_fn = jax.jit(lambda a, b: relative_deviation(a, b) * 100.0)

        def run(key, cond, bounds, steps):
            out, wire, path_dev = {}, {}, {}
            x0 = noise(key, cond)
            for i, node in enumerate(plan.nodes):
                nid, pe = node.nid, plan.preds[node.nid]
                if node.kind == MERGE_NODE:
                    xs = [out[e.src] for e in pe]
                    out[nid] = self._merge_fn(len(xs))(*xs)
                    if track:
                        path_dev[nid] = max(path_dev[e.src] for e in pe)
                    continue
                if node.kind == SELECT_NODE:
                    sel = plan.selects[nid]
                    ref, cand = sel.reference, sel.candidates[0]
                    dev_cand = float(dev_fn(out[ref], out[cand]))
                    base = path_dev[ref]
                    bound = select_bound_pct(node,
                                             base if base > 0.0 else 1.0)
                    winner = cand if dev_cand <= bound else ref
                    out[nid] = out[winner]
                    path_dev[nid] = path_dev[winner]
                    continue
                hop_dev = None
                if not pe:
                    x = x0
                elif pe[0] in fb.edges:
                    # fused consume: the segment fn's first step reads the
                    # wire payload emitted by the src node
                    x, hop_dev = wire[pe[0].src]
                else:
                    x = out[pe[0].src]
                    h = pe[0].handoff
                    if h is not None and h.compress:
                        x, hop_dev = self._hop_dev_fn(h.quantizer)(x)
                if track:
                    d = path_dev[pe[0].src] if pe else 0.0
                    path_dev[nid] = (d if hop_dev is None
                                     else max(d, float(hop_dev)))
                role = node.segment.model
                with spans.span(spans.SEGMENT, role=role, steps=steps[i]):
                    res = seg_fns[nid](role_params(fam, role), x, cond,
                                       *bounds[i])
                f = flavor.get(nid)
                if f is None:
                    out[nid] = res
                elif f == "wire":
                    wire[nid] = (res, None)
                else:
                    wire[nid] = res[:2]
                    if f == "wire_dev_latent":
                        out[nid] = res[2]
            return out[plan.sink]

        self._pipelines[shape] = run
        return run

    def warm(self, buckets=(1,)) -> Dict[str, float]:
        """JIT pre-fire: run every arm once at the smallest bucket so the
        pipelines, segment fns and fused boundary tails all compile before
        the first real request (the serving runtime calls this off the hot
        path).  Returns :meth:`cache_stats` afterwards — the warm-path
        tests assert the boundary telemetry is populated here and
        *unchanged* after the first real request."""
        for arm in self.arms:
            self.generate_bucketed(arm, np.asarray([0]),
                                   buckets=tuple(buckets))
            # The pipeline run above traces the boundary tails *inline*
            # (inside the outer-jitted segment fns), which leaves the
            # standalone tail caches cold; fire them directly so eager
            # callers (execute_program, transports, benchmarks) find them
            # compiled too — and so the telemetry below is observable.
            plan = self._compiled(arm.program)[0]
            spec = self.families[plan.graph.family].spec
            for qz in sorted({e.handoff.quantizer for e in plan.edge_order
                              if e.handoff is not None
                              and e.handoff.compress}):
                boundary.warm(spec.latent_shape, quantizer=qz)
        return self.cache_stats()

    def cache_stats(self) -> Dict[str, float]:
        """Shape-cache telemetry: how many distinct compiled pipelines back
        the requested arm programs (the dedup the shape key buys), plus the
        fused-boundary tail caches (``repro.core.boundary``) the segment
        fns compile through."""
        bstats = boundary.cache_stats()
        return {
            "pipeline_requests": self._requests,
            "pipelines_compiled": len(self._pipelines),
            "segment_fns_compiled": len(self._seg_fns),
            "noise_fns_compiled": len(self._noise_fns),
            "boundary_fns_cached": len(bstats),
            "boundary_traces_compiled": sum(
                v for v in bstats.values() if v > 0
            ),
            "cache_hit_rate": (
                1.0 - len(self._pipelines) / self._requests
                if self._requests else 0.0
            ),
        }

    # ------------------------------------------------------------------
    # generation
    # ------------------------------------------------------------------

    @staticmethod
    def _bounds(plan):
        """(traced int32 bounds, host step counts) per canonical node of
        the plan, with placeholders for join nodes (positional with
        ``plan.nodes``; for a chain, the segment order)."""
        segs = [n.segment for n in plan.nodes]
        return (tuple((jnp.int32(g.start), jnp.int32(g.stop))
                      if g is not None else () for g in segs),
                tuple(g.steps if g is not None else 0 for g in segs))

    def _compiled(self, program):
        """``(plan, fused boundaries, bounds, steps)`` of a program,
        memoized per program.  :func:`fuse_boundaries` validates each
        concrete program here: the bounds are traced, so the pipeline
        cache alone would check only the first program of a shape."""
        hit = self._plans.get(program)
        if hit is None:
            plan = compile_plan(as_graph(program))
            hit = self._plans[program] = (plan, fuse_boundaries(plan),
                                          *self._bounds(plan))
        return hit

    def _runner(self, arm: Arm, per_key: bool):
        """The arm's composed pipeline, bound to its segment bounds: a
        callable ``(key_or_keys, cond) -> final latent``."""
        plan, fb, bounds, steps = self._compiled(arm.program)
        fam = self.families[plan.graph.family]
        run = self._pipeline(plan, fb, fam.spec.latent_shape, per_key)
        return lambda key_or_keys, cond: run(key_or_keys, cond, bounds,
                                             steps)

    def generate(self, arm: Arm, seeds: np.ndarray) -> np.ndarray:
        """Run the arm's full program for a batch sharing one PRNG key
        (keyed off ``seeds[0]``); returns the decoded images as a numpy
        array.  Prefer :meth:`generate_bucketed` for serving paths."""
        family = arm.family or "XL"
        _, _, cond = synth.batch(seeds, family)
        key = jax.random.PRNGKey(int(seeds[0]) * 7919 + arm.idx)
        return np.asarray(
            self._runner(arm, per_key=False)(key, jnp.asarray(cond))
        )

    def generate_bucketed(self, arm: Arm, seeds: np.ndarray,
                          buckets=(1, 2, 4, 8), subset=None) -> np.ndarray:
        """Pad-to-bucket batched generation: the runtime aggregator's
        contract that each arm compiles at most ``len(buckets)`` programs
        regardless of micro-batch size (fewer still, now that arms sharing
        a program shape share compiled pipelines).  Per-sample PRNG keys
        (folded from each seed) make every sample's output identical
        whichever bucket its micro-batch lands in; padded slots re-run the
        last seed and are sliced off.  "Identical" holds bitwise for
        elementwise denoisers only: the backend compiles the convolutions
        and matmuls of real nets differently per batch size, so the
        trained families differ between bucket 1 and bucket 8 by up to
        ~5e-7 on the CPU and up to ~7e-3 on a TPU v5e (``chip_smoke.py``).

        ``subset`` — optional indices into ``seeds``: partial-batch
        re-execution, the straggler re-issue path.  Only the selected
        samples re-run (padded to their own, usually smaller, bucket), and
        because seeding is per-key the returned rows are bit-identical to
        the corresponding rows of the full call — a twin replica can
        re-run just a micro-batch's stragglers without perturbing their
        outputs.

        Three host spans (:mod:`repro.serving.obs.spans`) tile the call in
        the profiler's trace: ``executor.prepare`` up to the pipeline's
        call, ``executor.dispatch`` for the noise and segment calls, and
        ``executor.fetch`` for the wait and the copy to the host."""
        from repro.serving.runtime.batching import bucketize

        with spans.span(spans.PREPARE):
            seeds = np.asarray(seeds)
            if subset is not None:
                idx = np.asarray(subset, dtype=np.intp)
                if idx.size == 0:
                    raise ValueError("empty subset: nothing to re-execute")
                seeds = seeds[idx]
            n = len(seeds)
            b = bucketize(n, tuple(sorted(buckets)))
            if b > n:
                seeds = np.concatenate([seeds, np.repeat(seeds[-1:], b - n)])
            family = arm.family or "XL"
            _, _, cond = synth.batch(seeds, family)
            keys = request_keys(arm.idx * 7919, jnp.asarray(seeds, jnp.int32))
            cond = jnp.asarray(cond)
            run = self._runner(arm, per_key=True)
        with spans.span(spans.DISPATCH):
            out = run(keys, cond)
        with spans.span(spans.FETCH):
            return np.asarray(out)[:n]

    def quality_table(self, seeds: np.ndarray, arms=None) -> np.ndarray:
        """(N, n_arms) array of metric dicts — precomputed for the event sim
        and the offline policy training.  ``arms`` may restrict which
        columns are filled but must be a subset of this executor's action
        space (columns are indexed by ``arm.idx``)."""
        arms = arms if arms is not None else self.arms
        bad = [a.label for a in arms if a.idx >= len(self.arms)]
        if bad:
            raise ValueError(
                f"arms outside this executor's {len(self.arms)}-arm action "
                f"space: {bad} — construct the Executor with those arms"
            )
        prompts = [synth.sample_prompt(int(s)) for s in seeds]
        table = np.empty((len(seeds), len(self.arms)), dtype=object)
        for arm in arms:
            gen = self.generate(arm, seeds)
            for i, p in enumerate(prompts):
                table[i, arm.idx] = metrics.quality_metrics(gen[i], p)
        return table
