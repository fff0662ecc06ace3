"""Arm executor: runs the actual JAX relay programs for every arm and
produces per-(prompt, arm) quality measurements via the oracles.

Generation is batched over prompts and compiled through a **shape-keyed
program cache**: each arm's :class:`RelayProgram` is lowered to a pipeline
of per-segment jitted samplers whose ladder *bounds are traced inputs*
(``lax.fori_loop``), so every arm sharing a program shape — same family,
role sequence, guidance and per-hop compression — shares one compiled
pipeline regardless of its relay step.  The legacy 11-arm space compiles 3
pipelines instead of 11 (hit rates in :meth:`Executor.cache_stats`).
Latent buffers are donated at segment boundaries on backends that support
donation (the handoff consumes the upstream latent), and the hot path
never materializes trajectory stacks (``capture_traj=False``).

**Fused boundaries** (default on): compressed handoffs flow as the int8+
scales wire payload *between* segment fns — the emitting segment's last
step writes ``(q, s)`` directly (:mod:`repro.core.boundary`) and the
consuming segment's first step reads it, so no standalone quant/dequant
dispatch (or fp16 boundary latent) sits between segments.  The pipeline
cache key gains the per-hop boundary format, and donation covers the int8
payload leaves exactly as it covered the fp16 latent."""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import boundary, samplers
from repro.core.program import (MERGE_NODE, SEGMENT_NODE, SELECT_NODE,
                                RelayGraph, RelayProgram, compile_plan,
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
                 arms: Optional[Sequence[Arm]] = None,
                 fused_boundary: bool = True):
        self.families = families
        self.arms = tuple(arms) if arms is not None else ARMS
        # fused int8 boundaries: compressed hops ride inside the segment
        # fns as the wire payload (exact payload/bytes, latents equivalent
        # per the repro.core.boundary parity contract, locked by
        # tests/test_fused_boundary.py)
        self.fused_boundary = bool(fused_boundary)
        self._pipelines = {}  # shape key -> composed program runner
        self._seg_fns = {}  # (family, role, guidance, in_q, out_q, flavor)
        self._noise_fns = {}  # (latent_shape, per_key) -> jitted noise fn
        self._hop_fns = {}  # quantizer -> jitted latent roundtrip
        self._requests = 0  # pipeline lookups (cache-hit-rate telemetry)

    def plan(self, arm: Arm):
        """Legacy two-hop plan view (None for standalone arms)."""
        return arm.plan

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
        DAG pipelines use it when a wire payload (or latent) fans out to
        more than one consumer, where donating would free buffers a later
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

    def _hop_fn(self, quantizer: str):
        if quantizer not in self._hop_fns:
            from repro.quantization import latent_roundtrip

            self._hop_fns[quantizer] = jax.jit(
                lambda x: latent_roundtrip(x, quantizer)[0],
                donate_argnums=_donate_argnums() and (0,),
            )
        return self._hop_fns[quantizer]

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
        DAG pipelines need the measured deviation to resolve Select
        bounds."""
        key = ("hopdev", quantizer)
        if key not in self._hop_fns:
            from repro.quantization import latent_roundtrip, relative_deviation

            def fn(x):
                rec, _ = latent_roundtrip(x, quantizer)
                return rec, relative_deviation(x, rec) * 100.0

            self._hop_fns[key] = jax.jit(fn)
        return self._hop_fns[key]

    def _pipeline(self, program, latent_shape, per_key: bool):
        """Composed runner for a program shape: noise → segments × handoffs.
        Segment bounds arrive as call-time int32 arguments, so programs
        sharing a shape share this runner *and* its compiled pieces.

        Accepts either plan currency: a chain :class:`RelayGraph`
        normalizes to its equivalent linear program (sharing this cache
        with legacy arms, bit-identically); a branching graph compiles via
        :meth:`_graph_pipeline` through the same per-segment/per-hop
        caches."""
        if isinstance(program, RelayGraph):
            plan = compile_plan(program)
            if plan.is_chain:
                program = plan.linear_program()
            else:
                return self._graph_pipeline(program, plan, latent_shape,
                                            per_key)
        self._requests += 1
        fused = self.fused_boundary
        # boundary-format key: per hop, whether the wire payload flows
        # fused through the segment fns or through a standalone roundtrip
        bfmt = tuple(
            ("fused" if fused else "xla", h.quantizer) if h.compress
            else ("raw", None)
            for h in program.handoffs
        )
        if fused:
            # validate before the cache lookup: segment bounds are traced,
            # so programs sharing a shape share one pipeline — every
            # concrete program must be checked, not just the first one
            for k, seg in enumerate(program.segments):
                fin = k > 0 and program.handoffs[k - 1].compress
                fout = (k < len(program.handoffs)
                        and program.handoffs[k].compress)
                if fin and fout and seg.steps < 2:
                    raise ValueError(
                        f"segment {k} of the {program.family} program has "
                        "too few steps to both consume and emit a fused "
                        "boundary (needs >= 2)"
                    )
        shape = (program.shape_key(), tuple(latent_shape), per_key, bfmt)
        if shape in self._pipelines:
            return self._pipelines[shape]
        fam = self.families[program.family]
        if (isinstance(fam, Family) and not fam.has_mid
                and any(s.model == "mid" for s in program.segments)):
            raise ValueError(
                f"family {program.family} has no trained mid-size stage — "
                f"load families with with_mid=True to run cascade programs"
            )
        noise = self._noise_fn(latent_shape, per_key)

        def _hop_q(k):  # wire quantizer of hop k when fused, else None
            hs = program.handoffs
            return (hs[k].quantizer
                    if fused and 0 <= k < len(hs) and hs[k].compress else None)

        seg_fns = [
            self._segment_fn(program.family, seg.model, seg.guidance,
                             in_q=_hop_q(k - 1), out_q=_hop_q(k))
            for k, seg in enumerate(program.segments)
        ]
        roles = [seg.model for seg in program.segments]
        hop_fns = [
            self._hop_fn(h.quantizer) if h.compress and not fused else None
            for h in program.handoffs
        ]

        def run(key, cond, bounds, steps):
            x = noise(key, cond)
            for k, (fn, role) in enumerate(zip(seg_fns, roles)):
                with spans.span(spans.SEGMENT, role=role, steps=steps[k]):
                    x = fn(role_params(fam, role), x, cond, *bounds[k])
                if k < len(hop_fns) and hop_fns[k] is not None:
                    x = hop_fns[k](x)
            return x

        self._pipelines[shape] = run
        return run

    def _graph_pipeline(self, graph: RelayGraph, plan, latent_shape,
                        per_key: bool):
        """Composed runner for a branching DAG plan.

        Node groups compile through the *same* shape-keyed caches as linear
        programs — each segment node reuses the per-(family, role, guidance)
        jitted sampler with traced bounds, hop edges the jitted wire
        roundtrips, Merge nodes a jitted k-way latent average.  Select
        resolution is eager (the accept decision is Python control flow):
        the candidate branch's Eq. 1 deviation against the reference latent
        decides which handoff survives."""
        self._requests += 1
        fused = self.fused_boundary

        # fused-boundary plan analysis (static — the plan is concrete):
        # which segment nodes emit the wire payload from their last step,
        # and which edges consume it at their dst's first step.  Runs
        # *before* the pipeline-cache lookup so the too-few-steps
        # validation covers every concrete plan sharing a shape, not just
        # the first one that compiled it.
        kind_of = {n.nid: n.kind for n in plan.nodes}
        fused_edges: set = set()
        emit_cfg: Dict[str, tuple] = {}  # nid -> (quantizer, flavor)
        if fused:
            succs = {n.nid: [] for n in plan.nodes}
            for e in plan.edge_order:
                succs[e.src].append(e)
            for n in plan.nodes:
                if n.kind != SEGMENT_NODE:
                    continue
                wire_succ = [
                    e for e in succs[n.nid]
                    if e.handoff is not None and e.handoff.compress
                    and kind_of[e.dst] == SEGMENT_NODE
                ]
                if not wire_succ:
                    continue
                q0 = wire_succ[0].handoff.quantizer
                matched = [e for e in wire_succ
                           if e.handoff.quantizer == q0]
                fused_edges.update(matched)
                need_latent = (n.nid == plan.sink
                               or len(matched) < len(succs[n.nid]))
                emit_cfg[n.nid] = (
                    q0, "wire_dev_latent" if need_latent else "wire_dev"
                )
                consumed = any(e in fused_edges for e in plan.preds[n.nid])
                if n.segment.steps < (2 if consumed else 1):
                    raise ValueError(
                        f"graph node {n.nid} has too few steps to both "
                        "consume and emit a fused boundary"
                    )

        shape = (graph.shape_key(), tuple(latent_shape), per_key, fused)
        if shape in self._pipelines:
            return self._pipelines[shape]
        fam = self.families[graph.family]
        if (isinstance(fam, Family) and not fam.has_mid
                and any(s.model == "mid" for s in graph.segments)):
            raise ValueError(
                f"family {graph.family} has no trained mid-size stage — "
                f"load families with with_mid=True to run cascade programs"
            )
        noise = self._noise_fn(latent_shape, per_key)

        n_succ = {n.nid: 0 for n in plan.nodes}
        for e in plan.edge_order:
            n_succ[e.src] += 1
        n_sources = sum(1 for n in plan.nodes if not plan.preds[n.nid])

        def _donate_ok(n):  # safe to donate this node's input buffers?
            pe = plan.preds[n.nid]
            if not pe:  # x0 is shared by every source node
                return n_sources == 1
            # the upstream output (latent or wire payload) must have no
            # other consumer — donation frees it for everyone
            return n_succ[pe[0].src] == 1

        seg_fns = {
            n.nid: self._segment_fn(
                graph.family, n.segment.model, n.segment.guidance,
                in_q=(plan.preds[n.nid][0].handoff.quantizer
                      if plan.preds[n.nid]
                      and plan.preds[n.nid][0] in fused_edges else None),
                out_q=emit_cfg.get(n.nid, (None,))[0],
                out_flavor=emit_cfg.get(n.nid, (None, "wire"))[1],
                donate=_donate_ok(n),
            )
            for n in plan.nodes if n.kind == SEGMENT_NODE
        }
        from repro.quantization import relative_deviation

        dev_fn = jax.jit(lambda a, b: relative_deviation(a, b) * 100.0)

        def run(key, cond, bounds, steps):
            out, wire, path_dev = {}, {}, {}
            x0 = noise(key, cond)
            for i, node in enumerate(plan.nodes):
                pe = plan.preds[node.nid]
                if node.kind == SEGMENT_NODE:
                    if not pe:
                        x_in, d_in = x0, 0.0
                    elif pe[0] in fused_edges:
                        # fused consume: the segment fn's first step reads
                        # the shared wire payload emitted by the src node
                        e = pe[0]
                        x_in, dev = wire[e.src]
                        d_in = max(path_dev[e.src], float(dev))
                    else:
                        e = pe[0]
                        x_in, d_in = out[e.src], path_dev[e.src]
                        if e.handoff is not None and e.handoff.compress:
                            x_in, dev = self._hop_dev_fn(e.handoff.quantizer)(
                                x_in)
                            d_in = max(d_in, float(dev))
                    role = node.segment.model
                    with spans.span(spans.SEGMENT, role=role,
                                    steps=steps[i]):
                        res = seg_fns[node.nid](
                            role_params(fam, role), x_in, cond, *bounds[i]
                        )
                    cfg = emit_cfg.get(node.nid)
                    if cfg is None:
                        out[node.nid] = res
                    else:
                        w, dev = res[0], res[1]
                        wire[node.nid] = ((w[0], w[1]), dev)
                        if cfg[1] == "wire_dev_latent":
                            out[node.nid] = res[2]
                    path_dev[node.nid] = d_in
                elif node.kind == MERGE_NODE:
                    xs = [out[e.src] for e in pe]
                    out[node.nid] = self._merge_fn(len(xs))(*xs)
                    path_dev[node.nid] = max(path_dev[e.src] for e in pe)
                else:  # SELECT_NODE
                    sel = plan.selects[node.nid]
                    ref, cand = sel.reference, sel.candidates[0]
                    dev_cand = float(dev_fn(out[ref], out[cand]))
                    base = path_dev[ref]
                    bound = select_bound_pct(node,
                                             base if base > 0.0 else 1.0)
                    winner = cand if dev_cand <= bound else ref
                    out[node.nid] = out[winner]
                    path_dev[node.nid] = path_dev[winner]
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
            if self.fused_boundary:
                # The pipeline run above traces the boundary tails *inline*
                # (inside the outer-jitted segment fns), which leaves the
                # standalone tail caches cold; fire them directly so eager
                # callers (execute_program, transports, benchmarks) find
                # them compiled too — and so the telemetry below is
                # observable at all.
                prog = arm.program
                fam = prog.family
                if fam is not None:
                    spec = self.families[fam].spec
                    if isinstance(prog, RelayGraph):
                        hoffs = [e.handoff for e in prog.edges
                                 if e.handoff is not None]
                    else:
                        hoffs = prog.handoffs
                    for qz in sorted({h.quantizer for h in hoffs
                                      if h.compress}):
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
    def _bounds(program):
        """(traced int32 bounds, host step counts) per segment; for a
        branching graph per canonical node, with placeholders for join
        nodes (positional with ``plan.nodes``)."""
        if isinstance(program, RelayGraph):
            plan = compile_plan(program)
            if plan.is_chain:
                program = plan.linear_program()
            else:
                segs = [n.segment if n.kind == SEGMENT_NODE else None
                        for n in plan.nodes]
                return (tuple((jnp.int32(g.start), jnp.int32(g.stop))
                              if g is not None else () for g in segs),
                        tuple(g.stop - g.start if g is not None else 0
                              for g in segs))
        return (tuple((jnp.int32(seg.start), jnp.int32(seg.stop))
                      for seg in program.segments),
                tuple(seg.stop - seg.start for seg in program.segments))

    def _runner(self, arm: Arm, per_key: bool):
        """The arm's composed pipeline, bound to its segment bounds: a
        callable ``(key_or_keys, cond) -> final latent``."""
        prog = arm.program
        fam = self.families[prog.family]
        run = self._pipeline(prog, fam.spec.latent_shape, per_key)
        bounds, steps = self._bounds(prog)
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
