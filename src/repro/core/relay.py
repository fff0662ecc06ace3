"""Relay inference (paper §III), generalized to N-hop cascades.

The paper's mechanism: the large edge model runs the first s denoising
steps, the intermediate latent is handed to the small device model (start
step s' by sigma matching, Eq. 4), which finishes refinement.  Training-free
— the only requirement is a shared latent space within the family and
noise-level continuity at the handoff.  Nothing in that argument is
two-hop-specific, so the execution engine here folds over an arbitrary
:class:`repro.core.program.RelayProgram` — e.g. a 3-hop L→M→S cascade —
applying Eq. 4 sigma matching and Eq. 1-style deviation accounting *per
hop*.  :func:`relay_generate` remains the two-segment convenience wrapper.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Mapping, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import samplers
from repro.core.program import (MERGE_NODE, ROLES, SEGMENT_NODE, SELECT_NODE,
                                CompiledPlan, FusedBoundaries, Handoff,
                                RelayGraph, RelayProgram, RelaySegment,
                                as_graph, compile_plan, fuse_boundaries,
                                linear_graph, phase_name, select_bound_pct)
from repro.core.schedules import sigma_match


@dataclass(frozen=True)
class FamilySpec:
    """One relay family: models sharing a latent space, keyed by role.

    The classic pair is (large, small); families may also carry a mid-size
    ladder (``sigmas_mid``) for L→M→S cascades."""

    name: str  # "XL" (UNet/DDIM/Karras) or "F3" (MMDiT/RF/linear)
    kind: str  # "ddim" | "rf"
    sigmas_edge: jnp.ndarray  # noise ladder of M_L (length T_e+1)
    sigmas_device: jnp.ndarray  # noise ladder of M_S (length T_d+1)
    latent_shape: tuple = (8, 8, 4)
    sigmas_mid: Optional[jnp.ndarray] = None  # ladder of M_mid (cascades)

    @property
    def t_edge(self) -> int:
        return len(self.sigmas_edge) - 1

    @property
    def t_device(self) -> int:
        return len(self.sigmas_device) - 1

    @property
    def t_mid(self) -> int:
        if self.sigmas_mid is None:
            raise ValueError(f"family {self.name} has no mid-size ladder")
        return len(self.sigmas_mid) - 1

    def ladder(self, role: str) -> jnp.ndarray:
        """Sigma ladder of a model role ("large" | "mid" | "small")."""
        if role not in ROLES:
            raise KeyError(f"unknown model role {role!r}; expected one of {ROLES}")
        if role == "large":
            return self.sigmas_edge
        if role == "small":
            return self.sigmas_device
        if self.sigmas_mid is None:
            raise ValueError(f"family {self.name} has no mid-size ladder")
        return self.sigmas_mid


@dataclass(frozen=True)
class RelayPlan:
    """Two-hop view of a relay: the first handoff of a two-segment program
    (kept as the paper-facing Eq. 4 vocabulary)."""

    family: str
    s: int  # edge handoff step
    s_prime: int  # device start step (sigma-matched)
    sigma_handoff: float
    sigma_resume: float

    @property
    def noise_gap(self) -> float:
        return abs(self.sigma_handoff - self.sigma_resume)


def make_relay_plan(spec: FamilySpec, s: int) -> RelayPlan:
    """Sigma-match the handoff (Eq. 4).  For identical linear schedules this
    resolves to s'=s; for Karras 50→25 ladders it is a genuine argmin."""
    sp = sigma_match(spec.sigmas_edge, s, spec.sigmas_device)
    return RelayPlan(
        family=spec.name,
        s=s,
        s_prime=sp,
        sigma_handoff=float(spec.sigmas_edge[s]),
        sigma_resume=float(spec.sigmas_device[sp]),
    )


def plan_view(program: RelayProgram) -> Optional[RelayPlan]:
    """The legacy two-hop plan of a program's *first* hop (None for a
    standalone one-segment program)."""
    if program.n_segments < 2:
        return None
    return RelayPlan(
        family=program.family,
        s=program.segments[0].stop,
        s_prime=program.segments[1].start,
        sigma_handoff=program.handoffs[0].sigma_out,
        sigma_resume=program.handoffs[0].sigma_in,
    )


def _sampler(kind: str):
    return samplers.sampler_for(kind)


def execute_program(
    spec: FamilySpec,
    program: RelayProgram,
    models: Mapping[str, Tuple[Callable, object]],
    x_init: jnp.ndarray,
    cond,
    *,
    uncond=None,
    capture_traj: bool = False,
    fused_boundary: bool = False,
):
    """Fold the latent through a program's segments, handing off between
    models with Eq. 4 noise continuity and per-hop Eq. 1-style deviation
    accounting.

    ``models`` maps each segment's role to ``(fn, params)``; ``cond`` (and
    ``uncond``) may be a single array shared by every segment or a dict
    keyed by role.  Compressed hops serialize the latent through the
    registered int8 quantizer — the downstream model resumes from the
    *dequantized* latent, exactly what the wire would deliver.

    ``fused_boundary`` routes compressed hops through
    :mod:`repro.core.boundary`: the emitting segment's last step writes the
    int8+scales wire payload in one fused dispatch and the consuming
    segment's first step reads it — exact byte counts and payload ints,
    numerically equivalent latents and deviations (the parity contract in
    :mod:`repro.core.boundary`, locked by ``tests/test_fused_boundary.py``),
    and the fp16 boundary latent is never materialized between the step
    and the wire.
    Incompatible with ``capture_traj`` (the fused steps are not part of
    the recorded scans); fused hop dicts carry ``x_out=None``.  A 1-step
    segment cannot both consume and emit fused (its only step can't be
    two boundary steps) — that program shape raises.  Which hops fuse is
    :func:`repro.core.program.fuse_boundaries` of the program's chain.

    Returns ``(x_final, info)``.  ``info`` carries per-segment trajectories
    (``trajs``, when ``capture_traj``), per-hop dicts (``hops``: latent,
    bytes-on-wire, deviation percentage, sigmas) and the totals the legacy
    API exposed (``transfer_bytes``, ``handoff_deviation_pct`` — the worst
    hop)."""
    if fused_boundary and capture_traj:
        raise ValueError(
            "fused_boundary is incompatible with capture_traj: boundary "
            "steps run outside the recorded scan"
        )
    sample = _sampler(spec.kind)
    fuse_out = [False] * program.n_segments  # segment k emits fused
    if fused_boundary:
        plan = compile_plan(linear_graph(program))
        emits = fuse_boundaries(plan).emits
        fuse_out = [nid in emits for nid in plan.order]

    def _for(role, v):
        return v[role] if isinstance(v, dict) else v

    x = x_init
    pending = None  # (wire payload, quantizer) emitted by the previous hop
    trajs = []
    hops = []
    total_bytes = 0
    worst_dev = jnp.zeros(())
    for k, seg in enumerate(program.segments):
        fn, params = models[seg.model]
        sigmas = spec.ladder(seg.model)
        seg_cond = _for(seg.model, cond)
        seg_uncond = _for(seg.model, uncond) if uncond is not None else None
        lo, hi = seg.start, seg.stop
        if pending is not None:
            # fused consume: the first step reads the wire payload
            from repro.core import boundary

            qs, pq = pending
            x = boundary.dequant_step(
                spec.kind, fn, params, qs, spec.latent_shape, sigmas,
                lo, seg_cond, seg_uncond, seg.guidance, quantizer=pq,
            )
            pending = None
            lo = lo + 1
        if fuse_out[k]:
            hi = hi - 1
        x, traj = sample(
            fn, params, x, sigmas, seg_cond,
            start=lo, stop=hi,
            uncond=seg_uncond,
            guidance=seg.guidance, capture_traj=capture_traj,
        )
        trajs.append(traj)
        if k == program.n_hops:
            break
        # ---- handoff: latent transferred to the next segment's pool
        # (noise continuity via sigma matching; shared latent space).
        # Optionally int8-quantized for the wire, in which case the next
        # model sees the round-tripped latent.
        h = program.handoffs[k]
        x_out = x
        if fuse_out[k]:
            # fused emit: the segment's last step writes the wire payload
            from repro.core import boundary

            res = boundary.quant_step(
                spec.kind, fn, params, x, sigmas, hi, seg_cond, seg_uncond,
                seg.guidance, quantizer=h.quantizer, flavor="wire_dev",
            )
            pending = (res["wire"], h.quantizer)
            nbytes = res["bytes"]
            dev = res["dev_pct"]
            x_out = None  # never materialized — that's the point
        elif h.compress:
            from repro.quantization import latent_roundtrip, relative_deviation

            rec, nbytes = latent_roundtrip(x, h.quantizer)
            dev = relative_deviation(x, rec) * 100.0
            x = rec
        else:
            nbytes = int(np.prod(x.shape)) * x.dtype.itemsize
            dev = jnp.zeros(())
        total_bytes += nbytes
        worst_dev = jnp.maximum(worst_dev, dev)
        hops.append({
            "x_out": x_out,
            "transfer_bytes": nbytes,
            "deviation_pct": dev,
            "sigma_out": h.sigma_out,
            "sigma_in": h.sigma_in,
        })
    info = {
        "trajs": trajs,
        "hops": hops,
        "segment_steps": [seg.steps for seg in program.segments],
        "phases": [phase_name(program, k) for k in range(program.n_segments)],
        "transfer_bytes": total_bytes,
        "handoff_deviation_pct": worst_dev,
    }
    return x, info


def execute_graph(
    spec: FamilySpec,
    graph: "RelayGraph | CompiledPlan",
    models: Mapping[str, Tuple[Callable, object]],
    x_init: jnp.ndarray,
    cond,
    *,
    uncond=None,
    capture_traj: bool = False,
    fused_boundary: bool = False,
):
    """The flow coordinator: execute a DAG plan over real latents.

    Walks the compiled plan in canonical topological order — each ready
    node's input latent is resolved from its predecessor edges (hop edges
    round-trip through the wire quantizer with Eq. 1 deviation accounting,
    exactly as :func:`execute_program` does per hop), ``Merge`` nodes
    average their incoming branch latents, and ``Select`` nodes measure the
    candidate branch's Eq. 1 deviation against the reference branch and
    keep the candidate iff it is within the node's bound.  The coordinator
    is eager, so the reference branch is always *computed* (it is the
    measurement baseline); cancellation on acceptance is a scheduling
    concern that lives in the serving engines.

    A chain graph performs the identical op sequence as
    :func:`execute_program` on the bridged program — bit-identical latents
    (property-tested in ``tests/test_dag.py``).

    With ``fused_boundary`` compressed hop edges into segment nodes route
    through :mod:`repro.core.boundary`: a branch point with compressed
    out-edges emits the wire payload once from its last step (shared by
    every same-quantizer consumer — it is the same payload the unfused
    path would compute per edge), and each consuming segment's first step
    reads it.  Nodes whose other consumers need the latent (joins, the
    sink, mixed edges) keep it alongside the payload; byte accounting is
    exact vs the unfused walk and the latents follow the parity contract
    in :mod:`repro.core.boundary`.  Which edges fuse and what each emitter
    keeps is :func:`repro.core.program.fuse_boundaries`.  Incompatible
    with ``capture_traj``.

    Returns ``(x_final, info)``; ``info`` mirrors the linear coordinator
    (``trajs``/``hops``/``transfer_bytes``/``handoff_deviation_pct`` over
    the *surviving* path) plus ``joins`` — one dict per join node with the
    winning predecessor and, for selects, the measured candidate deviation
    and the accept decision."""
    if fused_boundary and capture_traj:
        raise ValueError(
            "fused_boundary is incompatible with capture_traj: boundary "
            "steps run outside the recorded scan"
        )
    plan = graph if isinstance(graph, CompiledPlan) else compile_plan(as_graph(graph))
    sample = _sampler(spec.kind)

    def _for(role, v):
        return v[role] if isinstance(v, dict) else v

    fb = (fuse_boundaries(plan) if fused_boundary
          else FusedBoundaries(frozenset(), {}))

    out: dict = {}  # nid -> output latent
    wire: dict = {}  # nid -> (payload, dev_pct, bytes) of a fused emit
    path_dev: dict = {}  # nid -> worst hop deviation on the path into nid
    path_bytes: dict = {}  # nid -> wire bytes on the path into nid
    trajs, hops, joins = [], [], []

    def _cross(edge, x):
        """Deliver a latent across an edge, round-tripping hop edges."""
        if edge.handoff is None or not edge.handoff.compress:
            nbytes = int(np.prod(x.shape)) * x.dtype.itemsize
            if edge.handoff is None:
                nbytes = 0  # zero-cost continuation / join input
            return x, nbytes, jnp.zeros(())
        from repro.quantization import latent_roundtrip, relative_deviation

        rec, nbytes = latent_roundtrip(x, edge.handoff.quantizer)
        dev = relative_deviation(x, rec) * 100.0
        return rec, nbytes, dev

    for node in plan.nodes:
        pe = plan.preds[node.nid]
        if node.kind == SEGMENT_NODE:
            seg = node.segment
            fn, params = models[seg.model]
            sigmas = spec.ladder(seg.model)
            seg_cond = _for(seg.model, cond)
            seg_uncond = (_for(seg.model, uncond)
                          if uncond is not None else None)
            lo, hi = seg.start, seg.stop
            if not pe:
                x_in, dev_in, bytes_in = x_init, jnp.zeros(()), 0
            elif pe[0] in fb.edges:
                # fused consume: step `start` reads the shared wire payload
                from repro.core import boundary

                e = pe[0]
                qs, dev, nbytes = wire[e.src]
                x_in = boundary.dequant_step(
                    spec.kind, fn, params, qs, spec.latent_shape, sigmas,
                    lo, seg_cond, seg_uncond, seg.guidance,
                    quantizer=e.handoff.quantizer,
                )
                hops.append({
                    "x_out": None,
                    "transfer_bytes": nbytes,
                    "deviation_pct": dev,
                    "sigma_out": e.handoff.sigma_out,
                    "sigma_in": e.handoff.sigma_in,
                    "edge": (e.src, e.dst),
                })
                dev_in = jnp.maximum(path_dev[e.src], dev)
                bytes_in = path_bytes[e.src] + nbytes
                lo = lo + 1
            else:
                e = pe[0]
                x_up = out[e.src]
                x_in, nbytes, dev = _cross(e, x_up)
                if e.handoff is not None:
                    hops.append({
                        "x_out": x_up,
                        "transfer_bytes": nbytes,
                        "deviation_pct": dev,
                        "sigma_out": e.handoff.sigma_out,
                        "sigma_in": e.handoff.sigma_in,
                        "edge": (e.src, e.dst),
                    })
                dev_in = jnp.maximum(path_dev[e.src], dev)
                bytes_in = path_bytes[e.src] + nbytes
            emits = fb.emits.get(node.nid)
            if emits is not None:
                hi = hi - 1
            x, traj = sample(
                fn, params, x_in, sigmas, seg_cond,
                start=lo, stop=hi,
                uncond=seg_uncond,
                guidance=seg.guidance, capture_traj=capture_traj,
            )
            trajs.append(traj)
            if emits is not None:
                from repro.core import boundary

                q0, need_latent = emits
                res = boundary.quant_step(
                    spec.kind, fn, params, x, sigmas, hi, seg_cond,
                    seg_uncond, seg.guidance, quantizer=q0,
                    flavor="wire_dev_latent" if need_latent else "wire_dev",
                )
                wire[node.nid] = (res["wire"], res["dev_pct"], res["bytes"])
                if need_latent:
                    out[node.nid] = res["latent"]
            else:
                out[node.nid] = x
            path_dev[node.nid] = dev_in
            path_bytes[node.nid] = bytes_in
        elif node.kind == MERGE_NODE:
            xs = [out[e.src] for e in pe]
            out[node.nid] = sum(xs[1:], xs[0]) / float(len(xs))
            # every branch's wire crossed; deviation follows the worst one
            path_dev[node.nid] = max(
                (path_dev[e.src] for e in pe), key=float
            )
            path_bytes[node.nid] = sum(path_bytes[e.src] for e in pe)
            joins.append({"node": node.nid, "kind": MERGE_NODE,
                          "inputs": [e.src for e in pe]})
        else:  # SELECT_NODE
            from repro.quantization import relative_deviation

            sel = plan.selects[node.nid]
            ref = sel.reference
            cand = sel.candidates[0]
            dev_cand = relative_deviation(out[ref], out[cand]) * 100.0
            base = float(path_dev[ref])
            bound = select_bound_pct(node, base if base > 0.0 else 1.0)
            accept = bool(float(dev_cand) <= bound)
            winner = cand if accept else ref
            out[node.nid] = out[winner]
            path_dev[node.nid] = jnp.maximum(
                path_dev[winner], dev_cand if accept else jnp.zeros(())
            )
            path_bytes[node.nid] = path_bytes[winner]
            joins.append({
                "node": node.nid, "kind": SELECT_NODE, "winner": winner,
                "accepted": accept, "deviation_pct": float(dev_cand),
                "bound_pct": bound,
            })

    sink = plan.sink
    info = {
        "trajs": trajs,
        "hops": hops,
        "joins": joins,
        "segment_steps": [n.segment.steps for n in plan.nodes
                          if n.kind == SEGMENT_NODE],
        "phases": [n.nid for n in plan.nodes],
        "transfer_bytes": int(path_bytes[sink]),
        "handoff_deviation_pct": path_dev[sink],
    }
    return out[sink], info


def relay_generate(
    spec: FamilySpec,
    plan: RelayPlan,
    large_fn: Callable,
    large_params,
    small_fn: Callable,
    small_params,
    x_init: jnp.ndarray,
    cond_large: jnp.ndarray,
    cond_small: jnp.ndarray,
    *,
    guidance: float = 1.0,
    uncond_large=None,
    uncond_small=None,
    compress_handoff: bool = False,
    capture_traj: bool = True,
):
    """Run M_L for steps [0, s), hand the latent off, run M_S for [s', T_d)
    — the paper's two-hop relay, expressed as a two-segment
    :class:`RelayProgram` and executed by :func:`execute_program`.

    With ``compress_handoff`` the edge→device latent is serialized through
    the row-wise int8 quantizer (one scale per channel row), modelling the
    constrained edge→device link: the device resumes from the *dequantized*
    latent and the introduced deviation is accounted Eq. 1-style in
    ``info["handoff_deviation_pct"]`` (a traced scalar under jit).

    Returns (x_final, info) where info carries the handoff latent, both
    trajectories (``capture_traj=False`` skips the O(steps) stacks — the
    serving hot path) and the latent norms used by the Fig. 2 analysis;
    ``info["transfer_bytes"]`` is the actual bytes-on-wire of the handoff
    payload (int8 + scales when compressed, raw latent otherwise).
    """
    program = RelayProgram(
        family=spec.name,
        segments=(
            RelaySegment("large", None, 0, plan.s, guidance),
            RelaySegment("small", None, plan.s_prime, spec.t_device, guidance),
        ),
        handoffs=(
            Handoff(plan.sigma_handoff, plan.sigma_resume,
                    compress=compress_handoff),
        ),
    )
    x_final, pinfo = execute_program(
        spec, program,
        {"large": (large_fn, large_params), "small": (small_fn, small_params)},
        x_init,
        {"large": cond_large, "small": cond_small},
        uncond=(
            {"large": uncond_large, "small": uncond_small}
            if (uncond_large is not None or uncond_small is not None) else None
        ),
        capture_traj=capture_traj,
    )
    hop = pinfo["hops"][0]
    info = {
        "x_handoff": hop["x_out"],
        "traj_edge": pinfo["trajs"][0],
        "traj_device": pinfo["trajs"][1],
        "edge_steps": plan.s,
        "device_steps": spec.t_device - plan.s_prime,
        "transfer_bytes": pinfo["transfer_bytes"],
        "handoff_deviation_pct": pinfo["handoff_deviation_pct"],
    }
    return x_final, info


def latent_norms(traj: jnp.ndarray) -> jnp.ndarray:
    """‖x_t‖₂ per step (batch-meaned) — Fig. 2a quantity."""
    flat = traj.reshape(traj.shape[0], traj.shape[1], -1)
    return jnp.mean(jnp.linalg.norm(flat, axis=-1), axis=-1)


def per_step_deviation(norms_full: np.ndarray, norms_relay: np.ndarray) -> np.ndarray:
    """ρ_t (Eq. 1): |‖x_t^large‖ − ‖x_t^relay‖| / ‖x_t^large‖ × 100%."""
    n = min(len(norms_full), len(norms_relay))
    a = np.asarray(norms_full[-n:], dtype=np.float64)
    b = np.asarray(norms_relay[-n:], dtype=np.float64)
    return np.abs(a - b) / np.maximum(np.abs(a), 1e-9) * 100.0
