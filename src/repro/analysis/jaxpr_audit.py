"""Layer 2: jaxpr audit of the six public engine entry points.

Where the AST lint (layer 1) reasons about SOURCE, this layer reasons
about the TRACED program: it drives `bss_query_batched`,
`bss_knn_batched`, `sharded_query_batched`, `sharded_knn_batched`,
`forest_range_search` and `monotone_range_search` over tiny synthetic
indexes across the {metric x backend(jnp, pallas-interpret) x
realisation x precision(fp32, bf16)} matrix, captures the jaxpr of every
jitted engine function that fires (module-level jits are wrapped; the
sharded engine's dynamically created ``jax.jit(shard_map(...))`` closures
are caught by patching ``jax.jit`` itself), and statically walks the
jaxprs to assert:

* **no float64** — no var, const or ``convert_element_type`` target is
  f64 anywhere, including sub-jaxprs (pjit / scan / while / pallas_call);
* **no host callbacks** — no ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` primitive (a callback inside an engine jit is a
  hidden host sync);
* **bf16 confinement** — bfloat16 appears in a cell's jaxprs iff
  ``precision="bf16"``, and a dataflow taint walk from the bf16 inputs
  proves the bound-phase outputs (``alive`` / ``tile_mask`` / frontier
  hits / distance counts) are UNTAINTED: PR 6's bit-identity proof rests
  on the pruning tables never depending on the reduced-precision corpus,
  and this check makes that mechanical.

Plus the **compile-cache audit** (:func:`audit_compile_cache`): replay a
mixed-shape query stream through ``ServingFront`` and assert each engine
jit's distinct-lowering count equals the bucket-ladder prediction — PR
5's bounded-recompile guarantee as an equality, not a hope.

Pure trace-time analysis plus tiny real calls; no TPU needed (pallas runs
in interpret mode).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Callable

from repro.core.backends import EngineOpts

__all__ = [
    "AuditProblem",
    "run_audit",
    "audit_compile_cache",
    "AUDIT_METRICS",
]

AUDIT_METRICS = ("l2", "cosine", "jsd", "triangular")
_CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback"}


@dataclasses.dataclass(frozen=True)
class AuditProblem:
    cell: str      # matrix-cell description, e.g. "bss/jsd/pallas/bf16"
    fn: str        # jitted function name
    check: str     # f64 | callback | bf16-absent | bf16-present | taint
    detail: str

    def format(self) -> str:
        return f"[{self.cell}] {self.fn}: {self.check}: {self.detail}"


@dataclasses.dataclass
class _Capture:
    fn: str
    cell: str
    closed: Any        # jax.extend.core.ClosedJaxpr
    out_shape: Any     # pytree of ShapeDtypeStruct


# ---------------------------------------------------------------------------
# capture machinery
# ---------------------------------------------------------------------------


def _is_array_like(x) -> bool:
    import jax
    import numpy as np

    return isinstance(x, (np.ndarray, jax.Array, np.generic))


def _is_traced_arg(x) -> bool:
    """Pytrees containing any array are traced; bare scalars/strings/None
    are closed over as statics (matching how the engines pass them)."""
    import jax

    leaves = jax.tree_util.tree_leaves(x)
    return any(_is_array_like(l) for l in leaves)


class _Recorder:
    """Collects one jaxpr per distinct (fn, arg-signature) call, tagged
    with the matrix cell active at call time."""

    def __init__(self):
        self.captures: list[_Capture] = []
        self.cell = "?"
        self._seen: dict[str, _Capture] = {}

    def _signature(self, name, args, kwargs):
        import jax

        parts = [name]
        for a in args:
            if _is_traced_arg(a):
                for l in jax.tree_util.tree_leaves(a):
                    parts.append(f"{getattr(l, 'shape', ())}"
                                 f"{getattr(l, 'dtype', type(l).__name__)}")
            else:
                parts.append(repr(a))
        parts.append(repr(sorted(kwargs.items(), key=lambda kv: kv[0])))
        return "|".join(parts)

    def record(self, name: str, inner: Callable, args, kwargs) -> None:
        import jax

        sig = self._signature(name, args, kwargs)
        prior = self._seen.get(sig)
        if prior is not None:
            # identical trace already captured: register it under this
            # cell too (checks are per cell) without paying a re-trace
            if prior.cell != self.cell and not any(
                c.fn == name and c.cell == self.cell
                for c in self.captures
            ):
                self.captures.append(
                    _Capture(name, self.cell, prior.closed, prior.out_shape)
                )
            return
        spec: list = []
        arrays: list = []
        for a in args:
            if _is_traced_arg(a):
                spec.append(len(arrays))
                arrays.append(a)
            else:
                spec.append(("static", a))

        def closure(*arrs):
            rebuilt = [
                arrs[s] if isinstance(s, int) else s[1] for s in spec
            ]
            return inner(*rebuilt, **kwargs)

        closed, out_shape = jax.make_jaxpr(closure, return_shape=True)(
            *arrays
        )
        cap = _Capture(name, self.cell, closed, out_shape)
        self._seen[sig] = cap
        self.captures.append(cap)

    def for_cell(self, cell: str) -> list[_Capture]:
        return [c for c in self.captures if c.cell == cell]


def _wrap_module_jit(rec: _Recorder, name: str, jitted):
    inner = jitted.__wrapped__

    @functools.wraps(jitted)
    def wrapper(*args, **kwargs):
        rec.record(name, inner, args, kwargs)
        return jitted(*args, **kwargs)

    wrapper.__audit_original__ = jitted
    return wrapper


@contextlib.contextmanager
def _patched_engines(rec: _Recorder):
    """Wrap every module-level engine jit AND ``jax.jit`` itself (the
    sharded engine creates its shard_map jits lazily per dispatch key)."""
    import jax

    from repro.core import flat_index
    from repro.forest import walk

    targets = [
        (flat_index, n)
        for n in (
            "_lower_bounds_jit",
            "_cells_exact_jit",
            "_cells_exact_bf16_jit",
            "_dense_hit_mask_jit",
            "_query_batched_jit",
            "_query_batched_bf16_jit",
            "_knn_round_jit",
            "_knn_round_bf16_jit",
            "_knn_round_cells_jit",
            "_knn_round_cells_bf16_jit",
            "_knn_lb_jit",
        )
    ] + [(walk, n) for n in ("_forest_walk_jit", "_monotone_walk_jit")]

    saved = [(m, n, getattr(m, n)) for m, n in targets]
    real_jit = jax.jit

    def recording_jit(fun, *a, **kw):
        jitted = real_jit(fun, *a, **kw)

        @functools.wraps(jitted)
        def wrapper(*args, **kwargs):
            rec.record(
                getattr(fun, "__name__", "dynamic_jit"), fun, args, kwargs
            )
            return jitted(*args, **kwargs)

        return wrapper

    try:
        for m, n, fn in saved:
            setattr(m, n, _wrap_module_jit(rec, n, fn))
        jax.jit = recording_jit
        yield
    finally:
        jax.jit = real_jit
        for m, n, fn in saved:
            setattr(m, n, fn)


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _sub_jaxprs(params: dict):
    """Yield every (Closed)Jaxpr buried in an eqn's params."""
    import jax.extend.core as jcore

    def visit(v):
        if isinstance(v, jcore.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jcore.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                yield from visit(x)
        elif isinstance(v, dict):
            for x in v.values():
                yield from visit(x)

    for v in params.values():
        yield from visit(v)


def _all_jaxprs(jaxpr):
    """The jaxpr and every sub-jaxpr, recursively."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for sub in _sub_jaxprs(eqn.params):
            yield from _all_jaxprs(sub)


def _dtype_of(v):
    aval = getattr(v, "aval", None)
    return getattr(aval, "dtype", None)


def _check_no_f64(cap: _Capture) -> list[str]:
    import numpy as np

    problems = []
    for j in _all_jaxprs(cap.closed.jaxpr):
        for eqn in j.eqns:
            nd = eqn.params.get("new_dtype")
            if nd is not None and np.dtype(nd) == np.float64:
                problems.append(
                    f"{eqn.primitive.name} converts to float64"
                )
            for v in list(eqn.invars) + list(eqn.outvars):
                if _dtype_of(v) == np.float64:
                    problems.append(
                        f"float64 value at {eqn.primitive.name}"
                    )
        for v in list(j.invars) + list(j.constvars) + list(j.outvars):
            if _dtype_of(v) == np.float64:
                problems.append("float64 jaxpr binder")
    return sorted(set(problems))


def _check_no_callbacks(cap: _Capture) -> list[str]:
    problems = []
    for j in _all_jaxprs(cap.closed.jaxpr):
        for eqn in j.eqns:
            if eqn.primitive.name in _CALLBACK_PRIMS:
                problems.append(f"{eqn.primitive.name} primitive present")
    return sorted(set(problems))


def _has_bf16(cap: _Capture) -> bool:
    import jax.numpy as jnp

    bf16 = jnp.bfloat16
    for j in _all_jaxprs(cap.closed.jaxpr):
        for v in list(j.invars) + list(j.constvars) + list(j.outvars):
            if _dtype_of(v) == bf16:
                return True
        for eqn in j.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                if _dtype_of(v) == bf16:
                    return True
    return False


# -- taint ------------------------------------------------------------------


def _is_bf16_var(v) -> bool:
    import jax.numpy as jnp

    return _dtype_of(v) == jnp.bfloat16


def _taint_jaxpr(jaxpr, in_taint: list[bool], consts=None) -> list[bool]:
    """Propagate bf16 taint through one jaxpr: returns per-outvar taint.

    Precise through pjit-style call eqns (sub-jaxpr arity matches the
    eqn's) and through scan/while carry loops (iterated to fixpoint);
    conservative (any tainted input taints all outputs) elsewhere —
    pallas_call included, which is sound because the only pallas kernels
    fed bf16 are the exact-phase distance scans whose outputs are
    legitimately tainted."""
    import jax.extend.core as jcore

    tainted: set = set()

    def var_tainted(x) -> bool:
        if isinstance(x, jcore.Literal):
            return _is_bf16_var(x)
        return x in tainted or _is_bf16_var(x)

    for v, t in zip(jaxpr.invars, in_taint):
        if t:
            tainted.add(v)
    if consts is not None:
        for v, c in zip(jaxpr.constvars, consts):
            if getattr(c, "dtype", None) is not None and str(c.dtype) == (
                "bfloat16"
            ):
                tainted.add(v)

    changed = True
    while changed:
        changed = False
        for eqn in eqns_of(jaxpr):
            in_t = [var_tainted(x) for x in eqn.invars]
            out_t = _eqn_out_taint(eqn, in_t)
            for o, t in zip(eqn.outvars, out_t):
                if t and o not in tainted:
                    tainted.add(o)
                    changed = True
    return [var_tainted(o) for o in jaxpr.outvars]


def eqns_of(jaxpr):
    return jaxpr.eqns


def _eqn_out_taint(eqn, in_t: list[bool]) -> list[bool]:
    import jax.extend.core as jcore

    name = eqn.primitive.name
    params = eqn.params
    if name == "scan" and "jaxpr" in params:
        sub = params["jaxpr"]
        sub_j = sub.jaxpr if isinstance(sub, jcore.ClosedJaxpr) else sub
        nc = params.get("num_consts", 0)
        ncar = params.get("num_carry", 0)
        cur = list(in_t)
        while True:
            out_t = _taint_jaxpr(sub_j, cur)
            nxt = list(cur)
            for i in range(ncar):
                if out_t[i]:
                    nxt[nc + i] = True
            if nxt == cur:
                return out_t
            cur = nxt
    if name == "while" and "body_jaxpr" in params:
        body = params["body_jaxpr"]
        body_j = body.jaxpr if isinstance(body, jcore.ClosedJaxpr) else body
        cn = params.get("cond_nconsts", 0)
        bn = params.get("body_nconsts", 0)
        carry_t = list(in_t[cn + bn:])
        body_consts_t = list(in_t[cn:cn + bn])
        while True:
            out_t = _taint_jaxpr(body_j, body_consts_t + carry_t)
            nxt = [a or b for a, b in zip(carry_t, out_t)]
            if nxt == carry_t:
                return carry_t
            carry_t = nxt
    sub = params.get("jaxpr", params.get("call_jaxpr"))
    if sub is not None:
        sub_j = sub.jaxpr if isinstance(sub, jcore.ClosedJaxpr) else sub
        consts = (
            sub.consts if isinstance(sub, jcore.ClosedJaxpr) else None
        )
        if len(sub_j.invars) == len(eqn.invars) and len(
            sub_j.outvars
        ) == len(eqn.outvars):
            return _taint_jaxpr(sub_j, in_t, consts)
    # conservative fallback (pallas_call, cond, collectives, ...)
    any_t = any(in_t)
    return [any_t] * len(eqn.outvars)


def _output_taint(cap: _Capture) -> list[bool]:
    """Per-flat-output bf16 taint of a captured jaxpr (bf16 invars AND
    bf16 closed-over consts seed the walk)."""
    closed = cap.closed
    in_taint = [_is_bf16_var(v) for v in closed.jaxpr.invars]
    return _taint_jaxpr(closed.jaxpr, in_taint, closed.consts)


# which flat outputs of each bf16-bearing engine jit must stay UNTAINTED.
# Specs are functions of the output pytree (from make_jaxpr(...,
# return_shape=True)) returning a same-structure pytree of bools — True
# means "this output is part of the bound/pruning phase and must not
# depend on the bf16 corpus".
def _mask(tree, flag: bool):
    import jax

    return jax.tree_util.tree_map(lambda _: flag, tree)


def _spec_query_bf16(out):
    hit, alive, tile_mask, rtiles, band = out
    return (
        _mask(hit, False), _mask(alive, True), _mask(tile_mask, True),
        _mask(rtiles, False), _mask(band, False),
    )


def _spec_knn_round_bf16(out):
    cand_idx, cand_dist, kth, done, alive, tile_mask, rtiles, band = out
    return (
        _mask(cand_idx, False), _mask(cand_dist, False),
        _mask(kth, False), _mask(done, False), _mask(alive, True),
        _mask(tile_mask, True), _mask(rtiles, False), _mask(band, False),
    )


def _spec_forest_walk(out):
    # obs (exclusion attribution + frontier occupancy) is derived from the
    # fp32 bound phase only, so it must stay untainted — instrumenting the
    # walker STRENGTHENED this audit rather than weakening it
    ref_hits, leaf_hit, counts, band, rtiles, obs = out
    return (
        _mask(ref_hits, True), _mask(leaf_hit, False),
        _mask(counts, True), _mask(band, False), _mask(rtiles, False),
        _mask(obs, True),
    )


def _spec_monotone_walk(out):
    root_hit, p2_hits, leaf_hit, counts, band, rtiles, obs = out
    return (
        _mask(root_hit, True), _mask(p2_hits, True),
        _mask(leaf_hit, False), _mask(counts, True), _mask(band, False),
        _mask(rtiles, False), _mask(obs, True),
    )


_UNTAINTED_SPECS: dict[str, Callable] = {
    "_query_batched_bf16_jit": _spec_query_bf16,
    "_knn_round_bf16_jit": _spec_knn_round_bf16,
    "_forest_walk_jit": _spec_forest_walk,
    "_monotone_walk_jit": _spec_monotone_walk,
}


def _check_taint(cap: _Capture) -> list[str]:
    import jax

    spec_fn = _UNTAINTED_SPECS.get(cap.fn)
    if spec_fn is None or not _has_bf16(cap):
        return []
    must_be_clean, _ = jax.tree_util.tree_flatten(spec_fn(cap.out_shape))
    taint = _output_taint(cap)
    if len(taint) != len(must_be_clean):  # pragma: no cover - spec bug
        return [
            f"output arity mismatch: {len(taint)} outvars vs "
            f"{len(must_be_clean)} spec entries"
        ]
    problems = []
    for i, (clean, t) in enumerate(zip(must_be_clean, taint)):
        if clean and t:
            problems.append(
                f"bound-phase output #{i} is tainted by the bf16 corpus "
                "(pruning must be precision-independent)"
            )
    return problems


# ---------------------------------------------------------------------------
# matrix driver
# ---------------------------------------------------------------------------


def _synth(metric: str, n: int, dim: int, seed: int):
    """Tiny CLUSTERED corpus+queries (isotropic gaussians defeat the
    planar bounds entirely, so the adaptive path would never go sparse);
    simplex-normalised for the probability-space metrics."""
    import numpy as np

    rng = np.random.default_rng(seed)
    n_clusters = 16
    centers = rng.normal(size=(n_clusters, dim)) * 8.0
    lab = np.repeat(np.arange(n_clusters), -(-n // n_clusters))[:n]
    db = (centers[lab] + rng.normal(size=(n, dim)) * 0.15).astype(
        np.float32
    )
    q = (centers[:8] + rng.normal(size=(8, dim)) * 0.15).astype(np.float32)
    if metric in ("jsd", "triangular"):
        db = np.abs(db) + 0.05
        db /= db.sum(axis=1, keepdims=True)
        q = np.abs(q) + 0.05
        q /= q.sum(axis=1, keepdims=True)
    return db, q


def _range_radii(metric: str, db, q) -> tuple[float, float]:
    """(narrow, wide) radii from the oracle distance distribution: narrow
    leaves a thin alive set (the adaptive jnp path goes cell-gather),
    wide floods it (dense) — both exact-phase realisations trace."""
    import numpy as np

    from repro.core.npdist import pairwise_np

    d = pairwise_np(metric, q, db)
    return float(np.quantile(d, 0.02)), float(np.quantile(d, 0.6))


def _audit_captures(rec: _Recorder, cell: str, bf16: bool) -> list[
    AuditProblem
]:
    problems: list[AuditProblem] = []
    caps = rec.for_cell(cell)
    if not caps:
        problems.append(
            AuditProblem(cell, "-", "coverage", "no jaxpr captured")
        )
    any_bf16 = False
    for cap in caps:
        for d in _check_no_f64(cap):
            problems.append(AuditProblem(cell, cap.fn, "f64", d))
        for d in _check_no_callbacks(cap):
            problems.append(AuditProblem(cell, cap.fn, "callback", d))
        has16 = _has_bf16(cap)
        any_bf16 = any_bf16 or has16
        if has16 and not bf16:
            problems.append(
                AuditProblem(
                    cell, cap.fn, "bf16-present",
                    "bfloat16 in a fp32-precision cell",
                )
            )
        for d in _check_taint(cap):
            problems.append(AuditProblem(cell, cap.fn, "taint", d))
    if bf16 and caps and not any_bf16:
        problems.append(
            AuditProblem(
                cell, "-", "bf16-absent",
                "precision=bf16 but no bfloat16 in any captured jaxpr",
            )
        )
    return problems


def run_audit(
    full: bool = False, log: Callable[[str], None] | None = None
) -> list[AuditProblem]:
    """Drive the engine matrix and check every captured jaxpr.

    ``full=False`` (the default / self-check mode) audits the l2 column
    of the matrix — every entry point, backend, realisation and precision
    still fires.  ``full=True`` (CI) runs all four supermetrics."""
    import numpy as np

    from repro.core import flat_index, lrt, tree
    from repro.forest import encode_monotone, encode_tree
    from repro.forest.walk import forest_range_search, monotone_range_search

    log = log or (lambda s: None)
    metrics = AUDIT_METRICS if full else ("l2",)
    rec = _Recorder()
    problems: list[AuditProblem] = []

    with _patched_engines(rec):
        for metric in metrics:
            db, q = _synth(metric, 512, 8, seed=3)
            t_narrow, t_wide = _range_radii(metric, db, q)
            idx = flat_index.build_bss(
                metric, db, n_pivots=6, n_pairs=8, block=32, seed=5
            )
            # backend x realisation legs as EngineOpts — the audit drives
            # the engines through the SAME frozen-options surface the
            # serving stack uses; the adaptive jnp path is run at both a
            # pruning and a flooding radius so BOTH its exact-phase
            # realisations (cell-gather and dense) trace.
            legs = [
                EngineOpts(backend="jnp", realisation="adaptive"),
                EngineOpts(backend="jnp", realisation="dense"),
                EngineOpts(backend="pallas", realisation="dense",
                           interpret=True),
            ]
            for leg in legs:
                for precision in ("fp32", "bf16"):
                    opts = dataclasses.replace(leg, precision=precision)
                    cell = (
                        f"bss/{metric}/{opts.backend}-{opts.realisation}"
                        f"/{precision}"
                    )
                    rec.cell = cell
                    log(f"audit {cell}")
                    for t in (t_narrow, t_wide):
                        flat_index.bss_query_batched(idx, q, t, opts=opts)
                    flat_index.bss_knn_batched(
                        idx, q, 3, r0=t_narrow, opts=opts,
                    )
                    problems += _audit_captures(
                        rec, cell, bf16=precision == "bf16"
                    )

            # sharded engine (1-device mesh: shard_map traces the same
            # collective program as the real pod, minus cross-chip hops)
            import jax
            from jax.sharding import Mesh

            from repro.parallel import shard_index

            mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
            sidx = shard_index.shard_bss(idx, mesh)
            # both backends: the pallas leg traces the planes-major bound
            # kernel and the scalar-prefetch masked kernels inside the
            # jax.shard_map closures
            for backend, interpret in (("jnp", None), ("pallas", True)):
                for precision in ("fp32", "bf16"):
                    opts = EngineOpts(
                        backend=backend, interpret=interpret,
                        precision=precision,
                    )
                    cell = f"sharded/{metric}/{backend}/{precision}"
                    rec.cell = cell
                    log(f"audit {cell}")
                    shard_index.sharded_query_batched(
                        sidx, q, t_narrow, opts=opts,
                    )
                    shard_index.sharded_knn_batched(sidx, q, 3, opts=opts)
                    problems += _audit_captures(
                        rec, cell, bf16=precision == "bf16"
                    )

            # forest + monotone walkers
            tr = tree.build_tree("hpt_random_fixed", metric, db, seed=7)
            enc = encode_tree(tr)
            mtr = lrt.build_monotone_tree("closer", "far", metric, db, seed=7)
            menc = encode_monotone(mtr)
            for backend, interpret in (("jnp", None), ("pallas", True)):
                for precision in ("fp32", "bf16"):
                    opts = EngineOpts(
                        backend=backend, interpret=interpret,
                        precision=precision,
                    )
                    cell = f"forest/{metric}/{backend}/{precision}"
                    rec.cell = cell
                    log(f"audit {cell}")
                    forest_range_search(enc, q, t_narrow, opts=opts)
                    monotone_range_search(menc, q, t_narrow, opts=opts)
                    problems += _audit_captures(
                        rec, cell, bf16=precision == "bf16"
                    )

    return problems


# ---------------------------------------------------------------------------
# compile-cache audit (PR 5's recompile bound, as an equality)
# ---------------------------------------------------------------------------


def audit_compile_cache(
    sizes=tuple(range(1, 11)), buckets=(4, 8)
) -> tuple[list[AuditProblem], dict]:
    """Replay a mixed-size range+knn stream through ``ServingFront`` and
    assert each engine jit's distinct-lowering growth EQUALS the ladder
    prediction: one lowering per bucket the stream touches, per entry
    point.  Returns (problems, info); info["skipped"] is True when this
    jax exposes no jit cache hook (growth then unobservable)."""
    import numpy as np

    from repro.core import flat_index
    from repro.core.backends import bucket_for, jit_cache_size
    from repro.serve.front import ServingFront

    db, q = _synth("l2", 320, 8, seed=11)
    idx = flat_index.build_bss("l2", db, n_pivots=6, n_pairs=8, block=64,
                               seed=13)
    fns = {
        "range/lb": flat_index._lower_bounds_jit,
        "range/dense": flat_index._dense_hit_mask_jit,
        "knn/lb": flat_index._knn_lb_jit,
        "knn/round": flat_index._knn_round_jit,
    }
    before = {name: jit_cache_size(fn) for name, fn in fns.items()}
    info: dict = {"buckets": list(buckets), "sizes": list(sizes)}
    if any(v < 0 for v in before.values()):
        info["skipped"] = True
        return [], info
    info["skipped"] = False

    # buckets the stream touches; waves larger than the top bucket are
    # split by the front into top-bucket chunks plus a remainder
    touched: set[int] = set()
    for n in sizes:
        while n > 0:
            chunk = min(n, buckets[-1])
            touched.add(bucket_for(chunk, buckets))
            n -= chunk
    predicted = len(touched)
    info["predicted_lowerings"] = predicted

    qbig = np.concatenate([q] * ((max(sizes) // len(q)) + 1))
    with ServingFront(idx, buckets=buckets, max_delay_s=0.02,
                      backend="jnp") as front:
        for n in sizes:
            futs = [
                front.submit(qv, "range", t=0.5 + 0.01 * i)
                for i, qv in enumerate(qbig[:n])
            ]
            futs += [front.submit(qv, "knn", k=3) for qv in qbig[:n]]
            for f in futs:
                f.result(timeout=60)

    problems: list[AuditProblem] = []
    growth = {}
    for name, fn in fns.items():
        grew = jit_cache_size(fn) - before[name]
        growth[name] = grew
        if grew != predicted:
            problems.append(
                AuditProblem(
                    "serving/compile-cache", name, "lowerings",
                    f"{grew} distinct lowerings, ladder predicts "
                    f"{predicted} (buckets {buckets}, sizes "
                    f"{min(sizes)}..{max(sizes)})",
                )
            )
    info["growth"] = growth
    return problems, info
