import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, and extract the roofline raw material.

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]

Per cell this emits JSON with:
    memory_analysis   (per-device bytes: args/outputs/temps/peak)
    cost_analysis     (HLO flops / bytes accessed)
    collective_bytes  (per-device bytes through all-gather / all-reduce /
                       reduce-scatter / all-to-all / collective-permute,
                       parsed from the SPMD-partitioned HLO)
    model_flops       (6*N*D dense / 6*N_active*D MoE analytic reference)

Failures here (sharding mismatch, OOM at compile, unsupported collective)
are bugs in the system — the run aborts loudly.
"""

import argparse
import dataclasses
import json
import re
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.serve.queue import now

from repro.configs import common
from repro.configs.registry import all_cells, get_arch, registry
from repro.launch.mesh import make_production_mesh
from repro.optim import make_optimizer
from repro.parallel.sharding import dp_axes, shard_tree
from repro.train.step import make_train_step

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLL_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\]))[^=]*?\b"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_from_hlo(hlo_text: str) -> dict:
    per_kind: dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        per_kind[kind] = per_kind.get(kind, 0) + _shape_bytes(shape_str)
    per_kind["total"] = sum(per_kind.values())
    return per_kind


def _sds_with_sharding(sds_tree, spec_tree, mesh):
    shardings = shard_tree(mesh, spec_tree)
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        sds_tree,
        shardings,
    )


def _model_flops(bundle, model, cell, batch_sds) -> float:
    """Analytic useful-FLOPs reference (6*N*D rule and analogues)."""
    cfg = model.cfg
    if bundle.family == "lm":
        n_act = cfg.n_active_params()
        if cell.kind == "train":
            toks = batch_sds["tokens"].shape[0] * (batch_sds["tokens"].shape[1] - 1)
            return 6.0 * n_act * toks
        if cell.kind == "prefill":
            toks = batch_sds["tokens"].shape[0] * batch_sds["tokens"].shape[1]
            return 2.0 * n_act * toks
        toks = batch_sds["token"].shape[0]
        return 2.0 * n_act * toks
    if bundle.family == "gnn":
        # dominant: per-edge message MLP + per-node update MLP, fwd+bwd (x3)
        x = batch_sds["x"]
        e = batch_sds["edge_src"].shape[0]
        n = x.shape[0]
        d = cfg.d_hidden
        per_layer = e * (2 * d) * d * 2 + n * (13 * d) * d * 2
        fwd = cfg.n_layers * per_layer + n * x.shape[1] * d * 2
        return 3.0 * fwd
    # recsys: embedding gathers dominate bytes, MLPs dominate flops
    model_params = sum(
        int(jnp.prod(jnp.array(s[0])))
        for s in jax.tree.leaves(
            model.param_shapes(),
            is_leaf=lambda v: isinstance(v, tuple) and len(v) == 2
            and isinstance(v[0], tuple),
        )
        if len(s[0]) == 2  # MLP mats only (tables are gathered, not matmul'd)
    )
    if "candidates" in batch_sds:
        # two-tower candidate scoring: ONE user-tower pass + a dot per
        # candidate (the MLP does NOT run per candidate row)
        n = batch_sds["candidates"].shape[0]
        e_dim = batch_sds["candidates"].shape[1]
        user_rows = batch_sds["user_ids"].shape[0]
        return 2.0 * model_params * user_rows + 2.0 * n * e_dim * user_rows
    rows = jax.tree.leaves(batch_sds)[0].shape[0]
    mult = 6.0 if cell.kind == "train" else 2.0
    return mult * model_params * rows


def _measure(compiled) -> dict:
    """Per-device flops / bytes / collective bytes from a compiled artifact."""
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes_from_hlo(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": {k: float(v) for k, v in coll.items()},
    }


def _combine(a: dict, b: dict, ca: float, cb: float) -> dict:
    """ca*a + cb*b, fieldwise (coll dict keys unioned)."""
    keys = set(a["coll"]) | set(b["coll"])
    return {
        "flops": ca * a["flops"] + cb * b["flops"],
        "bytes": ca * a["bytes"] + cb * b["bytes"],
        "coll": {
            k: ca * a["coll"].get(k, 0.0) + cb * b["coll"].get(k, 0.0) for k in keys
        },
    }


_ZERO = {"flops": 0.0, "bytes": 0.0, "coll": {}}


def _dp_size(mesh) -> int:
    out = 1
    for a in dp_axes(mesh):
        out *= mesh.shape[a]
    return out


def _with_batch_axes(model, mesh, rows: int, family: str = "lm"):
    """Rebuild a model with activation batch-sharding pinned to the data
    axes (when the row count divides them).  GNN node/edge rows shard over
    ALL axes (data + model) — cells pad to multiples of 512."""
    if not hasattr(model.cfg, "batch_axes"):
        return model
    if family == "gnn":
        axes = dp_axes(mesh) + ("model",)
    else:
        axes = dp_axes(mesh) if rows % _dp_size(mesh) == 0 else None
    return type(model)(dataclasses.replace(model.cfg, batch_axes=axes))


def _cell_rows(cell, batch_sds) -> int:
    if "tokens" in batch_sds:
        return batch_sds["tokens"].shape[0]
    if "token" in batch_sds:
        return batch_sds["token"].shape[0]
    if "candidates" in batch_sds:
        return batch_sds["candidates"].shape[0]
    return jax.tree.leaves(batch_sds)[0].shape[0]


def probe_lm_cell(model, family, cell, mesh, batch_sds) -> dict:
    """Loop-corrected per-device cost totals for an LM cell.

    XLA's HloCostAnalysis counts while-loop bodies once, so the scan-based
    production graph undercounts FLOPs/collectives by ~n_layers (and
    ~microbatches).  We compile small UNROLLED probes at L=2 and L=4 (the
    L=4/L=2 delta isolates exactly two layers, covering gemma2's local/global
    alternation), plus a standalone optimizer probe, and extrapolate:

        per_layer  = (P4 - P2) / 2
        fixed      = P2 - 2 * per_layer          (embed/logits/loss[/opt])
        train      = mb * (fixed - opt + L * per_layer) + opt
        prefill/decode =       fixed + L * per_layer
    """
    import dataclasses as dc

    cfg = model.cfg
    kind = cell.kind
    mb = getattr(cfg, "microbatches", 1) if kind == "train" else 1

    probes = {}
    for L in (2, 4):
        pcfg = dc.replace(
            cfg, n_layers=L, unroll_layers=True, attn_q_chunk=None,
            microbatches=1,
        )
        pmodel = type(model)(pcfg)
        params_sds = _sds_with_sharding(
            pmodel.abstract_params(), pmodel.param_specs(mesh), mesh
        )
        if kind == "train":
            toks = batch_sds["tokens"]
            pb = toks.shape[0] // mb
            ptoks = jax.ShapeDtypeStruct((pb, toks.shape[1]), toks.dtype,
                                         sharding=toks.sharding)
            opt = make_optimizer(cfg.optimizer)
            opt_sds = _sds_with_sharding(
                jax.eval_shape(opt.init, params_sds),
                opt.state_specs(pmodel.param_specs(mesh)), mesh,
            )
            state_sds = {"params": params_sds, "opt": opt_sds,
                         "step": jax.ShapeDtypeStruct((), jnp.int32)}
            loss_fn = common.loss_for(family, pmodel)
            step = make_train_step(loss_fn, opt, microbatches=1)
            compiled = jax.jit(step, donate_argnums=(0,)).lower(
                state_sds, {"tokens": ptoks}).compile()
        elif kind == "prefill":
            compiled = jax.jit(pmodel.prefill).lower(
                params_sds, batch_sds["tokens"]).compile()
        else:  # decode
            b = batch_sds["token"].shape[0]
            seq = common.LM_SHAPES[cell.shape_name]["seq"]
            cache_sds = _sds_with_sharding(
                pmodel.init_cache_shapes(b, seq), pmodel.cache_specs(mesh, b),
                mesh,
            )
            compiled = jax.jit(pmodel.decode_step, donate_argnums=(1,)).lower(
                params_sds, cache_sds, batch_sds["token"], batch_sds["pos"]
            ).compile()
        probes[L] = _measure(compiled)

    per_layer = _combine(probes[4], probes[2], 0.5, -0.5)
    fixed = _combine(probes[2], per_layer, 1.0, -2.0)

    if kind == "train":
        # The L-probes ran FULL train steps, so `per_layer`/`fixed` each mix
        # per-microbatch fwd+bwd cost with once-per-step optimizer cost.
        # Probe the optimizer alone at L=2 and L=4, split both components,
        # then: total = mb * body(L) + opt(L).
        opt = make_optimizer(cfg.optimizer)

        def _opt_probe(L: int) -> dict:
            pcfg = dataclasses.replace(
                cfg, n_layers=L, unroll_layers=True, microbatches=1
            )
            pm = type(model)(pcfg)
            psds = _sds_with_sharding(
                pm.abstract_params(), pm.param_specs(mesh), mesh
            )
            osds = _sds_with_sharding(
                jax.eval_shape(opt.init, psds),
                opt.state_specs(pm.param_specs(mesh)), mesh,
            )
            return _measure(
                jax.jit(opt.update).lower(psds, osds, psds).compile()
            )

        opt2, opt4 = _opt_probe(2), _opt_probe(4)
        per_layer_opt = _combine(opt4, opt2, 0.5, -0.5)
        opt_fixed = _combine(opt2, per_layer_opt, 1.0, -2.0)
        opt_full = _combine(opt_fixed, per_layer_opt, 1.0, float(cfg.n_layers))
        per_layer_body = _combine(per_layer, per_layer_opt, 1.0, -1.0)
        body_fixed = _combine(fixed, opt_fixed, 1.0, -1.0)
        per_mb = _combine(body_fixed, per_layer_body, 1.0, float(cfg.n_layers))
        total = _combine(per_mb, opt_full, float(mb), 1.0)
    else:
        total = _combine(fixed, per_layer, 1.0, float(cfg.n_layers))

    # numerical floor: extrapolation can go slightly negative on tiny terms
    total["flops"] = max(total["flops"], 0.0)
    total["bytes"] = max(total["bytes"], 0.0)
    total["coll"] = {k: max(v, 0.0) for k, v in total["coll"].items()}
    return {
        "method": "unrolled L2/L4 probe extrapolation (per-device)",
        "per_layer": per_layer,
        "fixed": fixed,
        "total": total,
        "microbatches": mb,
    }


def lower_cell(arch: str, shape_name: str, mesh, *, compile_: bool = True) -> dict:
    bundle = get_arch(arch)
    cell = bundle.cells[shape_name]
    if cell.skip:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": cell.skip}
    model = bundle.model_for(shape_name)
    t0 = now()

    with jax.set_mesh(mesh):
        batch_sds = _sds_with_sharding(cell.inputs(), cell.input_partition(mesh), mesh)
        model = _with_batch_axes(
            model, mesh, _cell_rows(cell, batch_sds), bundle.family
        )
        cfg = model.cfg

        if cell.kind == "train":
            params_sds = _sds_with_sharding(
                model.abstract_params(), model.param_specs(mesh), mesh
            )
            opt = make_optimizer(cfg.optimizer)
            opt_sds_raw = jax.eval_shape(opt.init, params_sds)
            opt_specs = opt.state_specs(model.param_specs(mesh))
            opt_sds = _sds_with_sharding(opt_sds_raw, opt_specs, mesh)
            state_sds = {
                "params": params_sds,
                "opt": opt_sds,
                "step": jax.ShapeDtypeStruct((), jnp.int32),
            }
            loss_fn = common.loss_for(bundle.family, model)
            import jax.numpy as _jnp

            step = make_train_step(
                loss_fn, opt, microbatches=getattr(cfg, "microbatches", 1),
                accum_dtype=getattr(_jnp, getattr(cfg, "grad_accum_dtype", "float32")),
            )
            jitted = jax.jit(step, donate_argnums=(0,))
            lowered = jitted.lower(state_sds, batch_sds)
        elif cell.kind == "prefill":
            params_sds = _sds_with_sharding(
                model.abstract_params(), model.param_specs(mesh), mesh
            )
            pre_cfg = dataclasses.replace(cfg, remat=False)
            pre_model = type(model)(pre_cfg)
            # Sarathi-style chunked prefill: bounds live activations + the
            # MoE dispatch buffer to one 2048-token segment.
            jitted = jax.jit(lambda p, t: pre_model.prefill(p, t, chunk=2048))
            lowered = jitted.lower(params_sds, batch_sds["tokens"])
        elif cell.kind == "decode":
            params_sds = _sds_with_sharding(
                model.abstract_params(), model.param_specs(mesh), mesh
            )
            b = batch_sds["token"].shape[0]
            seq = common.LM_SHAPES[shape_name]["seq"]
            cache_sds = _sds_with_sharding(
                model.init_cache_shapes(b, seq), model.cache_specs(mesh, b), mesh
            )
            from jax.sharding import NamedSharding

            logit_shard = NamedSharding(
                mesh,
                jax.sharding.PartitionSpec(
                    dp_axes(mesh) if b % _dp_size(mesh) == 0 else None, "model"
                ),
            )
            cache_shard = shard_tree(mesh, model.cache_specs(mesh, b))
            jitted = jax.jit(
                model.decode_step,
                donate_argnums=(1,),
                out_shardings=(logit_shard, cache_shard),
            )
            lowered = jitted.lower(
                params_sds, cache_sds, batch_sds["token"], batch_sds["pos"]
            )
        else:  # serve (recsys forward)
            params_sds = _sds_with_sharding(
                model.abstract_params(), model.param_specs(mesh), mesh
            )
            jitted = jax.jit(model.forward)
            lowered = jitted.lower(params_sds, batch_sds)
            # beyond-paper variant: supermetric-pruned candidate scoring
            # (the paper's technique in the serving graph) — lowered and
            # measured alongside the dense baseline.
            if (arch == "two-tower-retrieval"
                    and shape_name == "retrieval_cand"):
                n_cand = batch_sds["candidates"].shape[0]
                block, n_piv, n_pairs = 128, 16, 24
                b_blocks = -(-n_cand // block)
                e_dim = batch_sds["candidates"].shape[1]
                idx_sds = dict(batch_sds)
                idx_sds["pivots"] = jax.ShapeDtypeStruct(
                    (n_piv, e_dim), jnp.float32)
                idx_sds["pair_idx"] = jax.ShapeDtypeStruct(
                    (n_pairs, 2), jnp.int32)
                idx_sds["deltas"] = jax.ShapeDtypeStruct(
                    (n_pairs,), jnp.float32)
                idx_sds["boxes"] = jax.ShapeDtypeStruct(
                    (4, n_pairs, b_blocks), jnp.float32)
                fwd = lambda p, b: model.forward_retrieval_pruned(  # noqa: E731
                    p, b, block=block, budget_blocks=3136)
                opt_compiled = jax.jit(fwd).lower(params_sds, idx_sds).compile()

        lower_s = now() - t0
        rec = {
            "arch": arch,
            "shape": shape_name,
            "kind": cell.kind,
            "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
            "n_devices": int(mesh.devices.size),
            "lower_seconds": round(lower_s, 2),
            "status": "lowered",
            "note": cell.note,
        }
        if not compile_:
            return rec

        t1 = now()
        compiled = lowered.compile()
        rec["compile_seconds"] = round(now() - t1, 2)
        rec["status"] = "compiled"

        mem = compiled.memory_analysis()
        if mem is not None:
            for attr in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "alias_size_in_bytes",
                "generated_code_size_in_bytes",
            ):
                if hasattr(mem, attr):
                    rec.setdefault("memory", {})[attr] = int(getattr(mem, attr))
        cost = compiled.cost_analysis()
        if cost:
            rec["cost"] = {
                k: float(v)
                for k, v in cost.items()
                if isinstance(v, (int, float)) and k in (
                    "flops", "bytes accessed", "bytes accessed output",
                    "optimal_seconds", "utilization operand 0",
                )
            }
            # keep all numeric keys too (backend-dependent naming)
            rec["cost_all"] = {
                k: float(v) for k, v in cost.items() if isinstance(v, (int, float))
            }
        hlo = compiled.as_text()
        rec["collectives"] = collective_bytes_from_hlo(hlo)
        rec["model_flops"] = _model_flops(bundle, model, cell, cell.inputs())

        # loop-corrected totals: LM graphs wrap layers (and microbatches) in
        # lax.scan, which HloCostAnalysis counts once — probe & extrapolate.
        if (bundle.family == "recsys" and arch == "two-tower-retrieval"
                and shape_name == "retrieval_cand"):
            om = opt_compiled.memory_analysis()
            rec["supermetric_variant"] = {
                "budget_blocks": 3136,
                "of_blocks": -(-cell.inputs()["candidates"].shape[0] // 128),
                **_measure(opt_compiled),
                "memory": {
                    a: int(getattr(om, a)) for a in (
                        "argument_size_in_bytes", "output_size_in_bytes",
                        "temp_size_in_bytes", "alias_size_in_bytes")
                    if hasattr(om, a)
                },
            }
        if bundle.family == "lm":
            rec["corrected"] = probe_lm_cell(model, bundle.family, cell, mesh, batch_sds)
        else:
            rec["corrected"] = {
                "method": "loop-free graph: measured == true (per-device)",
                "total": {
                    "flops": rec.get("cost_all", {}).get("flops", 0.0),
                    "bytes": rec.get("cost_all", {}).get("bytes accessed", 0.0),
                    "coll": {k: float(v) for k, v in rec["collectives"].items()},
                },
            }
        return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--lower-only", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    meshes = []
    if args.both_meshes:
        meshes = [False, True]
    else:
        meshes = [args.multi_pod]

    cells = all_cells() if args.all else [(args.arch, args.shape)]
    failures = []
    for multi_pod in meshes:
        mesh = make_production_mesh(multi_pod=multi_pod)
        tag = "multipod" if multi_pod else "singlepod"
        for arch, shape in cells:
            fname = outdir / f"{arch.replace('/', '_')}__{shape}__{tag}.json"
            if args.skip_existing and fname.exists():
                ok = json.loads(fname.read_text()).get("status") in (
                    "compiled", "skipped")
                if ok:
                    print(f"[skip existing] {fname.name}")
                    continue
            print(f"=== {arch} x {shape} [{tag}] ===", flush=True)
            try:
                rec = lower_cell(arch, shape, mesh, compile_=not args.lower_only)
                fname.write_text(json.dumps(rec, indent=2))
                mem = rec.get("memory", {})
                print(
                    f"  status={rec['status']} "
                    f"args={mem.get('argument_size_in_bytes', 0)/2**30:.2f}GiB "
                    f"temps={mem.get('temp_size_in_bytes', 0)/2**30:.2f}GiB "
                    f"flops={rec.get('cost', {}).get('flops', 0):.3e} "
                    f"coll={rec.get('collectives', {}).get('total', 0)/2**30:.3f}GiB",
                    flush=True,
                )
            except Exception as e:  # noqa: BLE001
                failures.append((arch, shape, tag, repr(e)))
                fname.write_text(json.dumps({
                    "arch": arch, "shape": shape, "status": "failed",
                    "error": traceback.format_exc(),
                }, indent=2))
                print(f"  FAILED: {e}", flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nALL CELLS OK")


if __name__ == "__main__":
    main()
