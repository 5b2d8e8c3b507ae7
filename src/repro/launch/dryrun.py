import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (arch × shape × mesh) cell lowers,
partitions, and compiles on the production meshes, and extract the roofline
inputs (memory analysis, FLOPs/bytes, collective traffic) from the compiled
artifact.

The line above MUST stay first — jax locks the device count at first
initialization, and the dry-run needs 512 placeholder host devices to build
the (2, 16, 16) production mesh. Nothing here allocates: all model state is
ShapeDtypeStruct.

Usage:
  python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k --mesh single
  python -m repro.launch.dryrun --arch whisper-base --shape train_4k --optimizer ngd
  python -m repro.launch.dryrun --solver 4096 1000000 --mesh multi
  python -m repro.launch.dryrun --all --mesh both          # every cell, subprocesses
"""
import argparse
import json
import pathlib
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs.shapes import SHAPES, applicable
from repro.launch import hlo_analysis
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import MODEL, make_production_mesh
from repro.launch.shardings import param_shardings, tree_size
from repro.models.api import get_api, make_input_specs

ART = pathlib.Path(os.environ.get("REPRO_ART", "artifacts")) / "dryrun"


def active_params(param_specs, cfg) -> tuple[int, int]:
    """(total, active) parameter counts; MoE experts scaled by top_k/E."""
    total = active = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(param_specs):
        n = int(np.prod(leaf.shape))
        total += n
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in path)
        if leaf.ndim == 4 and re.search(r"w_(gate|up|down)$", key):
            n = int(n * cfg.top_k / max(cfg.n_experts, 1))
        active += n
    return total, active


def model_flops(cfg, kind: str, seq: int, batch: int, n_active: int) -> float:
    tokens = batch * (seq if kind in ("train", "prefill") else 1)
    if cfg.family in ("encdec", "audio"):
        tokens = batch * (min(seq, cfg.max_target_positions)
                          if kind in ("train", "prefill") else 1)
    mult = 6 if kind == "train" else 2
    return float(mult) * n_active * tokens


def _apply_overrides(cfg, overrides: dict):
    import dataclasses
    if not overrides:
        return cfg
    typed = {}
    for k, v in overrides.items():
        cur = getattr(cfg, k)
        if isinstance(cur, bool):
            typed[k] = v in ("1", "true", "True")
        elif isinstance(cur, int):
            typed[k] = int(v)
        elif isinstance(cur, float):
            typed[k] = float(v)
        else:
            typed[k] = v
    return dataclasses.replace(cfg, **typed)


def build_lowered(arch: str, shape_name: str, mesh, *, optimizer="adamw",
                  overrides=None, ngd_opts=None, variant="baseline"):
    """Lower one cell. Returns (lowered, meta)."""
    overrides = dict(overrides or {})
    fsdp = overrides.pop("fsdp", "auto")      # launch-level knob, not cfg
    if fsdp != "auto":
        fsdp = fsdp in ("1", "true", "True")
    donate = overrides.pop("donate", "false") in ("1", "true", "True")
    base = configs.get_tuned(arch, kind=SHAPES[shape_name].kind) \
        if variant == "tuned" else configs.get_config(arch)
    if variant == "tuned":
        donate = True           # production setting for the tuned variant
        if base.moe_ep_over_data and fsdp == "auto":
            fsdp = False        # EP-over-data pairs with replicated attn
    cfg = _apply_overrides(base, overrides)
    api = get_api(cfg)
    shape = SHAPES[shape_name]
    pspecs = api.param_specs()
    ispecs = make_input_specs(cfg, kind=shape.kind, seq=shape.seq,
                              batch=shape.batch)
    n_total, n_active = active_params(pspecs, cfg)
    meta = {"arch": arch, "shape": shape_name, "kind": shape.kind,
            "seq": shape.seq, "batch": shape.batch, "optimizer": optimizer,
            "params_total": n_total, "params_active": n_active,
            "model_flops": model_flops(cfg, shape.kind, shape.seq,
                                       shape.batch, n_active)}

    from repro.launch import train as T
    if shape.kind == "train":
        if optimizer == "ngd":
            from repro.optim import NaturalGradient
            opt = NaturalGradient(1e-3, damping=1e-3)
            ngd_opts = ngd_opts or {}
            jfn, _ = T.jit_ngd_train_step(
                api, opt, mesh, param_specs=pspecs, input_specs=ispecs,
                score_chunk=min(32, shape.batch), donate=donate,
                score_dtype=ngd_opts.get("score_dtype"),
                score_sharding=ngd_opts.get("score_sharding", "1d"),
                replicate_model=bool(ngd_opts.get("replicate_model")))
        else:
            from repro.optim import AdamW
            opt = AdamW(3e-4)
            jfn, _ = T.jit_train_step(api, opt, mesh, param_specs=pspecs,
                                      input_specs=ispecs, donate=donate,
                                      fsdp=fsdp,
                                      ep_over_data=cfg.moe_ep_over_data)
        opt_specs = jax.eval_shape(opt.init, pspecs)
        lowered = jfn.lower(pspecs, opt_specs, ispecs)
    elif shape.kind == "prefill":
        jfn, _ = T.jit_prefill(api, mesh, param_specs=pspecs,
                               input_specs=ispecs)
        lowered = jfn.lower(pspecs, ispecs)
    else:  # decode
        jfn, _ = T.jit_serve_step(api, mesh, param_specs=pspecs,
                                  input_specs=ispecs, donate=False)
        lowered = jfn.lower(pspecs, ispecs["cache"], ispecs["cache_index"],
                            ispecs["tokens"])
    return lowered, meta


def build_solver_lowered(n: int, m: int, mesh):
    """Paper-scale solver dry-run: Algorithm 1 on an (n, m) score matrix
    sharded over the model axis (the RVB+23 layout)."""
    from repro.core import chol_solve
    S = jax.ShapeDtypeStruct((n, m), jnp.float32)
    v = jax.ShapeDtypeStruct((m,), jnp.float32)
    sshard = NamedSharding(mesh, P(None, MODEL))
    vshard = NamedSharding(mesh, P(MODEL))
    fn = jax.jit(lambda S, v: chol_solve(S, v, 1e-3),
                 in_shardings=(sshard, vshard), out_shardings=vshard)
    meta = {"arch": f"solver_n{n}_m{m}", "shape": "paper", "kind": "solver",
            "seq": n, "batch": m, "optimizer": "chol",
            "params_total": m, "params_active": m,
            "model_flops": float(n) * n * m + n ** 3 / 3 + 2.0 * n * m}
    return fn.lower(S, v), meta


def compile_and_analyze(lowered, meta, mesh) -> dict:
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    txt = compiled.as_text()
    # trip-count-aware structural analysis (XLA's cost_analysis counts
    # while bodies once — see hlo_analysis docstring); cost_analysis totals
    # are recorded below as a lower-bound cross-check.
    mod = hlo_analysis.analyze_module(txt)
    coll = mod["collectives"]
    chips = int(np.prod(list(mesh.shape.values())))
    roof = hlo_analysis.roofline(
        flops=mod["flops"],
        hbm_bytes=mod["hbm_bytes"],
        wire_bytes=float(coll["total_wire_bytes"]),
        model_flops=meta["model_flops"], chips=chips)
    rec = {
        **meta,
        "mesh_shape": dict(mesh.shape),
        "chips": chips,
        "compile_s": round(compile_s, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            # temp_size has no liveness analysis (sums all temporaries);
            # peak_memory is the buffer-assignment high-water mark and is
            # the number checked against the 16 GB v5e HBM budget.
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
            "resident_bytes": (mem.argument_size_in_bytes
                               + getattr(mem, "peak_memory_in_bytes", 0)),
        },
        "cost": {"flops": mod["flops"],
                 "hbm_bytes": mod["hbm_bytes"],
                 "xla_flops_lower_bound": float(cost.get("flops", 0.0)),
                 "xla_bytes_lower_bound": float(cost.get("bytes accessed",
                                                         0.0))},
        "collectives": coll,
        "roofline": roof,
    }
    return rec


def run_cell(arch, shape_name, mesh_kind, optimizer="adamw",
             solver_nm=None, overrides=None, ngd_opts=None,
             variant="baseline") -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    # explicit mesh context: lets opt-in perf levers use bare-PartitionSpec
    # with_sharding_constraint (jax resolves axis names against this mesh).
    jax.sharding.set_mesh(mesh)
    if solver_nm:
        lowered, meta = build_solver_lowered(*solver_nm, mesh)
    else:
        lowered, meta = build_lowered(arch, shape_name, mesh,
                                      optimizer=optimizer,
                                      overrides=overrides,
                                      ngd_opts=ngd_opts, variant=variant)
    rec = compile_and_analyze(lowered, meta, mesh)
    rec["mesh"] = mesh_kind
    rec["variant"] = variant
    if overrides:
        rec["overrides"] = overrides
    if ngd_opts:
        rec["ngd_opts"] = ngd_opts
    return rec


def _cell_id(rec):
    tag = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}"
    if rec["optimizer"] == "ngd":
        tag += "__ngd"
    return tag


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.list_archs())
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--optimizer", choices=["adamw", "ngd"],
                    default="adamw")
    ap.add_argument("--solver", nargs=2, type=int, metavar=("N", "M"))
    ap.add_argument("--all", action="store_true",
                    help="run every applicable cell in subprocesses")
    ap.add_argument("--out", default=str(ART))
    ap.add_argument("--override", action="append", default=[],
                    metavar="K=V", help="ModelConfig field override "
                    "(perf levers, e.g. remat=full ssd_factored=true)")
    ap.add_argument("--ngd-score-sharding", choices=["1d", "2d"],
                    default="1d")
    ap.add_argument("--ngd-score-dtype", default=None,
                    choices=[None, "bfloat16", "float32"])
    ap.add_argument("--ngd-replicate-model", action="store_true")
    ap.add_argument("--tag", default="",
                    help="suffix for the output JSON (hillclimb variants)")
    ap.add_argument("--variant", choices=["baseline", "tuned"],
                    default="baseline",
                    help="tuned = CONFIG + confirmed §Perf levers + donation")
    args = ap.parse_args(argv)
    use_compile_cache()
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    if args.all:
        meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        cells = []
        for arch in configs.list_archs():
            cfg = configs.get_config(arch)
            for sname in SHAPES:
                if applicable(cfg, sname):
                    for mk in meshes:
                        cells.append((arch, sname, mk, "adamw"))
        # the NGD showcase cells (DESIGN.md §5): whisper-base train
        for mk in meshes:
            cells.append(("whisper-base", "train_4k", mk, "ngd"))
        failures = []
        for arch, sname, mk, optname in cells:
            tag = f"{arch}__{sname}__{mk}" + ("__ngd" if optname == "ngd" else "")
            if args.variant == "tuned":
                tag += "__tuned"
            if (out / f"{tag}.json").exists():
                print(f"[skip cached] {tag}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", sname, "--mesh", mk,
                   "--optimizer", optname, "--out", str(out),
                   "--variant", args.variant]
            if args.variant == "tuned":
                cmd += ["--tag", "tuned"]
                if optname == "ngd":
                    # confirmed NGD schedule (§Perf Cell 3); attention
                    # levers are refuted for the NGD step
                    cmd += ["--ngd-score-sharding", "2d",
                            "--ngd-replicate-model",
                            "--override", "attn_seq_shard=false",
                            "--override", "attn_bf16=false"]
            print(f"[run] {tag}", flush=True)
            r = subprocess.run(cmd, capture_output=True, text=True)
            if r.returncode != 0:
                failures.append((tag, r.stderr[-2000:]))
                print(f"[FAIL] {tag}\n{r.stderr[-2000:]}", flush=True)
        print(f"\n{len(cells) - len(failures)}/{len(cells)} cells OK")
        if failures:
            sys.exit(1)
        return

    overrides = dict(kv.split("=", 1) for kv in args.override)
    ngd_opts = {"score_sharding": args.ngd_score_sharding,
                "score_dtype": args.ngd_score_dtype,
                "replicate_model": args.ngd_replicate_model}
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for mk in meshes:
        rec = run_cell(args.arch, args.shape, mk, optimizer=args.optimizer,
                       solver_nm=tuple(args.solver) if args.solver else None,
                       overrides=overrides, ngd_opts=ngd_opts,
                       variant=args.variant)
        tag = _cell_id(rec) + (f"__{args.tag}" if args.tag else "")
        path = out / f"{tag}.json"
        path.write_text(json.dumps(rec, indent=1))
        m = rec["memory"]
        r = rec["roofline"]
        print(f"{tag}: compile={rec['compile_s']}s "
              f"peak/dev={m['peak_bytes'] / 2**30:.2f}GiB "
              f"args/dev={m['argument_bytes'] / 2**30:.2f}GiB "
              f"flops/dev={rec['cost']['flops']:.3e} "
              f"roofline=[{r['t_compute_s']:.4f}, {r['t_memory_s']:.4f}, "
              f"{r['t_collective_s']:.4f}]s dominant={r['dominant']}")


if __name__ == "__main__":
    main()
