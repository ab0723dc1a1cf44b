"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
record memory / cost / collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun                  # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --mesh multi_pod --force

Results append to experiments/dryrun/<arch>__<shape>__<mesh>.json; already-
present cells are skipped unless --force (the full sweep takes a while on
one CPU core, so it is resumable)."""
import argparse
import json
import os
import time
import traceback
from pathlib import Path

import jax

from repro.configs.base import SHAPES
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step
from repro.models import get_config, list_archs

RESULTS_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def plan_cells(arch_filter=None, shape_filter=None, mesh_filter=None):
    """The 40 assigned cells x 2 meshes, minus documented skips."""
    cells = []
    for arch in list_archs():
        cfg = get_config(arch)
        for sname, shape in SHAPES.items():
            if sname == "long_500k" and not cfg.sub_quadratic:
                continue  # full-attention arch: documented skip
            for mesh_kind in ("single_pod", "multi_pod"):
                if arch_filter and arch != arch_filter:
                    continue
                if shape_filter and sname != shape_filter:
                    continue
                if mesh_filter and mesh_kind != mesh_filter:
                    continue
                cells.append((arch, sname, mesh_kind))
    return cells


def run_cell(arch: str, shape_name: str, mesh_kind: str, opts=None) -> dict:
    """Lower + compile one cell; return the analysis record."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi_pod"))
    n_chips = mesh.devices.size
    t0 = time.time()
    opts = dict(opts or {})
    if shape.kind == "train":
        # Megatron-style sequence parallelism: measured win on every train
        # cell (see EXPERIMENTS.md §Perf iteration 4).
        opts.setdefault("seq_parallel", True)
    built = build_step(cfg, mesh, shape, **opts)
    lowered = built.fn.lower(*built.abstract_args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = hlo_analysis.analyze_hlo(compiled.as_text())

    # Loop-weighted analysis (XLA's cost_analysis counts while bodies once
    # — see hlo_analysis docstring). flops: dot ops x trip counts (exact
    # for einsum-dominated models). hbm bytes: fusion-boundary traffic
    # upper bound; the lower bound is the argument working set read once.
    flops = float(hlo.flops)
    bytes_upper = float(hlo.hbm_bytes)
    bytes_lower = float(mem.argument_size_in_bytes)
    terms = hlo_analysis.roofline_terms(flops, bytes_upper, hlo.collective_bytes)
    terms["memory_lower_s"] = bytes_lower / hlo_analysis.HBM_BW

    # Useful-FLOPs baseline: 6*N*D train / 2*N per decoded token.
    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind == "train" else 1)
    if shape.kind == "train":
        model_flops = 6 * n_active * tokens
    elif shape.kind == "prefill":
        model_flops = 2 * n_active * shape.global_batch * shape.seq_len
    else:
        model_flops = 2 * n_active * tokens
    model_flops_per_dev = model_flops / n_chips

    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "n_chips": int(n_chips),
        "kind": shape.kind,
        "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "params": int(n_params),
        "active_params": int(n_active),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            # args + temps + non-aliased outputs (donated buffers alias).
            "peak_bytes": mem.argument_size_in_bytes
            + mem.temp_size_in_bytes
            + max(mem.output_size_in_bytes - mem.alias_size_in_bytes, 0),
        },
        "cost": {
            "flops_per_device": flops,
            "bytes_per_device": bytes_upper,
            "bytes_lower_per_device": bytes_lower,
            "xla_raw_flops": float(cost.get("flops", 0.0)),
            "xla_raw_bytes": float(cost.get("bytes accessed", 0.0)),
        },
        "collectives": {
            "total_bytes": hlo.collective_bytes,
            "bytes_by_op": dict(hlo.collective_bytes_by_op),
            "count_by_op": dict(hlo.collective_count_by_op),
            "raw_bytes_loop_once": hlo.raw_collective_bytes,
        },
        "roofline": terms,
        "model_flops_per_device": model_flops_per_dev,
        "useful_flop_ratio": (model_flops_per_dev / flops) if flops else None,
    }


def run_store_cell(mesh_kind: str, rows_per_tablet: int = 4_000_000) -> dict:
    """Extra (beyond the 40 assigned cells): the paper's OWN system on the
    production mesh — the distributed tablet scan (filter + count + top-k)
    lowered and compiled with every chip acting as a tablet server.
    4M rows x 12 fields/tablet = ~1B rows (~200 GB columnar) single-pod."""
    from repro.core import And, Eq, Not, web_proxy_schema, EventStore
    from repro.core.dist_query import build_scan_step, dist_store_shapes
    from repro.core.filter import compile_tree
    from repro.kernels.filter_scan.ops import pad_program

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi_pod"))
    n_chips = mesh.devices.size
    store = EventStore(web_proxy_schema(), n_shards=4)  # schema carrier
    store.ingest(
        [0, 1], {"domain": ["a.com", "b.com"], "method": ["GET", "POST"], "status": ["200", "404"]}
    )
    tree = And(Eq("domain", "a.com"), Not(Eq("status", "404")))
    prog = compile_tree(store, tree)
    opc, a0, a1, cs = pad_program(prog)
    shapes = dist_store_shapes(mesh, rows_per_tablet, store.schema.n_fields)
    step = build_scan_step(mesh, store.schema.n_fields, len(opc), cs.shape)
    import jax.numpy as jnp

    t0 = time.time()
    lowered = step.lower(
        shapes["rev_ts"], shapes["cols"], shapes["counts"],
        jax.ShapeDtypeStruct(opc.shape, jnp.int32), jax.ShapeDtypeStruct(a0.shape, jnp.int32),
        jax.ShapeDtypeStruct(a1.shape, jnp.int32), jax.ShapeDtypeStruct(cs.shape, jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32), jax.ShapeDtypeStruct((), jnp.int32),
    )
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    hlo = hlo_analysis.analyze_hlo(compiled.as_text())
    terms = hlo_analysis.roofline_terms(hlo.flops, hlo.hbm_bytes, hlo.collective_bytes)
    return {
        "arch": "llcysa-store",
        "shape": f"scan_{rows_per_tablet * n_chips // 10**6}M_rows",
        "mesh": mesh_kind,
        "n_chips": int(n_chips),
        "kind": "scan",
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes": mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + max(mem.output_size_in_bytes - mem.alias_size_in_bytes, 0),
        },
        "cost": {"flops_per_device": hlo.flops, "bytes_per_device": hlo.hbm_bytes},
        "collectives": {
            "total_bytes": hlo.collective_bytes,
            "bytes_by_op": dict(hlo.collective_bytes_by_op),
        },
        "roofline": terms,
    }


def main() -> None:
    # Must run before the first jax call creates the CPU client: the device
    # count is fixed then, and the multi-pod dry-run needs 512 placeholder
    # host devices. Set here, not at import, so importing this module (the
    # tests import plan_cells) leaves the process's devices alone.
    os.environ["XLA_FLAGS"] = " ".join(
        (os.environ.get("XLA_FLAGS", ""), "--xla_force_host_platform_device_count=512")
    ).strip()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default=None, choices=[None, "single_pod", "multi_pod"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--zero1", action="store_true", default=True)
    ap.add_argument("--store-cells", action="store_true", help="run ONLY the extra llcysa-store cells")
    args = ap.parse_args()

    if args.store_cells:
        RESULTS_DIR.mkdir(parents=True, exist_ok=True)
        for mesh_kind in ("single_pod", "multi_pod"):
            rec = run_store_cell(mesh_kind)
            out = RESULTS_DIR / f"llcysa-store__{rec['shape']}__{mesh_kind}.json"
            out.write_text(json.dumps(rec, indent=1))
            r = rec["roofline"]
            print(
                f"OK  llcysa-store {rec['shape']} {mesh_kind} compile={rec['compile_s']:.1f}s "
                f"peak={rec['memory']['peak_bytes']/2**30:.2f}GiB "
                f"comp={r['compute_s']:.2e}s mem={r['memory_s']:.2e}s coll={r['collective_s']:.2e}s",
                flush=True,
            )
        return

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    cells = plan_cells(args.arch, args.shape, args.mesh)
    print(f"dry-run: {len(cells)} cells on {len(jax.devices())} host devices")
    n_ok = n_skip = n_fail = 0
    for arch, sname, mesh_kind in cells:
        out = RESULTS_DIR / f"{arch}__{sname}__{mesh_kind}.json"
        if out.exists() and not args.force:
            n_skip += 1
            continue
        try:
            rec = run_cell(arch, sname, mesh_kind)
            out.write_text(json.dumps(rec, indent=1))
            r = rec["roofline"]
            print(
                f"OK  {arch:22s} {sname:12s} {mesh_kind:10s} "
                f"compile={rec['compile_s']:7.1f}s peak={rec['memory']['peak_bytes']/2**30:6.2f}GiB "
                f"comp={r['compute_s']:.2e}s mem={r['memory_s']:.2e}s coll={r['collective_s']:.2e}s "
                f"bound={r['bottleneck']}",
                flush=True,
            )
            n_ok += 1
        except Exception as e:  # noqa: BLE001 — a failing cell is a bug; record it
            n_fail += 1
            err = {"arch": arch, "shape": sname, "mesh": mesh_kind, "error": repr(e),
                   "traceback": traceback.format_exc()}
            (RESULTS_DIR / f"FAIL__{arch}__{sname}__{mesh_kind}.json").write_text(
                json.dumps(err, indent=1)
            )
            print(f"FAIL {arch} {sname} {mesh_kind}: {e!r}", flush=True)
    print(f"done: ok={n_ok} skipped={n_skip} failed={n_fail}")


if __name__ == "__main__":
    main()
