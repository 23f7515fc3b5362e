"""Multi-pod dry-run: prove every (architecture × input shape × mesh)
cell lowers AND compiles under the production sharding config, and
extract the roofline terms from the compiled artifact.

MUST be run as its own process (``python -m repro.launch.dryrun``): run
as the main module it appends ``--xla_force_host_platform_device_count=
512`` to ``XLA_FLAGS`` before any jax import, since jax locks the device
count on first init. Importing the module sets nothing, so smoke tests
and benches keep seeing 1 device. It exits 1 when any arch or cell
fails.

Per cell it records into results/dryrun/<cell>.json:
  * compiled.memory_analysis()  — per-device bytes (proves it fits)
  * compiled.cost_analysis()    — HLO FLOPs / bytes for §Roofline
  * collective_bytes by op kind — parsed from the optimized HLO
  * the three roofline terms + dominant bottleneck (§Roofline)

Usage:
  python -m repro.launch.dryrun --all [--mesh single|multi|both]
  python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k
  python -m repro.launch.dryrun --list
  python -m repro.launch.dryrun --pardnn --arch gemma3-1b \
      --pardnn-devices 4                       # emit PartitionPlan files
  python -m repro.launch.dryrun --calibrate --arch repro-lm-100m \
      --pardnn-devices 4   # profile ops/links, fit + save a
                           # CalibrationProfile, report stage MAPE
Flags for §Perf iterations: --remat, --tag (variant label kept in the
result file name so baselines are never overwritten).

``--pardnn`` goes through the ``repro`` facade: it traces each arch's
reduced training step, partitions it, and writes the versioned plan
artifact next to the dry-run results — the op-level counterpart of the
mesh cells above.
"""
import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"),
        "--xla_force_host_platform_device_count=512")))

import argparse       # noqa: E402
import json           # noqa: E402
import re             # noqa: E402
import time           # noqa: E402
import traceback      # noqa: E402

import jax            # noqa: E402
import numpy as np    # noqa: E402

from repro.configs import (ASSIGNED_ARCHS, SHAPES, get_config,  # noqa: E402
                           shape_skip_reason)
from repro.core.costmodel import (TPU_V5E_HBM_BW, TPU_V5E_ICI_BW,  # noqa: E402
                                  TPU_V5E_PEAK_FLOPS)
from repro.launch.mesh import make_production_mesh, mesh_num_chips  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> float:
    if dtype not in _DTYPE_BYTES:
        return 0.0
    n = 1.0
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes_from_hlo(hlo: str) -> dict:
    """Sum operand sizes of every collective op in optimized HLO.

    HLO text inlines operand shapes:
      %ag = bf16[512,14336]{...} all-gather(bf16[32,14336]{...} %p), ...
    The first shape on the line is the result; the rest are operands.
    '-done' ops are skipped (their '-start' was counted)."""
    out = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo.splitlines():
        line = line.strip()
        if "-done" in line:
            continue
        m = re.search(r"=\s*(?:\([^)]*\)\s*)?[a-z0-9]+\[[0-9,]*\][^ ]*\s+"
                      r"([a-z\-]+)", line)
        if not m:
            continue
        op = m.group(1).replace("-start", "")
        if op not in _COLLECTIVES:
            continue
        # operands = shapes appearing inside the call parens
        paren = line.find(op)
        args = line[paren:]
        shapes = _SHAPE_RE.findall(args)
        nbytes = sum(_shape_bytes(d, s) for d, s in shapes)
        if nbytes == 0.0:
            # fall back to the result shape
            shapes = _SHAPE_RE.findall(line[:paren])
            nbytes = sum(_shape_bytes(d, s) for d, s in shapes[:1])
        out[op] += nbytes
        counts[op] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": float(sum(out.values()))}


def roofline_terms(flops: float, hbm_bytes: float, coll_bytes: float,
                   chips: int) -> dict:
    t_c = flops / (chips * TPU_V5E_PEAK_FLOPS)
    t_m = hbm_bytes / (chips * TPU_V5E_HBM_BW)
    t_x = coll_bytes / (chips * TPU_V5E_ICI_BW)
    dom = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))
    return {"compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
            "dominant": dom[1], "bound_s": dom[0]}


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE); decode D=batch
    tokens; train includes the 3x backward factor already (6 = 2 fwd + 4
    bwd per param per token)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch  # decode: 1 token/slot


def _lower_cell(cfg, shape, mesh, remat: str):
    from repro.train.step import (abstract_train_args, abstract_serve_args,
                                  build_serve_step, build_train_step)
    if shape.kind == "train":
        built = build_train_step(cfg, mesh, remat_policy=remat)
        p_abs, o_abs, b_abs = abstract_train_args(cfg, mesh, shape)
        return built.fn.lower(p_abs, o_abs, b_abs)
    if shape.kind == "prefill":
        from repro.train.step import build_prefill_step
        from repro.models.io_spec import params_spec, prefill_batch_spec
        built = build_prefill_step(cfg, mesh, max_len=shape.seq_len)
        return built.fn.lower(
            params_spec(cfg),
            prefill_batch_spec(cfg, shape.global_batch, shape.seq_len))
    from repro.models.io_spec import params_spec
    built = build_serve_step(cfg, mesh, shape)
    c_abs, t_abs, pos_abs = abstract_serve_args(cfg, shape)
    return built.fn.lower(params_spec(cfg), c_abs, t_abs, pos_abs)


def _compile_metrics(cfg, shape, mesh, remat: str) -> dict:
    """Lower+compile once; return cost/collective metrics."""
    lowered = _lower_cell(cfg, shape, mesh, remat)
    compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": coll, "hlo_size": len(hlo), "compiled": compiled}


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             remat: str = "dots", tag: str = "",
             roofline: bool = True) -> dict:
    """One dry-run cell.

    Compile #1 (deployment program, scanned): proves lower+compile,
    memory_analysis, collective schedule. Compiles #2/#3 (ROOFLINE_MODE,
    layer-scan unroll 1 and 2): XLA's HloCostAnalysis counts while bodies
    once, so with u body copies cost(u) = fixed + u·body; two points give
    body = C2−C1 and the exact per-device total fixed + P·body =
    C1 + (P−1)·(C2−C1), with inner scans (attention kv-chunks, CE chunks,
    ssm chunks) flattened by ROOFLINE_MODE. Costs are per-device (the
    SPMD module is one replica's program): global = per-device × chips."""
    from repro.configs.base import SHAPES
    from repro.models.layers import roofline_mode
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    skip = shape_skip_reason(cfg, shape)
    if skip:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "SKIP", "reason": skip}
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh_num_chips(mesh)

    # --- compile 1: the deployment program --------------------------------
    t0 = time.perf_counter()
    lowered = _lower_cell(cfg, shape, mesh, remat)
    t_lower = time.perf_counter() - t0
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower
    mem = compiled.memory_analysis()
    mem_d = {}
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                mem_d[k] = int(v)
    hlo = compiled.as_text()
    sched_coll = collective_bytes_from_hlo(hlo)
    res = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "status": "OK", "tag": tag, "remat": remat, "chips": chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": mem_d,
        "per_device_total_bytes": (
            (mem_d.get("argument_size_in_bytes", 0)
             + mem_d.get("temp_size_in_bytes", 0)
             + mem_d.get("output_size_in_bytes", 0)
             - mem_d.get("alias_size_in_bytes", 0)) if mem_d else None),
        "collective_schedule": sched_coll["counts"],
        "hlo_size_chars": len(hlo),
    }
    del compiled, hlo

    # --- compiles 2+3: roofline accounting --------------------------------
    if roofline and mesh_kind == "single":
        P_ = cfg.num_periods
        with roofline_mode(1):
            c1 = _compile_metrics(cfg, shape, mesh, remat)
        if P_ > 1:
            with roofline_mode(2):
                c2 = _compile_metrics(cfg, shape, mesh, remat)
            def extrap(a, b):
                return a + (P_ - 1) * (b - a)
            flops_dev = extrap(c1["flops"], c2["flops"])
            bytes_dev = extrap(c1["bytes"], c2["bytes"])
            coll_dev = {k: extrap(c1["coll"]["bytes"][k],
                                  c2["coll"]["bytes"][k])
                        for k in c1["coll"]["bytes"]}
        else:
            flops_dev, bytes_dev = c1["flops"], c1["bytes"]
            coll_dev = c1["coll"]["bytes"]
        coll_total_dev = float(sum(max(v, 0.0) for v in coll_dev.values()))
        flops_global = flops_dev * chips
        bytes_global = bytes_dev * chips
        coll_global = coll_total_dev * chips
        terms = roofline_terms(flops_global, bytes_global, coll_global,
                               chips)
        mf = model_flops(cfg, shape)
        res.update({
            "hlo_flops": flops_global,
            "hlo_bytes": bytes_global,
            "collective_bytes_by_op": {k: v * chips
                                       for k, v in coll_dev.items()},
            "collective_bytes": coll_global,
            "roofline": terms,
            "model_flops": mf,
            "useful_flops_ratio": (mf / flops_global if flops_global
                                   else None),
        })
    return res


def run_pardnn_plan(arch: str, devices: int, out_dir: str,
                    mem_cap_mb: float | None = None,
                    execute: bool = False, lint: bool = False,
                    trace: str | None = None) -> dict:
    """Trace the arch's reduced train step and emit a versioned
    :class:`repro.api.PartitionPlan` artifact (JSON header + npz).

    With ``execute=True`` the placement is additionally *run* through
    both execution engines (this process forces 512 host devices, so
    every pe gets a real device): the op-by-op interpreter and the
    compiled segment runtime — and the result records the
    interpreter-vs-compiled speedup plus measured-vs-predicted peak
    bytes per device, the execution-side counterpart of the
    memory_analysis numbers the mesh cells above report.

    With ``lint=True`` the program is recorded even without execution so
    the full static verifier (``repro.analysis``) can run, and the
    diagnostic report is written next to the plan. Either way
    ``plan.save`` refuses to write a plan carrying error-severity
    diagnostics — the caller sees the raise, not a silent artifact."""
    import repro
    from repro.configs import reduced
    from repro.models import init_params, loss_fn, smoke_batch

    cfg = reduced(get_config(arch))
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = smoke_batch(cfg)
    traced = repro.trace(lambda p: loss_fn(cfg, p, batch)[0], params,
                         record=execute or lint)
    plan = repro.partition(
        traced, devices=devices,
        memory=mem_cap_mb * 1e6 if mem_cap_mb else None,
        meta={"arch": arch, "config": "reduced", "source": "dryrun"})
    path = os.path.join(out_dir, f"{arch}__pardnn_k{devices}.plan.json")
    res = {"arch": arch, "ops": plan.n, "path": path,
           "makespan_s": plan.makespan, "feasible": plan.feasible}
    vrep = plan.verify()
    res["diagnostics"] = vrep.summary_dict()
    res["verify_errors"] = len(vrep.errors)
    if lint:
        lpath = os.path.join(out_dir,
                             f"{arch}__pardnn_k{devices}.diagnostics.json")
        with open(lpath, "w") as f:
            json.dump(vrep.to_dict(), f, indent=1)
        res["diagnostics_path"] = lpath
    if execute:
        res["runtime"] = plan.benchmark_runtimes(params, reps=1)
        plan.meta["runtime"] = res["runtime"]
        if trace:
            # one traced execution on top of the benchmark: merged
            # measured + predicted device lanes (see repro.obs.trace)
            plan.execute(params, trace=trace)
            res["trace_path"] = trace
    plan.save(path)
    return res


def run_calibration_cell(arch: str, devices: int, out_dir: str,
                         tiny: bool = False) -> dict:
    """Close the predict→execute loop for one arch: profile the reduced
    training step's ops + links, fit the device model, save the
    :class:`~repro.profiling.CalibrationProfile` artifact next to the
    dry-run results, re-annotate, re-partition, and score the Step-2
    emulator's per-stage predictions against the segment runtime's
    measured times (``PartitionPlan.accuracy_report``)."""
    import repro
    from repro.configs import reduced
    from repro.models import init_params, loss_fn, smoke_batch
    from repro.profiling import MeasureSpec, quick_spec

    cfg = reduced(get_config(arch))
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = smoke_batch(cfg, batch=2, seq=16 if tiny else 32)
    traced = repro.trace(lambda p: loss_fn(cfg, p, batch)[0], params,
                         record=True)
    ppath = os.path.join(out_dir, f"{arch}__calibration.json")
    spec = quick_spec(reps=2) if tiny else MeasureSpec()
    profile = repro.calibrate(traced, spec=spec,
                              max_signatures=40 if tiny else None,
                              meta={"arch": arch, "source": "dryrun"},
                              save=ppath)
    traced.annotate(profile)
    device_map = repro.fold_device_map(devices)
    plan = repro.partition(traced, devices=devices,
                           meta={"arch": arch, "source": "dryrun",
                                 "calibration": ppath})
    acc = plan.accuracy_report(params, device_map=device_map,
                               reps=2 if tiny else 3)
    return {"arch": arch, "ops": plan.n, "profile": ppath,
            "signatures": len(profile.ops), "fitted": profile.fitted,
            "stage_mape_pct": acc["stage_mape_pct"],
            "device_mape_pct": acc["device_mape_pct"],
            "measured_wall_s": acc["measured_wall_s"],
            "predicted_makespan_s": acc["predicted_makespan_s"],
            "summary": profile.summary()}


def cell_name(arch, shape, mesh_kind, tag=""):
    t = f"__{tag}" if tag else ""
    return f"{arch}__{shape}__{mesh_kind}{t}"


def _arch_path(path: str | None, arch: str, multi: bool) -> str | None:
    """Suffix the arch into ``path`` before the extension when one flag
    value has to fan out over several archs."""
    if path is None or not multi:
        return path
    root, ext = os.path.splitext(path)
    return f"{root}.{arch}{ext or '.json'}"


def _write_metrics(path: str, source: str, records: dict) -> None:
    from repro.obs.metrics import wrap_metrics
    with open(path, "w") as f:
        json.dump(wrap_metrics(source, {"records": records}), f, indent=1)
    print(f"wrote metrics {path}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--tag", default="")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--pardnn", action="store_true",
                    help="emit PartitionPlan artifacts via the repro "
                         "facade instead of lower/compile cells")
    ap.add_argument("--pardnn-devices", type=int, default=4)
    ap.add_argument("--pardnn-mem-cap-mb", type=float, default=None)
    ap.add_argument("--pardnn-execute", action="store_true",
                    help="also run the plan through both execution "
                         "engines and report interpreter-vs-compiled "
                         "speedup + measured-vs-predicted peak bytes")
    ap.add_argument("--lint", action="store_true",
                    help="with --pardnn: record the program so the full "
                         "static verifier runs, and write each plan's "
                         "diagnostic report next to its artifact")
    ap.add_argument("--calibrate", action="store_true",
                    help="profile real op/link costs, fit the device "
                         "model, save a CalibrationProfile per arch and "
                         "report predicted-vs-measured stage MAPE")
    ap.add_argument("--calibrate-tiny", action="store_true",
                    help="cheap calibration settings (CI smoke)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="with --pardnn --pardnn-execute: write a "
                         "Perfetto trace (measured + predicted lanes) of "
                         "each plan's compiled execution; multi-arch runs "
                         "suffix the arch before the extension")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="write the per-arch result records as one "
                         "versioned repro-metrics envelope JSON")
    args = ap.parse_args()

    if args.calibrate:
        os.makedirs(args.out, exist_ok=True)
        archs = ASSIGNED_ARCHS if args.arch is None else [args.arch]
        records = {}
        for a in archs:
            t0 = time.perf_counter()
            try:
                res = run_calibration_cell(a, args.pardnn_devices,
                                           args.out,
                                           tiny=args.calibrate_tiny)
                records[a] = res
                path = os.path.join(args.out, f"{a}__calibration_report"
                                              f".json")
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                mape = res["stage_mape_pct"]   # None: nothing scorable
                print(f"[OK] {a}: {res['summary']}; stage MAPE "
                      f"{'n/a' if mape is None else f'{mape:.1f}%'}, wall "
                      f"{res['measured_wall_s'] * 1e3:.1f} ms vs "
                      f"predicted {res['predicted_makespan_s'] * 1e3:.1f}"
                      f" ms -> {res['profile']} "
                      f"({time.perf_counter() - t0:.1f}s)", flush=True)
            except Exception as e:
                records[a] = {"arch": a,
                              "error": f"{type(e).__name__}: {e}"}
                print(f"[FAIL] {a}: {type(e).__name__}: {e}", flush=True)
        if args.metrics:
            _write_metrics(args.metrics, "dryrun_calibrate", records)
        return 1 if any("error" in r for r in records.values()) else 0

    if args.pardnn:
        os.makedirs(args.out, exist_ok=True)
        archs = ASSIGNED_ARCHS if args.arch is None else [args.arch]
        failed = 0
        records = {}
        multi = len(archs) > 1
        for a in archs:
            t0 = time.perf_counter()
            try:
                res = run_pardnn_plan(a, args.pardnn_devices, args.out,
                                      args.pardnn_mem_cap_mb,
                                      execute=args.pardnn_execute,
                                      lint=args.lint,
                                      trace=_arch_path(args.trace, a,
                                                       multi))
                records[a] = res
                dcounts = res["diagnostics"]["counts"]
                print(f"[OK] {a}: {res['ops']} ops, makespan "
                      f"{res['makespan_s'] * 1e3:.3f} ms, "
                      f"feasible={res['feasible']}, verified "
                      f"({dcounts['error']}E/{dcounts['warn']}W/"
                      f"{dcounts['info']}I) -> {res['path']} "
                      f"({time.perf_counter() - t0:.1f}s)", flush=True)
                rt = res.get("runtime")
                if rt:
                    mvp = " ".join(
                        f"d{i}:{m / 1e6:.1f}/{p / 1e6:.1f}MB"
                        for i, (m, p) in enumerate(zip(
                            rt["measured_peak_bytes"],
                            rt["predicted_peak_bytes"])))
                    print(f"     runtime: {rt['num_segments']} segments, "
                          f"{rt['transfers']} transfers, compiled "
                          f"{rt['compiled_s'] * 1e3:.1f} ms vs interpreter "
                          f"{rt['interpreter_s'] * 1e3:.0f} ms "
                          f"({rt['speedup']:.0f}x); measured/predicted "
                          f"peaks {mvp}", flush=True)
                    if "overlap_speedup" in rt:
                        print(f"     overlap: async "
                              f"{rt['compiled_s'] * 1e3:.1f} ms vs sync "
                              f"{rt['compiled_sync_s'] * 1e3:.1f} ms "
                              f"({rt['overlap_speedup']:.2f}x), "
                              f"{rt['prefetched_transfers']}/"
                              f"{rt['transfers']} transfers prefetched "
                              f"({rt['deferred_transfers']} deferred), "
                              f"sync/async drift "
                              f"{rt['sync_async_drift']:.3g}", flush=True)
                    if rt["output_drift"] > 1e-5:
                        print(f"     WARNING: output drift "
                              f"{rt['output_drift']:.3g}", flush=True)
            except Exception as e:
                # includes PlanValidationError RP107: plan.save refuses
                # to write a plan with error-severity diagnostics
                records[a] = {"arch": a,
                              "error": f"{type(e).__name__}: {e}"}
                print(f"[FAIL] {a}: {type(e).__name__}: {e}", flush=True)
                failed += 1
        if args.metrics:
            _write_metrics(args.metrics, "dryrun_pardnn", records)
        return 1 if failed else 0

    cells = []
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = ASSIGNED_ARCHS if (args.all or args.arch is None) \
        else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    for a in archs:
        for s in shapes:
            for m in meshes:
                cells.append((a, s, m))

    if args.list:
        for a, s, m in cells:
            skip = shape_skip_reason(get_config(a), SHAPES[s])
            print(f"{cell_name(a, s, m):60s} "
                  f"{'SKIP: ' + skip if skip else 'RUN'}")
        return 0

    os.makedirs(args.out, exist_ok=True)
    failed = 0
    for a, s, m in cells:
        name = cell_name(a, s, m, args.tag)
        path = os.path.join(args.out, name + ".json")
        if os.path.exists(path) and not args.force:
            print(f"[cached] {name}")
            continue
        print(f"[run] {name} ...", flush=True)
        t0 = time.perf_counter()
        try:
            res = run_cell(a, s, m, remat=args.remat, tag=args.tag)
        except Exception as e:
            res = {"arch": a, "shape": s, "mesh": m, "status": "FAIL",
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
        res["wall_s"] = round(time.perf_counter() - t0, 1)
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
        status = res["status"]
        extra = ""
        if status == "OK" and "roofline" in res:
            r = res["roofline"]
            extra = (f"dom={r['dominant']} bound={r['bound_s']:.4f}s "
                     f"flops={res['hlo_flops']:.3g}")
        elif status == "OK":
            mem = res.get("per_device_total_bytes")
            extra = (f"compile-only mem/dev="
                     f"{mem / 2**30:.1f}G" if mem else "compile-only")
        elif status == "FAIL":
            extra = res["error"][:200]
            failed += 1
        print(f"[{status}] {name} ({res['wall_s']}s) {extra}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
