import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) combination
on 512 placeholder host devices — proves the sharding config is coherent and
yields the roofline inputs (memory_analysis / cost_analysis / HLO collectives).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \\
      --shape train_4k [--multi-pod] [--mode cors|fedavg|il]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Artifacts: artifacts/dryrun/<arch>__<shape>__<mesh>__<mode>.json
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import ARCHS, SHAPES, get_arch, get_shape
from repro.launch import roofline, serve as serve_lib, train as train_lib
from repro.launch.mesh import make_production_mesh
from repro.types import CollabConfig

ARTDIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                      "artifacts", "dryrun")
# The chip the roofline terms model (a `roofline.PEAKS` key): the dry-run
# compiles on placeholder host devices, so the chip is named, not detected.
CHIP = "TPU v5 lite"


def should_skip(cfg, shape) -> str:
    if shape.name == "long_500k" and cfg.long_context_mode == "skip":
        return ("enc-dec with a 30s audio frontend has no 500k-token decode "
                "regime (DESIGN.md shape/skip matrix)")
    return ""


def build_lowered(cfg, shape, mesh, *, mode: str, n_clients: int,
                  strategy: str = "tp", moe_ep: bool = False,
                  sync: str = "step"):
    """Lower the step for (cfg, shape) on `mesh` with full shardings.

    §Perf knobs: strategy ("tp" | "dp_only"), moe_ep (expert-parallel
    sharding), sync ("step" = exchange folded into every step;
    "round" = paper Algorithm 1 cadence, exchange amortized per round)."""
    from repro import sharding as sharding_mod
    sharding_mod.set_hints(mesh=mesh, moe_ep=moe_ep,
                           moe_dp=strategy in ("dp_only", "zero1"))
    if shape.mode == "train":
        ccfg = CollabConfig(mode=mode, num_classes=cfg.vocab_size,
                            d_feature=cfg.d_feature, num_negatives=1023)
        step = train_lib.make_train_step(cfg, ccfg, n_clients=n_clients,
                                         sync_in_step=(sync == "step"))
        state = train_lib.init_state_shapes(cfg, n_clients)
        batch = train_lib.train_batch_specs(cfg, shape, n_clients)
        state_sh = train_lib.state_shardings(state, cfg, mesh, n_clients,
                                             strategy=strategy)
        batch_sh = train_lib.batch_shardings(batch, mesh, n_clients,
                                             strategy=strategy)
        seed = jax.ShapeDtypeStruct((), jax.numpy.int32)
        fn = jax.jit(lambda st, b, s: step(st, b, jax.random.PRNGKey(s)),
                     in_shardings=(state_sh, batch_sh, None))
        return fn.lower(state, batch, seed)
    if shape.mode == "prefill":
        step = serve_lib.make_prefill_step(cfg)
        params = serve_lib.params_shapes(cfg)
        batch = serve_lib.serve_batch_specs(cfg, shape)
        p_sh = serve_lib.params_shardings(params, cfg, mesh)
        b_sh = serve_lib.batch_shardings(batch, mesh)
        return jax.jit(step, in_shardings=(p_sh, b_sh)).lower(params, batch)
    window = serve_lib.decode_window(cfg, shape)
    step = serve_lib.make_decode_step(cfg, window=window)
    params = serve_lib.params_shapes(cfg)
    batch = serve_lib.serve_batch_specs(cfg, shape)
    caches = serve_lib.cache_shapes(cfg, shape)
    p_sh = serve_lib.params_shardings(params, cfg, mesh)
    b_sh = serve_lib.batch_shardings(batch, mesh)
    c_sh = serve_lib.cache_shardings(caches, cfg, mesh, shape)
    return jax.jit(step, in_shardings=(p_sh, b_sh, c_sh)).lower(
        params, batch, caches)


def _compile_metrics(compiled):
    cost = dict(compiled.cost_analysis() or {})
    hlo = compiled.as_text()
    coll = roofline.collective_bytes(hlo)
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": float(coll["total"]), "coll_detail": coll,
            "hlo_bytes": len(hlo)}


def estimate_corrected(cfg, shape, mesh, *, mode: str, n_clients: int,
                       **knobs):
    """Scan-corrected roofline inputs via shallow unrolled depth variants
    (see roofline.py module docstring)."""
    from repro.models import blocks
    cfgs, counts, names = roofline.depth_variants(cfg)
    vals = {"flops": [], "bytes": [], "coll": []}
    blocks.UNROLL = True
    try:
        for vc in cfgs:
            lowered = build_lowered(vc, shape, mesh, mode=mode,
                                    n_clients=n_clients, **knobs)
            m = _compile_metrics(lowered.compile())
            for k in vals:
                vals[k].append(m[k])
    finally:
        blocks.UNROLL = False
    rc = roofline.real_counts(cfg)
    corrected = {}
    probes = {}
    for k, v in vals.items():
        coefs = roofline.solve_linear(counts, names, v)
        corrected[k] = roofline.evaluate_linear(coefs, rc)
        probes[k] = {"coefs": coefs, "probe_values": v}
    return corrected, probes


def lower_one(arch: str, shape_name: str, *, multi_pod: bool,
              mode: str = "cors", with_roofline: bool = True,
              strategy: str = "tp", moe_ep: bool = False,
              sync: str = "step", tag: str = ""):
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    skip = should_skip(cfg, shape)
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag, "mode": mode,
           "status": "skip", "skip_reason": skip, "tag": tag,
           "knobs": {"strategy": strategy, "moe_ep": moe_ep, "sync": sync}}
    if skip:
        return rec

    knobs = dict(strategy=strategy, moe_ep=moe_ep, sync=sync)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_clients = mesh.shape.get("pod", 1)
    with mesh:
        t0 = time.time()
        lowered = build_lowered(cfg, shape, mesh, mode=mode,
                                n_clients=n_clients, **knobs)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        try:
            mem = compiled.memory_analysis()
            mem_rec = {k: int(getattr(mem, k)) for k in
                       ("argument_size_in_bytes", "output_size_in_bytes",
                        "temp_size_in_bytes", "generated_code_size_in_bytes")
                       if hasattr(mem, k)}
        except Exception as e:  # pragma: no cover - backend specific
            mem_rec = {"error": str(e)}
        raw = _compile_metrics(compiled)

        corrected, probes = (raw, None)
        if with_roofline:
            corrected, probes = estimate_corrected(
                cfg, shape, mesh, mode=mode, n_clients=n_clients, **knobs)

    terms = roofline.terms({"flops": corrected["flops"],
                            "bytes accessed": corrected["bytes"]},
                           {"total": corrected["coll"]}, CHIP)
    mf = roofline.model_flops(cfg, shape, n_clients)
    n_dev = mesh.size
    hlo_flops_global = terms["flops"] * n_dev
    rec.update(
        status="ok", n_devices=n_dev, chip=CHIP,
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        raw_scan_metrics=raw, probes=probes,
        memory=mem_rec, terms=terms,
        model_flops_global=mf,
        useful_flops_ratio=(mf / hlo_flops_global
                            if hlo_flops_global else None))
    return rec


def save(rec, outdir=ARTDIR):
    os.makedirs(outdir, exist_ok=True)
    tag = rec.get("tag", "") or ""
    if tag:
        tag = "__" + tag
    name = (f"{rec['arch']}__{rec['shape']}__{rec['mesh']}__{rec['mode']}"
            f"{tag}.json")
    with open(os.path.join(outdir, name), "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return name


def fmt(rec) -> str:
    if rec["status"] != "ok":
        return (f"{rec['arch']:24s} {rec['shape']:12s} {rec['mesh']:12s} "
                f"{rec['status'].upper()}: {rec.get('skip_reason', rec.get('error', ''))[:60]}")
    t = rec["terms"]
    return (f"{rec['arch']:24s} {rec['shape']:12s} {rec['mesh']:12s} "
            f"compile={rec['compile_s']:6.1f}s "
            f"comp={t['compute_s']*1e3:8.2f}ms mem={t['memory_s']*1e3:8.2f}ms "
            f"coll={t['collective_s']*1e3:8.2f}ms -> {t['bottleneck']}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mode", default="cors",
                    choices=["cors", "fedavg", "il"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-roofline", action="store_true",
                    help="compile proof only (multi-pod pass)")
    ap.add_argument("--strategy", default="tp",
                    choices=["tp", "dp_only", "zero1"])
    ap.add_argument("--moe-ep", action="store_true",
                    help="expert-parallel MoE sharding (§Perf variant)")
    ap.add_argument("--sync", default="step", choices=["step", "round"],
                    help="prototype exchange cadence (§Perf variant)")
    ap.add_argument("--remat", default="full",
                    choices=["full", "dots", "none"],
                    help="activation checkpoint policy (§Perf variant)")
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    ap.add_argument("--out", default=ARTDIR)
    args = ap.parse_args()

    combos = []
    archs = [args.arch] if args.arch else sorted(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    for a in archs:
        for s in shapes:
            combos.append((a, s))
    from repro.models import blocks as _blocks
    _blocks.REMAT_POLICY = args.remat
    ok = skip = fail = 0
    for a, s in combos:
        try:
            rec = lower_one(a, s, multi_pod=args.multi_pod, mode=args.mode,
                            with_roofline=not args.no_roofline,
                            strategy=args.strategy, moe_ep=args.moe_ep,
                            sync=args.sync, tag=args.tag)
        except Exception as e:
            rec = {"arch": a, "shape": s,
                   "mesh": "pod2x16x16" if args.multi_pod else "pod16x16",
                   "mode": args.mode, "status": "fail",
                   "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
        save(rec, args.out)
        print(fmt(rec), flush=True)
        ok += rec["status"] == "ok"
        skip += rec["status"] == "skip"
        fail += rec["status"] == "fail"
    print(f"\n== dry-run summary: {ok} ok / {skip} skip / {fail} fail ==")
    if fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
