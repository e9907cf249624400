import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape) on
the production mesh, with 512 placeholder host devices standing in for the
2-pod v5e fleet.  THE TWO LINES ABOVE MUST STAY FIRST — jax locks the device
count at first init.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
        --shape train_4k [--multi-pod] [--out experiments/dryrun]

Emits JSON: memory_analysis, cost_analysis, per-kind collective bytes, and
the roofline terms (single-pod only, per DESIGN.md §6).
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.analysis.hlo import parse_collectives  # noqa: E402
from repro.analysis.roofline import model_flops, roofline_terms  # noqa: E402
from repro.configs.base import INPUT_SHAPES, get_config  # noqa: E402
from repro.core import DepositumConfig, MixPlan  # noqa: E402
from repro.core.depositum import DepositumState  # noqa: E402
from repro.launch.gossip_dist import make_shardmap_mixer  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.sharding import (  # noqa: E402
    make_placement,
    tree_shardings,
    with_client_dim,
)
from repro.launch.specs import (  # noqa: E402
    decode_cache_specs,
    decode_capacity,
    decode_token_specs,
    prefill_specs,
    train_batch_specs,
)
from repro.launch.steps import (  # noqa: E402
    build_prefill_step,
    build_serve_step,
    build_train_step,
)
from repro.models import build_model  # noqa: E402

S = jax.ShapeDtypeStruct


def shapes_and_axes(model):
    """eval_shape the param init; capture the (static) axes via side effect."""
    box = {}

    def f(k):
        p, a = model.init(k)
        box["axes"] = a
        return p

    shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    return shapes, box["axes"]


def state_specs(model, n_clients: int):
    """(ShapeDtypeStruct, axes) pytrees for the full DEPOSITUM state."""
    p_shapes, p_axes = shapes_and_axes(model)

    def add_clients(tree):
        return jax.tree_util.tree_map(
            lambda s: S((n_clients,) + s.shape, s.dtype), tree
        )

    xs = add_clients(p_shapes)
    ax = with_client_dim(p_axes)
    shapes = DepositumState(
        x=xs, y=xs, nu=xs, mu=xs, g=xs, t=S((), np.int32)
    )
    axes = DepositumState(x=ax, y=ax, nu=ax, mu=ax, g=ax, t=())
    return shapes, axes


def _cost_dict(compiled):
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float))}
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}


def _memory_dict(compiled):
    try:
        ma = compiled.memory_analysis()
        keys = [
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes",
        ]
        return {k: int(getattr(ma, k)) for k in keys if hasattr(ma, k)}
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}


def _depth_variants(cfg):
    """(cfg_depth1, cfg_depth2, trip_count) for the scan-cost calibration.

    XLA's HloCostAnalysis counts a while-loop body ONCE regardless of trip
    count, so scanned-layer models under-report flops/bytes by ~n_layers.
    We compile two shallow *unrolled* variants; body = f(2)-f(1), base =
    f(1)-body, corrected = base + trips*body.
    """
    import dataclasses as dc

    if cfg.family == "hybrid":
        every = cfg.shared_attn_every
        trips = cfg.n_layers // every
        d1 = dc.replace(cfg, n_layers=every, scan_unroll=True)
        d2 = dc.replace(cfg, n_layers=2 * every, scan_unroll=True)
        return d1, d2, trips
    if cfg.family == "encdec":
        if cfg.n_layers != cfg.n_encoder_layers:
            return None, None, 1  # correction needs equal trip counts
        d1 = dc.replace(cfg, n_layers=1, n_encoder_layers=1, scan_unroll=True)
        d2 = dc.replace(cfg, n_layers=2, n_encoder_layers=2, scan_unroll=True)
        return d1, d2, cfg.n_layers
    d1 = dc.replace(cfg, n_layers=1, scan_unroll=True)
    d2 = dc.replace(cfg, n_layers=2, scan_unroll=True)
    return d1, d2, cfg.n_layers


def _lower_combo(cfg, arch, shape_name, mesh, *, mixer_kind="dense",
                 topology="ring", microbatch=1):
    """Lower+compile one (cfg x shape) on the mesh; returns compiled."""
    model = build_model(cfg)
    seq, global_batch, kind = INPUT_SHAPES[shape_name]
    if kind == "train":
        placement = make_placement(arch, mesh, role="train")
        n = placement.n_clients
        st_shapes, st_axes = state_specs(model, n)
        b_shapes, b_axes = train_batch_specs(cfg, shape_name, n)
        st_sh = tree_shardings(placement, st_axes, st_shapes)
        b_sh = tree_shardings(placement, b_axes, b_shapes)
        dep_cfg = DepositumConfig(
            alpha=1e-3, beta=1.0, gamma=0.8, comm_period=8,
            prox_name="l1", prox_kwargs={"lam": 1e-6},
        )
        if mixer_kind == "dense":
            mixer = MixPlan.from_topology(topology, n)
        else:
            # ring/complete lower to ppermute/pmean, the rest to all_gather
            # + per-shard rows; the mixer sees one state variable (x or y)
            # at a time, so its specs are the param-level tree
            mixer = make_shardmap_mixer(
                placement, st_axes.x, st_shapes.x,
                MixPlan.from_topology(topology, n, prefer="sparse"))
        step = build_train_step(model, dep_cfg, n, mixer,
                                microbatch=microbatch)
        jitted = jax.jit(step, in_shardings=(st_sh, b_sh),
                         out_shardings=(st_sh, None), donate_argnums=(0,))
        return jitted.lower(st_shapes, b_shapes)
    if kind == "prefill":
        placement = make_placement(arch, mesh, role="serve")
        p_shapes, p_axes = _shapes_axes_for(model)
        p_sh = tree_shardings(placement, p_axes, p_shapes)
        b_shapes, b_axes = prefill_specs(cfg, shape_name)
        b_sh = tree_shardings(placement, b_axes, b_shapes)
        step = build_prefill_step(model, min(seq, 32768))
        jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
        return jitted.lower(p_shapes, b_shapes)
    placement = make_placement(arch, mesh, role="serve")
    p_shapes, p_axes = _shapes_axes_for(model)
    p_sh = tree_shardings(placement, p_axes, p_shapes)
    c_shapes, c_axes = decode_cache_specs(cfg, shape_name)
    c_sh = tree_shardings(placement, c_axes, c_shapes)
    t_shapes, t_axes = decode_token_specs(cfg, shape_name)
    t_sh = tree_shardings(placement, t_axes, t_shapes)
    step = build_serve_step(model)
    jitted = jax.jit(step, in_shardings=(p_sh, c_sh, t_sh),
                     out_shardings=(None, c_sh), donate_argnums=(1,))
    return jitted.lower(p_shapes, c_shapes, t_shapes)


def _shapes_axes_for(model):
    return shapes_and_axes(model)


def calibrate_costs(cfg, arch, shape_name, mesh, *, mixer_kind, topology):
    """Corrected {flops, bytes} using two shallow unrolled compiles."""
    d1, d2, trips = _depth_variants(cfg)
    if d1 is None:
        return None
    out = {}
    for tag, c in (("d1", d1), ("d2", d2)):
        compiled = _lower_combo(c, arch, shape_name, mesh,
                                mixer_kind=mixer_kind,
                                topology=topology).compile()
        out[tag] = _cost_dict(compiled)
    corrected = {}
    for key in ("flops", "bytes accessed"):
        f1 = out["d1"].get(key, 0.0)
        f2 = out["d2"].get(key, 0.0)
        body = max(f2 - f1, 0.0)
        base = max(f1 - body, 0.0)
        corrected[key] = base + trips * body
    corrected["trips"] = trips
    return corrected


def run_one(arch: str, shape_name: str, multi_pod: bool, *,
            mixer_kind: str = "dense", topology: str = "ring",
            calibrate: bool = True, remat_policy: str = "",
            microbatch: int = 1) -> dict:
    cfg = get_config(arch)
    if remat_policy:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, remat_policy=remat_policy)
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    seq, global_batch, kind = INPUT_SHAPES[shape_name]
    t0 = time.perf_counter()

    lowered = _lower_combo(cfg, arch, shape_name, mesh,
                           mixer_kind=mixer_kind, topology=topology,
                           microbatch=microbatch)
    lower_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t1

    mem = _memory_dict(compiled)
    cost = _cost_dict(compiled)
    print(f"[{arch} x {shape_name} x {'multi' if multi_pod else 'single'}-pod]")
    print("memory_analysis:", mem)
    print("cost_analysis (flops/bytes):",
          {k: cost.get(k) for k in ("flops", "bytes accessed")})

    hlo = compiled.as_text()
    colls = parse_collectives(hlo)
    coll_bytes = int(sum(v["bytes"] for v in colls.values()))

    # scan-cost calibration: XLA counts while bodies once; correct by trips
    corrected = None
    if calibrate:
        try:
            corrected = calibrate_costs(cfg, arch, shape_name, mesh,
                                        mixer_kind=mixer_kind,
                                        topology=topology)
        except Exception as e:  # pragma: no cover - calibration best-effort
            corrected = {"error": str(e)[-500:]}
    flops = float(cost.get("flops", 0.0))
    hbm_bytes = float(cost.get("bytes accessed", 0.0))
    if corrected and "flops" in corrected:
        flops = max(flops, corrected["flops"])
        hbm_bytes = max(hbm_bytes, corrected["bytes accessed"])
    rl = roofline_terms(flops, hbm_bytes, coll_bytes, per_device=True,
                        chips=chips)
    mf = model_flops(cfg, shape_name)
    rl["model_flops_global"] = mf
    rl["hlo_flops_per_device"] = flops
    rl["useful_flops_ratio"] = (
        mf / (flops * chips) if flops > 0 else 0.0
    )
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "chips": chips,
        "kind": kind,
        "mixer": mixer_kind,
        "lower_s": round(lower_s, 2),
        "compile_s": round(compile_s, 2),
        "memory_analysis": mem,
        "cost_analysis": cost,
        "cost_corrected": corrected,
        "collectives": colls,
        "collective_bytes_per_device": coll_bytes,
        "roofline": rl,
    }
    print("collectives:", {k: v for k, v in colls.items()})
    print("roofline:", {k: rl[k] for k in
                        ("t_compute_s", "t_memory_s", "t_collective_s",
                         "dominant")})
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mixer", default="dense", choices=["dense", "ppermute"])
    ap.add_argument("--no-calibrate", action="store_true")
    ap.add_argument("--remat-policy", default="", choices=["", "full", "dots"])
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--topology", default="ring")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    res = run_one(args.arch, args.shape, args.multi_pod,
                  mixer_kind="dense" if args.mixer == "dense" else "ppermute",
                  topology=args.topology, calibrate=not args.no_calibrate,
                  remat_policy=args.remat_policy, microbatch=args.microbatch)
    os.makedirs(args.out, exist_ok=True)
    tag = f"{args.arch}__{args.shape}__{res['mesh']}__{args.mixer}"
    if args.remat_policy:
        tag += f"__remat-{args.remat_policy}"
    if args.microbatch > 1:
        tag += f"__mb{args.microbatch}"
    path = os.path.join(args.out, tag + ".json")
    with open(path, "w") as f:
        json.dump(res, f, indent=2)
    print("wrote", path)


if __name__ == "__main__":
    main()
