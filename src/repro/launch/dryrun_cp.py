import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Dry-run of the paper's own workload at pod scale: distributed CP-ALS.

Lowers one full distributed ALS sweep (repro.plan.als_sweep on a
ShardedExecutor) on the production mesh for a pod-scale dense tensor
(default: a 2048 time x 1024 subject x 400 x 400 region
functional-connectivity tensor, 1.34 TB fp32 --
the paper's fMRI application grown to the scale its Sec. 3 calls for), and
records the same cost/memory/collective stats as the LM dry-run.

The MTTKRP method is selectable -- this is the SPerf hillclimb axis:
  1step : paper Alg. 3 with the explicit KRP (materializes K_L (.) K_R)
  2step : paper Alg. 4 (partial MTTKRP + multi-TTV)
  auto  : paper's recommended mix (Sec. 5.3.3)
  dimtree: the binary dimension tree (two X-sized partials a sweep)
The non-tree methods run the flat per-mode schedule.

    PYTHONPATH=src python -m repro.launch.dryrun_cp --method auto --mesh pod
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def run(shape, rank, method, mesh_kind, mode_axes, out_dir):
    from repro.analysis.roofline import parse_collectives
    from repro.dist.dist_mttkrp import _factor_specs, _x_spec
    from repro.launch.mesh import make_production_mesh
    from repro.plan import Problem, ShardedExecutor, SweepState, als_sweep, plan_sweep
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    ndim = len(shape)

    x_struct = jax.ShapeDtypeStruct(
        tuple(shape), jnp.float32,
        sharding=NamedSharding(mesh, _x_spec(ndim, mode_axes)),
    )
    f_structs = [
        jax.ShapeDtypeStruct(
            (dim, rank), jnp.float32, sharding=NamedSharding(mesh, spec)
        )
        for dim, spec in zip(shape, _factor_specs(ndim, mode_axes))
    ]
    scalars = [
        jax.ShapeDtypeStruct((rank,), jnp.float32, sharding=NamedSharding(mesh, P())),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=NamedSharding(mesh, P())),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P())),
    ]

    problem = Problem.from_tensor(x_struct, rank, mode_axes=mode_axes, mesh=mesh)
    plan = plan_sweep(
        problem, strategy=method, executor="sharded",
        schedule=None if method == "dimtree" else "flat",
    )
    executor = ShardedExecutor(mesh, mode_axes)

    def fn(x, factors, weights, norm_x, it):
        state = SweepState(
            x=x, factors=list(factors), weights=weights, norm_x=norm_x, it=it
        )
        out = als_sweep(problem, plan, executor, state)
        return out.factors, out.weights, out.fit

    t0 = time.perf_counter()
    lowered = jax.jit(fn).lower(x_struct, f_structs, *scalars)
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0

    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    coll = parse_collectives(compiled.as_text())
    entries = 1
    for d in shape:
        entries *= d
    # MODEL_FLOPS for one ALS sweep: N modes x (2|X|C MTTKRP + small solves)
    model_flops = 2.0 * entries * rank * ndim

    record = {
        "kind": "cp_als_sweep",
        "shape": list(shape),
        "rank": rank,
        "method": method,
        "mesh": mesh_kind,
        "chips": mesh.size,
        "mode_axes": {str(k): v for k, v in mode_axes.items()},
        "model_flops": model_flops,
        "compile_s": round(compile_s, 2),
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_bytes": coll["total_bytes"],
        "coll_by_kind": coll["bytes_by_kind"],
        "coll_counts": coll["count_by_kind"],
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "arg_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "ok": True,
    }
    os.makedirs(out_dir, exist_ok=True)
    axes_tag = "-".join(f"{k}{v[0]}" for k, v in sorted(mode_axes.items()))
    fname = os.path.join(out_dir, f"cpals__{method}__{mesh_kind}__{axes_tag}.json")
    with open(fname, "w") as f:
        json.dump(record, f, indent=1)
    print(
        f"[OK] cpals method={method} mesh={mesh_kind} axes={mode_axes}: "
        f"compile={compile_s:.1f}s flops={record['flops']:.3e} "
        f"bytes={record['bytes']:.3e} coll={record['coll_bytes']:.3e} "
        f"temp={record['temp_bytes']/1e9:.2f}GB -> {fname}",
        flush=True,
    )
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", type=int, nargs="*", default=[2048, 1024, 400, 400])
    ap.add_argument("--rank", type=int, default=32)
    ap.add_argument("--method", default="auto",
                    choices=["auto", "1step", "2step", "einsum", "dimtree"])
    ap.add_argument("--mesh", choices=["pod", "multipod"], default="pod")
    ap.add_argument("--axes", default="0:data,1:model",
                    help="mode:axis pairs, e.g. '0:data,1:model' or '0:pod,1:data,2:model'")
    ap.add_argument("--out", default="results/dryrun_cp")
    args = ap.parse_args()

    mode_axes = {}
    for pair in args.axes.split(","):
        k, v = pair.split(":")
        mode_axes[int(k)] = v
    run(tuple(args.shape), args.rank, args.method, args.mesh, mode_axes, args.out)


if __name__ == "__main__":
    main()
