"""Allreduce bandwidth benchmark (north-star metric #2: BASELINE.md's
`ray.util.collective`-equivalent allreduce bandwidth over ICI).

Two modes:

- ``--mode mesh`` (default): jax-native — allreduce (psum) over ALL local
  devices via shard_map on a 1-axis mesh, the path a TPU slice actually
  uses (XLA compiles it onto ICI).  On a single chip this degenerates to a
  copy; on a v5e-8/v5p slice it measures real ICI bandwidth.
- ``--mode group``: drives the ray_tpu.util.collective API across actor
  ranks (the reference library's shape), exercising the store/xla backends.

``--compression bf16,int8,hier,hier_int8`` sweeps the compressed-collective
programs (util/collective/compression.py) over the same devices: bf16 is
the stock psum, int8 the EQuARX-style two-phase quantized allreduce, hier
the two-level (slice,intra) algorithm, hier_int8 both.  Compressed rows
carry wire vs logical bytes and the reduction ratio alongside busbw.

Prints one JSON line per size:
  {"metric": "allreduce_busbw", "bytes": N, "value": GB/s, ...}
busbw uses the standard ring formula 2*(n-1)/n * size / time.
"""

from __future__ import annotations

import argparse
import json
import time


def bench_mesh(sizes_mb, dtype_name="bfloat16", iters=20):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu._private import runtime_metrics
    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(devices, ("x",))
    dtype = jnp.dtype(dtype_name)

    @jax.jit
    def allreduce(x):
        return jax.shard_map(
            lambda s: jax.lax.psum(s, "x"),
            mesh=mesh,
            in_specs=P("x"),
            out_specs=P(),  # replicated result
        )(x)

    results = []
    for mb in sizes_mb:
        count = int(mb * 2**20 / dtype.itemsize)
        count -= count % max(n, 1)
        x = jax.device_put(
            jnp.ones((count,), dtype),
            NamedSharding(mesh, P("x")))
        allreduce(x).block_until_ready()  # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = allreduce(x)
        out.block_until_ready()
        dt = (time.perf_counter() - t0) / iters
        size = count * dtype.itemsize
        busbw = (2 * (n - 1) / max(n, 1)) * size / dt if n > 1 else size / dt
        # book the measured op into the built-in collective metrics so
        # collective_snapshot() (and any scrape) picks the numbers up for free
        runtime_metrics.record_collective(
            "allreduce", "xla_mesh", n, size, dt, dtype_name)
        results.append({
            "metric": "allreduce_busbw",
            "mode": "mesh",
            "devices": n,
            "bytes": size,
            "time_s": round(dt, 6),
            "value": round(busbw / 1e9, 3),
            "unit": "GB/s",
        })
    return results


def bench_mesh_compressed(sizes_mb, variant="int8", iters=10, block_size=256):
    """Compressed-collective sweep over all local devices: each device is
    one 'rank' contributing a per-rank payload of the given size.

    variant: "int8" (flat EQuARX two-phase), "hier" (hierarchical, no
    codec), "hier_int8" (hierarchical with the int8 DCN phase).  Reported
    busbw is EFFECTIVE (logical bytes / time) so rows compare directly
    against the bf16 rows; wire_bytes tracks what the transport carried.
    """
    import numpy as np

    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu._private import runtime_metrics
    from ray_tpu.util.collective import compression as comp
    from ray_tpu.util.collective.collective_group import xla_group as xg

    devices = jax.devices()
    world = len(devices)
    results = []
    hier = variant.startswith("hier")
    quant = variant.endswith("int8")
    scheme = comp.SCHEME_INT8 if quant else comp.SCHEME_NONE
    nslices = 2 if (hier and world % 2 == 0 and world >= 4) else 1
    if hier and nslices == 1:
        return [{"metric": "allreduce_busbw", "mode": "mesh",
                 "compression": variant,
                 "error": f"{world} devices cannot split into slices"}]
    for mb in sizes_mb:
        per_rank = int(mb * 2**20 / 4)  # f32 elements per rank
        granule = world * block_size
        per_rank -= per_rank % granule
        rows = [np.random.default_rng(r).standard_normal(per_rank)
                .astype(np.float32) for r in range(world)]
        logical = per_rank * 4
        if hier:
            ss = world // nslices
            mesh2 = Mesh(np.array(devices).reshape(nslices, ss),
                         ("slice", "intra"))
            fn = xg.build_hierarchical_allreduce(
                mesh2, nslices, ss, scheme, block_size)
            garr = jax.device_put(
                np.stack(rows).reshape(nslices, ss, per_rank),
                NamedSharding(mesh2, P("slice", "intra")))
            args = (garr,)
            wire, inter = comp.estimate_wire_bytes(
                "hierarchical", scheme, logical, world, ss, block_size)
        else:
            mesh = Mesh(np.array(devices), ("world",))
            fn = xg.build_quantized_allreduce(mesh, "world", world, block_size)
            pairs = [comp.quantize_blocks(r, block_size) for r in rows]
            sharding = NamedSharding(mesh, P("world"))
            garr_c = jax.device_put(np.stack([p[0] for p in pairs]), sharding)
            garr_s = jax.device_put(np.stack([p[1] for p in pairs]), sharding)
            args = (garr_c, garr_s)
            wire, inter = comp.estimate_wire_bytes(
                "flat", scheme, logical, world, block_size=block_size)
        out = fn(*args)
        out.block_until_ready()  # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        out.block_until_ready()
        dt = (time.perf_counter() - t0) / iters
        busbw = (2 * (world - 1) / max(world, 1)) * logical / dt
        # quality figure: reduced output vs exact f32 sum
        exact = np.sum(np.stack(rows), axis=0)
        rel = comp.relative_error(exact, np.asarray(out)[:per_rank])
        runtime_metrics.record_collective_compression(
            "allreduce", "xla_mesh", world, "bench", logical, int(wire),
            "hierarchical" if hier else "flat", scheme, rel, int(inter))
        results.append({
            "metric": "allreduce_busbw",
            "mode": "mesh",
            "compression": variant,
            "devices": world,
            "bytes": logical,
            "wire_bytes": int(wire),
            "wire_reduction_x": round(logical / wire, 3) if wire else None,
            "rel_error": round(rel, 6),
            "time_s": round(dt, 6),
            "value": round(busbw / 1e9, 3),
            "unit": "GB/s",
        })
    return results


def bench_mesh_algorithms(sizes_mb, algorithm, iters=10):
    """Planner-algorithm sweep (ISSUE 10): drive the explicit ring /
    recursive-halving-doubling tree / lossless hierarchical programs over
    all local devices and report busbw per algorithm alongside what the
    planner WOULD choose for that size (so rows double as a decision
    audit).  ``algorithm``: "ring" | "tree" | "hier"."""
    import numpy as np

    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu._private import runtime_metrics
    from ray_tpu.util.collective import compression as comp
    from ray_tpu.util.collective import planner as pl
    from ray_tpu.util.collective.collective_group import xla_group as xg

    devices = jax.devices()
    world = len(devices)
    results = []
    if algorithm == "tree" and world & (world - 1):
        return [{"metric": "allreduce_busbw", "mode": "mesh",
                 "algorithm": "tree",
                 "error": f"{world} devices is not a power of two"}]
    if algorithm == "hier" and not (world % 2 == 0 and world >= 4):
        return [{"metric": "allreduce_busbw", "mode": "mesh",
                 "algorithm": "hier",
                 "error": f"{world} devices cannot split into slices"}]
    topo = pl.Topology.flat(world, link=pl.LINK_HOST)
    spec = comp.CompressionSpec(scheme="none", min_bytes=0)
    for mb in sizes_mb:
        per_rank = int(mb * 2**20 / 4)
        per_rank -= per_rank % max(world * 2, 1)
        rows = [np.random.default_rng(r).standard_normal(per_rank)
                .astype(np.float32) for r in range(world)]
        logical = per_rank * 4
        if algorithm == "hier":
            ss = world // 2
            mesh2 = Mesh(np.array(devices).reshape(2, ss),
                         ("slice", "intra"))
            fn = xg.build_hierarchical_allreduce(
                mesh2, 2, ss, comp.SCHEME_NONE)
            garr = jax.device_put(
                np.stack(rows).reshape(2, ss, per_rank),
                NamedSharding(mesh2, P("slice", "intra")))
            alg_name = comp.ALG_HIERARCHICAL
            wire, _ = comp.estimate_wire_bytes(alg_name, comp.SCHEME_NONE,
                                               logical, world, ss)
        else:
            mesh = Mesh(np.array(devices), ("world",))
            builder = (xg.build_ring_allreduce if algorithm == "ring"
                       else xg.build_tree_allreduce)
            fn = builder(mesh, "world", world)
            garr = jax.device_put(np.stack(rows),
                                  NamedSharding(mesh, P("world")))
            alg_name = (comp.ALG_RING if algorithm == "ring"
                        else comp.ALG_TREE)
            wire, _ = comp.estimate_wire_bytes(alg_name, comp.SCHEME_NONE,
                                               logical, world)
        out = fn(garr)
        out.block_until_ready()  # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(garr)
        out.block_until_ready()
        dt = (time.perf_counter() - t0) / iters
        busbw = (2 * (world - 1) / max(world, 1)) * logical / dt
        planned = pl.plan_allreduce(logical, topo, spec)
        pl.record_plan(alg_name, "bench_forced")
        runtime_metrics.record_collective(
            "allreduce", "xla_mesh", world, logical, dt, "float32")
        results.append({
            "metric": "allreduce_busbw",
            "mode": "mesh",
            "algorithm": algorithm,
            "devices": world,
            "bytes": logical,
            "wire_bytes": int(wire),
            "time_s": round(dt, 6),
            "value": round(busbw / 1e9, 3),
            "planner_choice": planned.algorithm,
            "planner_reason": planned.reason,
            "unit": "GB/s",
        })
    return results


def bench_bucketed_overlap(sizes_mb, bucket_mb, iters=10):
    """Bucketed-vs-fused A/B over the local mesh (ISSUE 10): one fused
    psum of S against K optimization_barrier-chained per-bucket psums of
    S/K — the communication half of the overlapped-gradient-sync trick,
    isolated from any model."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    world = len(devices)
    mesh = Mesh(np.array(devices), ("x",))
    results = []
    for mb in sizes_mb:
        count = int(mb * 2**20 / 4)
        k = max(int(mb / max(bucket_mb, 1e-9) + 0.5), 1)
        count -= count % max(world * k, 1)
        chunk = count // k
        x = jax.device_put(
            jnp.arange(count, dtype=jnp.float32) % 97,
            NamedSharding(mesh, P("x")))

        @jax.jit
        def fused(v):
            return jax.shard_map(lambda s: jax.lax.psum(s, "x"), mesh=mesh,
                              in_specs=P("x"), out_specs=P())(v)

        @jax.jit
        def bucketed(v):
            def body(s):
                outs = []
                token = jnp.zeros((), jnp.float32)
                for j in range(k):
                    c = jax.lax.psum(s[j * chunk // world:
                                       (j + 1) * chunk // world], "x")
                    c, token = jax.lax.optimization_barrier((c, token))
                    outs.append(c)
                return jnp.concatenate(outs)

            return jax.shard_map(body, mesh=mesh, in_specs=P("x"),
                              out_specs=P())(v)

        def timeit(fn):
            fn(x).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(x)
            out.block_until_ready()
            return (time.perf_counter() - t0) / iters

        t_fused, t_bucketed = timeit(fused), timeit(bucketed)
        results.append({
            "metric": "bucketed_allreduce_ab",
            "devices": world,
            "bytes": count * 4,
            "bucket_mb": bucket_mb,
            "buckets": k,
            "fused_s": round(t_fused, 6),
            "bucketed_s": round(t_bucketed, 6),
            "bucketed_over_fused": round(t_bucketed / t_fused, 3)
            if t_fused > 0 else None,
        })
    return results


def bench_group(sizes_mb, world_size=2, iters=5):
    """Collective-library mode: actor ranks allreduce numpy arrays through
    ray_tpu.util.collective (store backend off-TPU)."""
    import numpy as np

    import ray_tpu

    @ray_tpu.remote
    class Rank:
        def setup(self, world_size, rank):
            from ray_tpu.util import collective

            collective.init_collective_group(world_size, rank,
                                             backend="store",
                                             group_name="bench")
            return rank

        def run(self, nbytes, iters):
            from ray_tpu.util import collective

            x = np.ones(nbytes // 4, np.float32)
            collective.allreduce(x, group_name="bench")  # warm
            t0 = time.perf_counter()
            for _ in range(iters):
                collective.allreduce(x, group_name="bench")
            return (time.perf_counter() - t0) / iters

    ranks = [Rank.remote() for _ in range(world_size)]
    ray_tpu.get([r.setup.remote(world_size, i) for i, r in enumerate(ranks)])
    results = []
    for mb in sizes_mb:
        nbytes = int(mb * 2**20)
        times = ray_tpu.get([r.run.remote(nbytes, iters) for r in ranks])
        dt = max(times)
        busbw = (2 * (world_size - 1) / world_size) * nbytes / dt
        results.append({
            "metric": "allreduce_busbw",
            "mode": "group",
            "devices": world_size,
            "bytes": nbytes,
            "time_s": round(dt, 6),
            "value": round(busbw / 1e9, 3),
            "unit": "GB/s",
        })
    for r in ranks:
        ray_tpu.kill(r)
    return results


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--mode", choices=("mesh", "group"), default="mesh")
    p.add_argument("--sizes-mb", default="1,8,64")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--world-size", type=int, default=2)
    p.add_argument("--compression", default="bf16",
                   help="comma list of bf16,int8,hier,hier_int8 (mesh mode)")
    p.add_argument("--algorithm", default="",
                   help="comma list of ring,tree,hier — planner-algorithm "
                        "sweep over the explicit lossless programs")
    p.add_argument("--bucket-mb", type=float, default=None,
                   help="bucketed-vs-fused psum A/B at this bucket size")
    args = p.parse_args(argv)
    sizes = [float(s) for s in args.sizes_mb.split(",")]
    if args.mode == "mesh":
        results = []
        for variant in [v.strip() for v in args.compression.split(",") if v.strip()]:
            if variant == "bf16":
                results += bench_mesh(sizes, iters=args.iters)
            elif variant in ("int8", "hier", "hier_int8"):
                results += bench_mesh_compressed(sizes, variant,
                                                 iters=args.iters)
            else:
                raise SystemExit(f"unknown --compression variant {variant!r}")
        for alg in [a.strip() for a in args.algorithm.split(",") if a.strip()]:
            if alg not in ("ring", "tree", "hier"):
                raise SystemExit(f"unknown --algorithm variant {alg!r}")
            results += bench_mesh_algorithms(sizes, alg, iters=args.iters)
        if args.bucket_mb is not None:
            results += bench_bucketed_overlap(sizes, args.bucket_mb,
                                              iters=args.iters)
    else:
        import ray_tpu

        ray_tpu.init(num_cpus=max(4, args.world_size))
        try:
            results = bench_group(sizes, world_size=args.world_size,
                                  iters=max(args.iters // 4, 1))
        finally:
            ray_tpu.shutdown()
    for r in results:
        print(json.dumps(r))
    return results


if __name__ == "__main__":
    import os
    import sys

    # `python benchmarks/allreduce_bench.py` puts benchmarks/ (not the repo
    # root) on sys.path; group mode needs the package importable
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    main()
