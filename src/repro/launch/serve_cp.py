"""CP serving driver: submit a mixed-signature tensor fleet, stream results.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python -m repro.launch.serve_cp --requests 16 --batch-size 8 \
        --rank 4 [--mesh] [--tuning-cache /path/cache.json]

Generates a fleet of small random tensors over two shapes (two signatures:
the scheduler must bucket them into separate compiled dispatches), submits
them all, drains the service, and logs problems/sec plus the serving
counters.  ``--mesh`` shards every dispatch's batch axis over all attached
devices (batch-parallel: zero collective traffic); ``--tuning-cache`` names
a persistent :class:`repro.plan.autotune.TuningCache` file to use as the
warm-plan store.  Compiles persist in ``<checkout>/.jax_cache`` unless
``JAX_COMPILATION_CACHE_DIR`` names another directory.
"""

from __future__ import annotations

import argparse
import logging
import time

import jax

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
log = logging.getLogger("repro.launch.serve_cp")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--rank", type=int, default=4)
    ap.add_argument("--n-iters", type=int, default=5)
    ap.add_argument("--dim", type=int, default=12, help="edge of the cubic shape")
    ap.add_argument("--mesh", action="store_true",
                    help="shard the batch axis over all attached devices")
    ap.add_argument("--tuning-cache", default=None,
                    help="persistent TuningCache file (the warm-plan store)")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_checkout_cache

    use_checkout_cache()

    from repro.core.tensor_ops import random_tensor
    from repro.launch.mesh import make_mesh
    from repro.plan.autotune import TuningCache
    from repro.serve import CPService

    mesh = None
    if args.mesh:
        import math

        # batch-parallel sharding needs the device count to divide the batch
        n_dev = math.gcd(jax.device_count(), args.batch_size)
        mesh = make_mesh((n_dev,), ("b",))
        log.info("batch-parallel over %d of %d devices", n_dev, jax.device_count())
    cache = TuningCache(args.tuning_cache) if args.tuning_cache else None
    svc = CPService(
        batch_size=args.batch_size, n_iters=args.n_iters, mesh=mesh,
        tuning_cache=cache,
    )
    # two shapes -> two signatures: the scheduler buckets them separately
    shapes = [(args.dim,) * 3, (args.dim, args.dim // 2, args.dim)]
    futures = [
        svc.submit(random_tensor(jax.random.PRNGKey(i), shapes[i % 2]), args.rank)
        for i in range(args.requests)
    ]
    t0 = time.perf_counter()
    done = svc.flush()
    dt = time.perf_counter() - t0
    assert all(f.done() for f in futures)
    stats = svc.stats()
    fits = [f.result().fit for f in done]
    log.info(
        "served %d problems in %.2fs (%.1f problems/s end-to-end, "
        "%.1f in-dispatch) mean fit %.4f",
        len(done), dt, len(done) / dt, stats["problems_per_s"],
        sum(fits) / len(fits),
    )
    log.info(
        "signatures=%d compiles=%d warm_plan_hits=%d batches=%d "
        "occupancy=%.2f padded=%d",
        stats["signatures"], stats["compiles"], stats["warm_plan_hits"],
        stats["batches"], stats["batch_occupancy"], stats["padded_slots"],
    )


if __name__ == "__main__":
    main()
