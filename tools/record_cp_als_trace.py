#!/usr/bin/env python3
"""Record a small profiler trace of ``cp_als`` on one TPU: the fixture
``bench/tests/data/tpu_cp_als.xplane.pb.gz`` that tests the reduction in
``bench/program_trace.py``.

    python3 tools/record_cp_als_trace.py <out_dir>

Two solves of a planted 16x12x10x8 rank-4 tensor, three sweeps each at
``sweeps_per_sync=1`` (``tol=0`` never stops early), each inside a
``solve`` span and all inside one ``window`` span, after one solve that
compiles everything.  Writes the trace, gzipped and without the plane
that holds the compiled programs' HLO (about 120 KB), to
``<out_dir>/tpu_cp_als.xplane.pb.gz`` and each solve's sweeps and host
syncs to ``<out_dir>/tpu_cp_als.json``.
Refuses any platform but TPU (exit 2).
"""

import glob
import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.program_trace import fields  # noqa: E402

SHAPE, RANK, SOLVES, SWEEPS = (16, 12, 10, 8), 4, 2, 3
HLO_PLANE = "/host:metadata"


def _varint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    return bytes(out + bytes([n]))


def without_plane(buf: bytes, name: str) -> bytes:
    """A serialized XSpace without its plane called ``name``; every other
    top-level field (all length-delimited) is copied as it is."""
    out = bytearray()
    for number, (lo, hi) in fields(buf):
        if number == 1 and any(n == 2 and buf[v[0]:v[1]] == name.encode()
                               for n, v in fields(buf, lo, hi)):
            continue
        out += _varint(number << 3 | 2) + _varint(hi - lo) + buf[lo:hi]
    return bytes(out)


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from repro.core.tensor_ops import random_factors
    from repro.plan import Problem, cp_als, plan_sweep

    if jax.devices()[0].platform != "tpu":
        print(f"needs a TPU, found {jax.devices()[0].platform!r}", file=sys.stderr)
        return 2
    true = random_factors(jax.random.PRNGKey(1), SHAPE, RANK)
    x = jnp.einsum("ic,jc,kc,lc->ijkl", *true)
    x = x + 0.05 * jax.random.normal(jax.random.PRNGKey(2), SHAPE)
    init = random_factors(jax.random.PRNGKey(3), SHAPE, RANK)
    plan = plan_sweep(Problem.from_tensor(x, RANK))
    cache = {}

    def solve():
        return cp_als(x, plan, n_iters=SWEEPS, tol=0.0, init_factors=init,
                      dispatch_cache=cache, dispatch_key=0)

    jax.block_until_ready(solve().factors)
    log_dir = tempfile.mkdtemp(prefix="cp-als-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # keeps the fixture small
    units = []
    try:
        jax.profiler.start_trace(log_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation("window"):
            for _ in range(SOLVES):
                with jax.profiler.TraceAnnotation("solve"):
                    st = solve()
                units.append({"sweeps": st.it, "host_syncs": st.host_syncs})
        jax.profiler.stop_trace()
        (found,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
        out = Path(out_dir)
        out.mkdir(parents=True, exist_ok=True)
        with open(found, "rb") as f:
            data = without_plane(f.read(), HLO_PLANE)
        (out / "tpu_cp_als.xplane.pb.gz").write_bytes(gzip.compress(data, 9))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    (out / "tpu_cp_als.json").write_text(json.dumps(
        {"shape": SHAPE, "rank": RANK, "device_kind": jax.devices()[0].device_kind,
         "units": units}, indent=1) + "\n")
    print(json.dumps(units))
    return 0


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(__doc__)
    sys.exit(main(sys.argv[1]))
