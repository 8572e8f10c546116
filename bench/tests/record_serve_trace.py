"""Record the small serve trace that ``test_units.py`` reads.

    python bench/tests/record_serve_trace.py --out <file.xplane.pb.gz>

On a TPU: a one-slot ``sim-se2-fourier`` server at the scene shape of the
``se2-wosac-serve`` cell serves one lane to its end, which compiles and
warms up its programs; then a second lane is submitted and three ticks are
traced, each in a ``bench.tick`` span, the first of them admitting the
lane. The newest ``.xplane.pb`` is written gzipped to ``--out``.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax

    from bench import serve
    from repro.runtime.sim_server import SceneRequest

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    cell = run.Cell.from_benchmark("se2-wosac-serve", seed=args.seed,
                                   seconds=1.0, trace=False)
    cell.config = dict(cell.config, serve_slots=1)
    cell.traffic = dict(cell.traffic, scene_pool=2)
    srv, _, pool = serve.build(cell)
    tr = cell.traffic

    def lane(uid):
        return SceneRequest(uid=uid, tensors=pool[uid % len(pool)],
                            t_hist=tr["t_hist"], t_total=tr["t_total"],
                            seed=args.seed, scene_id=uid, sample_id=0)

    srv.submit(lane(0))
    srv.run_until_drained()
    srv.submit(lane(1))
    tmp = tempfile.mkdtemp(prefix="serve_trace_")
    try:
        jax.profiler.start_trace(tmp)
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.tick"):
                srv.tick()
        jax.block_until_ready(srv.cache)
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                         "*.xplane.pb"))
        with open(path, "rb") as src, gzip.open(args.out, "wb") as dst:
            shutil.copyfileobj(src, dst)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {args.out} ({os.path.getsize(args.out)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
