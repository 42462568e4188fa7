"""ICI-analogue collective throughput (all-reduce / all-gather / reduce-scatter
/ all-to-all / ppermute) on an 8-device host mesh.  Own process: forces the
device count before jax init.  On TPU the same code measures real ICI links."""
import os
if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               + os.environ.get("XLA_FLAGS", ""))

import argparse           # noqa: E402

from benchmarks.common import emit                       # noqa: E402


def main(quick: bool = False):
    from repro.core.collective_bench import bench_all
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    res = bench_all(mesh, nbytes=(1 if quick else 8) * 2**20,
                    reps=4 if quick else 10)
    for r in res:
        emit(f"collectives/{r.op}/{r.axis}{r.group_size}", r.mean_s * 1e6,
             f"algo={r.algo_gbps:.2f}GB/s;link={r.link_gbps:.2f}GB/s")


if __name__ == "__main__":
    from repro.bench import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    main(**vars(ap.parse_args()))
