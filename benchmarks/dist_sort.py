"""Distributed sample-sort benchmark: single- vs multi-device throughput.

  PYTHONPATH=src python -m benchmarks.dist_sort
      spawns itself with XLA_FLAGS=--xla_force_host_platform_device_count=8
      so the PSRS pipeline actually spans 8 (virtual) devices, and records
      both configurations into the BENCH json flow
      (experiments/bench/dist_sort.json) alongside the usual CSV rows;

  PYTHONPATH=src python -m benchmarks.run --only dist_sort
      in-process single-configuration run at the current device count.

On a CPU host the 8 virtual devices share the same silicon, so the
multi-device rows measure pipeline overhead (partition + two all_to_alls),
not speedup — the json records device_count so downstream comparisons
know which regime they are reading.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Optional

RESULTS_PATH = os.path.join("experiments", "bench", "dist_sort.json")
_CHILD_ENV = "_REPRO_DIST_BENCH_CHILD"


def run(json_path: Optional[str] = None) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro
    from repro.parallel.sharding import Parallelism

    from .common import emit, timeit

    ndev = jax.device_count()
    rng = np.random.default_rng(0)
    par = None
    if ndev > 1:
        mesh = jax.make_mesh((ndev,), ("model",))
        par = Parallelism(mesh=mesh, dp_axes=(), tp_axis="model",
                          fsdp_axis=None)
    records = []
    for n in (16_384, 65_536):
        x = jnp.asarray(rng.standard_normal((1, n)), jnp.float32)
        f1 = jax.jit(lambda v: repro.sort(v))
        t1 = timeit(f1, x, warmup=1, iters=3)
        emit(f"dist_sort/single_n{n}", t1 * 1e6, f"{n / t1 / 1e6:.2f}Melem/s")
        records.append({"name": f"single_n{n}", "devices": 1,
                        "us_per_call": t1 * 1e6, "melem_per_s": n / t1 / 1e6})
        if par is not None:
            fd = jax.jit(lambda v: repro.sort(v, par=par))
            td = timeit(fd, x, warmup=1, iters=3)
            emit(f"dist_sort/dist{ndev}_n{n}", td * 1e6,
                 f"{n / td / 1e6:.2f}Melem/s")
            records.append({"name": f"dist{ndev}_n{n}", "devices": ndev,
                            "us_per_call": td * 1e6,
                            "melem_per_s": n / td / 1e6})
    # k-way merge: 4 pre-sorted lists
    lists = [jnp.sort(jnp.asarray(rng.standard_normal((1, 16_384)), jnp.float32), -1)
             for _ in range(4)]
    fm = jax.jit(lambda *ls: repro.merge_k(list(ls)))
    tm = timeit(fm, *lists, warmup=1, iters=3)
    emit("dist_sort/merge4_single_n16384", tm * 1e6)
    records.append({"name": "merge4_single_n16384", "devices": 1,
                    "us_per_call": tm * 1e6})
    if par is not None:
        fmd = jax.jit(lambda *ls: repro.merge_k(list(ls), par=par))
        tmd = timeit(fmd, *lists, warmup=1, iters=3)
        emit(f"dist_sort/merge4_dist{ndev}_n16384", tmd * 1e6)
        records.append({"name": f"merge4_dist{ndev}_n16384", "devices": ndev,
                        "us_per_call": tmd * 1e6})
    if json_path:
        os.makedirs(os.path.dirname(json_path), exist_ok=True)
        payload = {"bench": "dist_sort", "device_count": ndev,
                   "backend": jax.default_backend(), "rows": records}
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {json_path}", file=sys.stderr)


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if os.environ.get(_CHILD_ENV) == "1":
        print("name,us_per_call,derived")
        run(json_path=RESULTS_PATH)
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env[_CHILD_ENV] = "1"
    env.setdefault("PYTHONPATH", "src")
    subprocess.run([sys.executable, "-m", "benchmarks.dist_sort"], env=env,
                   check=True)


if __name__ == "__main__":
    main()
