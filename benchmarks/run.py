# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
#
#   RQ1 (paper §5.2)  cold vs incremental ingestion
#   RQ2 (paper §5.3)  hybrid vs pure-cosine entity Recall@1
#   RQ3 (paper §5.4)  container footprint + query latency
#   kernels           HSF / top-k micro-benchmarks
#   scale             sharded-retrieval payload accounting
#   serving           micro-batching scheduler load tests (open/closed loop)
#   persistence       journaled delta saves vs full container rewrites
#   index             IVF clustered retrieval: QPS-vs-Recall vs flat scan
#
# Roofline tables are a separate heavier entry point
# (``python -m benchmarks.roofline``) because they compile dry-run
# variants under the 512-device XLA flag.
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (
        bench_index,
        bench_paper,
        bench_persistence,
        bench_scale,
        bench_serving,
    )
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = 0
    for fn in (bench_paper.ALL + bench_scale.ALL + bench_serving.ALL
               + bench_persistence.ALL + bench_index.ALL):
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.1f},{derived}")
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{fn.__name__},NaN,FAILED", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
