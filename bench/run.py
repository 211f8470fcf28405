"""Run one cell of the benchmark once, on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The last line of standard output is one
JSON object with ``correct``, ``attempted``, ``failed``, ``metrics`` and
``device`` (and ``breakdown`` with ``--trace 1``); the last lines of
standard error list each number compared beside its limit. Without a TPU,
or with fewer chips than the cell asks for, it prints no result and exits
with a nonzero code.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled window. The persistent compilation
cache lives in ``JAX_COMPILATION_CACHE_DIR`` when that is set, else in
``.jax_cache/`` at the checkout's root; stores and traces go under the
temporary directory and are removed.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def fail(msg: str) -> None:
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"the program under test is not at {src}; run from a "
             "checkout of the repository")
    sys.path[:0] = [HERE, src]
    import harness
    cell = harness.load_cell(args.workload)

    import jax
    harness.use_compile_cache()
    from probe import CompileCounter
    counter = CompileCounter()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX's first device is {devs[0].platform!r} "
             f"({devs[0].device_kind}); the benchmark measures only on "
             "the chip")
    if len(devs) < cell.chips:
        fail(f"{args.workload} needs {cell.chips} chips, JAX finds "
             f"{len(devs)}")

    work = tempfile.mkdtemp(prefix="bench_")
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_START, work_dir=work,
                                  counter=counter)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    for line in harness.check_lines(result):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
