"""Run one cell of the benchmark described by ``BENCHMARK.json``.

    python3 bench/run.py --workload qwen32b.agent --seed 7 \
        --seconds 30 --trace 0

from the root of a checkout that holds the program (``src/``). The run
needs the chips the cell asks for: with no TPU, or too few, it exits
non-zero and prints no result. The last line of standard output is the
result as one JSON object.

``--rehearse`` runs the cell at its smoke size on the CPU (Pallas in
interpret mode) to try the harness without a chip; such a run reports
no device metric. See bench/README.md.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def arguments(ap: argparse.ArgumentParser) -> argparse.ArgumentParser:
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--benchmark", default="BENCHMARK.json",
                    help="the benchmark file; named files are found "
                         "under its first path")
    ap.add_argument("--rehearse", action="store_true",
                    help="smoke size on the CPU, no device metrics")
    return ap


def prepare(args):
    """Find the cell, point JAX's compile cache into the checkout and
    check the chips. Returns (cell, devices, peaks, root), or None when
    the chips the cell needs are not there."""
    bench_path = os.path.abspath(args.benchmark)
    root = os.path.dirname(bench_path)
    for p in (os.path.join(root, "src"), os.path.dirname(HERE)):
        if os.path.isdir(p) and p not in sys.path:
            sys.path.insert(0, p)
    from bench import spec
    cell = spec.find(bench_path, args.workload)

    import jax
    if not args.rehearse:
        # the program's fixed directory inside the checkout (or the one
        # JAX_COMPILATION_CACHE_DIR names): only a cell's first run
        # there compiles. JAX writes nothing until the directory exists.
        from repro.launch import compile_cache
        os.makedirs(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                    or compile_cache.REPO_CACHE_DIR, exist_ok=True)
        compile_cache.enable_compile_cache()
        # every program, however quick to compile
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        # no eviction: the directory is the checkout's own, and eviction
        # bookkeeping that finds an entry without its access-time file
        # fails every later write
        jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    print(f"device: platform {platform}, kind {kind}, count "
          f"{len(devices)}", flush=True)
    if args.rehearse:
        return cell, devices, None, root
    if platform != "tpu":
        print(f"bench: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return None
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return None
    from bench.peaks import peaks_for
    return cell, devices, peaks_for(kind), root


def main(argv=None) -> int:
    ap = arguments(argparse.ArgumentParser(
        description=__doc__.split("\n")[0]))
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    found = prepare(args)
    if found is None:
        return 2
    cell, devices, peaks, root = found

    from bench import harness
    result = harness.run(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), devices=devices,
                         peaks=peaks, t_start=T_START, root=root,
                         rehearse=args.rehearse)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
