"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload rm64.analytic --seed 7 --seconds 30 --trace 0

Run from the root of a checkout.  The cell's configuration, traffic mix and
metrics are read from ``BENCHMARK.json`` and ``bench/``.  A cell whose
``chips`` n is above 1 is served by the sharded engine, its table in n row
ranges, one on each of its n chips.  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device`` (with the fullest chip's
``memory_peak_bytes`` and every chip's in ``memory_peak_bytes_per_chip``;
with ``--trace 1`` also ``busy_s``, the mean over the chips, ``window_s``
and each chip's in ``busy_s_per_chip``, chip 0 first), with ``--trace 1``
a ``breakdown``, and ``checks``: each number the
reference comparison used, beside its limit (also the last lines of
standard error).

Exit codes: 0 after a result line; 2 when the engine cannot be imported
(no ``src/`` beside ``bench/``); 3 when JAX finds no TPU or fewer chips than
the cell asks for.  No result line is printed in either case.
"""

import time

T0 = time.perf_counter()  # process start, as near as Python gets to it

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import repro.serve  # noqa: F401
    except ImportError as e:
        print(f"bench: cannot import the engine ({e}); run from the root of "
              "a checkout", file=sys.stderr)
        return 2
    from bench import harness

    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t0=T0)
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
