"""Run one cell of the chip benchmark once.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the root of a checkout. The cell (``BENCHMARK.json``'s
``workloads``) names a configuration and a traffic mix; see
``chipbench/README.md``. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared, with its limit. Progress and the same checks go to
standard error.

Off the TPU, or with fewer chips than the cell asks for, or without the
system under test beside it (``src/``), it exits non-zero and prints no
result. JAX's persistent compilation cache lives at ``.jax_cache`` in
the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".chipbench_trace"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(HERE))
    import manifest

    bench = manifest.load(ROOT)
    cell = manifest.cell(bench, args.workload)
    cfg = manifest.config(bench, cell["config"], ROOT)
    import traffic
    mix_path = manifest.traffic_path(cell["traffic"], ROOT)
    mix, gen = traffic.load(mix_path), traffic.generator(mix_path)
    lim = manifest.limits(cell["name"], ROOT)
    layer_specs = manifest.per_layer(bench, cell["name"])
    for spec in layer_specs:            # fail before any work if missing
        manifest.load_layer(spec["name"], ROOT)

    if not (ROOT / "src" / "repro").is_dir():
        print("chipbench: the system under test (src/repro) is not in "
              "this checkout", file=sys.stderr)
        return 3
    sys.path.insert(1, str(ROOT / "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chipbench: no TPU (JAX platform {devs[0].platform!r}); "
              f"the benchmark measures on the chip only", file=sys.stderr)
        return 2
    if len(devs) < int(cell["chips"]):
        print(f"chipbench: {cell['name']} needs {cell['chips']} chip(s), "
              f"JAX sees {len(devs)}", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    import harness

    trace_dir = None
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = str(TRACE_DIR)
    try:
        out = harness.run_cell(cell["name"], cfg, mix, gen, lim,
                               manifest.end_to_end(bench, cell["name"]),
                               layer_specs,
                               args.seed, args.seconds, bool(args.trace),
                               T_START, device=devs[0], trace_dir=trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
