"""Find a cell's knee: the highest open-loop rate served without a
growing backlog. Run once on the chip when a cell's rate is chosen;
the benchmark's runs never sweep.

    python chipbench/sweep.py --workload <name> --seed <n> \\
        --rates 2.5,3,3.5,4 [--seconds 30]

Builds the cell's index once, then offers each rate (the cell's traffic
mix with ``rate_qps`` replaced) for ``ramp_s + seconds``, samples the
backlog (queued + in lanes) every second, and waits for every request
before the next rate. One JSON line per rate: offered and completed
rates over the window, the backlog at the window's open and close, and
the latency percentiles of the requests due in it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args()
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    import numpy as np

    import corpus as corpus_mod
    import harness
    import manifest
    import traffic

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = manifest.load(ROOT)
    cell = manifest.cell(bench, args.workload)
    cfg = manifest.config(bench, cell["config"], ROOT)
    mix_path = manifest.traffic_path(cell["traffic"], ROOT)
    mix, gen = traffic.load(mix_path), traffic.generator(mix_path)
    rates = [float(r) for r in args.rates.split(",")]
    k, efs = int(cfg["k"]), int(cfg["efs"])
    c = corpus_mod.make_corpus(cfg, args.seed)
    db, build_s = harness.build_db(c, cfg, args.seed)
    harness.say(f"build {build_s:.2f} s")
    svc = db.serve(index="emb", k_cap=k, efs_cap=efs,
                   max_batch=int(cfg["lanes"]),
                   step_iters=int(cfg["step_iters"]),
                   queue_size=8192).start()
    plans: dict = {}

    def submit(req, deadline_s=None):
        if req.pred not in plans:
            plans[req.pred] = gen.plan(req.pred, k, efs)
        return svc.submit(req.q, plan=plans[req.pred], k=k,
                          deadline_s=deadline_s)

    ramp = float(mix["ramp_s"])
    for i, rate in enumerate(rates):
        dur = ramp + args.seconds
        n = max(1, int(round(rate * dur)))
        reqs = gen.make_requests(mix, c, cfg, args.seed + 7919 * i, n)
        offs = gen.open_offsets(rate, n,
                                corpus_mod.rng_for(args.seed, 100 + i))
        t0 = time.perf_counter() + 0.2
        loop = traffic.OpenLoop(submit, reqs, offs, t0,
                                harness.no_annotation).start()
        t_open, t_close = t0 + ramp, t0 + dur
        backlog = []
        while time.perf_counter() < t_close:
            time.sleep(1.0)
            backlog.append(len(svc.queue) + svc.lanes.occupied_count())
        depth_close = len(svc.queue) + svc.lanes.occupied_count()
        traffic.wait_all(loop.records, time.perf_counter() + 180)
        loop.stop()
        win = [r for r in loop.records if t_open <= r.due < t_close]
        lat = [r.latency_s * 1e3 for r in win if r.answered]
        done = sum(1 for r in loop.records if r.answered
                   and t_open <= r.t_done < t_close)
        late = [r.lateness_s * 1e3 for r in win]
        print(json.dumps({
            "rate_qps": rate, "requests_in_window": len(win),
            "completed_qps": done / args.seconds,
            "backlog_open": backlog[int(ramp)] if len(backlog) > ramp
            else None, "backlog_close": depth_close,
            "backlog_max": max(backlog),
            "p50_ms": float(np.percentile(lat, 50)) if lat else None,
            "p90_ms": float(np.percentile(lat, 90)) if lat else None,
            "unanswered": sum(1 for r in win if not r.answered),
            "lateness_max_ms": max(late) if late else None}), flush=True)
    svc.shutdown(drain=False)
    harness.say(f"sweep done in {time.perf_counter() - T_START:.1f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
