"""The control and the planted faults, read on the chip at a cell's size.

    python chipbench/control.py --workload <name> --seeds 1,2,3 \\
        [--seconds 51]

For each seed it makes the corpus and the requests a run of ``--seconds``
makes (of a closed mix, the check set: the requests every run answers),
and compares, as a run compares the system's answers:

* ``control``: the reference put in the system's place, computed in
  bfloat16 (``reference.control_numbers``);
* ``altered``: the exact answers with one id per answer replaced by
  another selected id, the distance left as it was;
* ``unchanged``: answers from a search that never left its start: k
  selected ids drawn at random, with their exact distances;
* ``half_batch``: the second half of the answers replaced by the first
  half's;
* ``predicate``: the exact answers of the search without its Q_S.

One JSON line per seed. These readings set the upper ends of the
limits in ``chipbench/limits/<cell>.json``; the benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def readings(cfg: dict, mix: dict, gen, seed: int, seconds: float) -> dict:
    import numpy as np

    import corpus as corpus_mod
    import reference
    import traffic

    k, metric = int(cfg["k"]), cfg["metric"]
    c = corpus_mod.make_corpus(cfg, mix.get("data_seed", seed))
    count = (int(mix["clients"]) * int(mix["recall_per_client"])
             if "clients" in mix else traffic.request_count(mix, seconds))
    reqs = gen.make_requests(mix, c, cfg, seed, count + 1)[:-1]
    Q = np.stack([r.q for r in reqs])
    masks = np.stack([gen.mask(c, r.pred) for r in reqs])
    ids, d = reference.exact_topk(c.X, Q, masks, k, metric)
    rng = corpus_mod.rng_for(seed, 99)

    def numbers(got_ids, got_d, m=masks):
        out = reference.compare(c.X, Q, m, got_ids, got_d, k, metric)
        out.pop("recall")
        return out

    altered = ids.copy()
    unchanged_i = np.empty_like(ids)
    unchanged_d = np.empty_like(d)
    Xn = reference._prep(c.X, metric)
    for i, m in enumerate(masks):
        sel = np.flatnonzero(m)
        outside = np.setdiff1d(sel, ids[i])
        if len(outside):
            altered[i, 0] = rng.choice(outside)
        pick = rng.choice(sel, size=min(k, len(sel)), replace=False)
        pick = np.pad(pick, (0, k - len(pick)), constant_values=-1)
        dd = reference.row_dists(Xn, reference._prep(Q[i:i + 1], metric),
                                 pick[None], metric)[0]
        order = np.argsort(dd)
        unchanged_i[i], unchanged_d[i] = pick[order], dd[order]
    half = len(ids) // 2
    half_i, half_d = ids.copy(), d.copy()
    half_i[half:2 * half], half_d[half:2 * half] = ids[:half], d[:half]
    nofilter = np.ones_like(masks)
    p_ids, p_d = reference.exact_topk(c.X, Q, nofilter, k, metric)
    control = reference.control_numbers(c.X, Q, masks, k, metric)
    control.pop("recall")
    return {"seed": seed, "requests": len(reqs), "control": control,
            "altered": numbers(altered, d),
            "unchanged": numbers(unchanged_i, unchanged_d),
            "half_batch": numbers(half_i, half_d),
            "predicate": numbers(p_ids, p_d)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args()
    sys.path.insert(0, str(HERE))
    import jax

    import manifest
    import traffic

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    bench = manifest.load(ROOT)
    cell = manifest.cell(bench, args.workload)
    cfg = manifest.config(bench, cell["config"], ROOT)
    mix_path = manifest.traffic_path(cell["traffic"], ROOT)
    mix, gen = traffic.load(mix_path), traffic.generator(mix_path)
    for s in args.seeds.split(","):
        out = readings(cfg, mix, gen, int(s), args.seconds)
        print(json.dumps({"workload": args.workload} | out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
