"""A cell cut to a size that a CPU test run holds, for the tests under
``tests/chipbench``: the same configuration, mix and limits files, with
the corpus, index, lanes and window made small. Never used by a run of
the benchmark."""

from __future__ import annotations

import time
from typing import Optional

import harness
import manifest
import traffic


#: a closed mix at the test size: a client per lane, a check set of 16
CLOSED = {"clients": 8, "recall_per_client": 2, "requests_per_client": 400}


def run(cell_name: str, seed: int = 2**31 + 17, seconds: float = 2.0,
        root=manifest.ROOT, cfg_update: Optional[dict] = None,
        mix_update: Optional[dict] = None) -> dict:
    """One run of ``cell_name`` at the test size; returns its result.
    ``cfg_update`` and ``mix_update`` change the configuration and the
    mix after they are cut to the test size."""
    bench = manifest.load(root)
    cell = manifest.cell(bench, cell_name)
    cfg = manifest.config(bench, cell["config"], root)
    cfg.update(n=2048, d=32, lanes=8, k=10, efs=200)
    cfg["index"].update(m_u=16, m_l=32, ef_construction=100)
    cfg.update(cfg_update or {})
    mix_path = manifest.traffic_path(cell["traffic"], root)
    mix = traffic.load(mix_path)
    mix.update(ramp_s=1.0, drain_s=20.0)
    mix.update(CLOSED if "clients" in mix else {"rate_qps": 20.0})
    mix.update(mix_update or {})
    return harness.run_cell(cell_name, cfg, mix, traffic.generator(mix_path),
                            manifest.limits(cell_name, root),
                            manifest.end_to_end(bench, cell_name), [], seed,
                            seconds, False, time.perf_counter(),
                            annotate=harness.no_annotation)
