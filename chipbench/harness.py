"""One run of one cell: set-up, the measured window, the check.

``run_cell`` drives the system's normal served path::

    db.create_index(...)                       # (+ db.quantize_index)
    svc = db.serve(index=..., k_cap, efs_cap, max_batch, step_iters).start()
    svc.submit(query, plan=KnnSearch(child=<Q_S>, k, efs))

and returns the result line. Set-up (``setup_s``) runs from process
start to the window's opening: corpus, build, the compile of every
served program (one warm-up request), and ``ramp_s`` of the cell's own
traffic, which brings the lanes to steady occupancy. A mix with
``clients`` runs a closed loop (``traffic.ClosedLoop``), any other an
open one (``traffic.OpenLoop``). The window then measures for
``seconds``; requests still open at its close, and in a closed loop the
rest of its check set, are waited for up to ``drain_s``; the device's
peak memory is read; the service is shut down and freed; and the
reference checks every answer.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import statistics
import sys
import time
from typing import Callable, Optional

import numpy as np

import corpus as corpus_mod
import manifest
import reference
import tracing
import traffic

#: seconds between the window's opening and the traced stretch, and its
#: length; the trace covers steady serving only
TRACE_LEAD_S, TRACE_S = 2.0, 4.0


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class Compiles:
    """Backend compiles, process-wide, by ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0

        def on_duration(event, duration, **kw):
            if event == self.EVENT:
                self.count += 1
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(on_duration)


@dataclasses.dataclass
class Ctx:
    """What a per-layer reader may read (``layers/<metric>.py``)."""
    cfg: dict
    window: list               # traffic.Record of the window's requests
    timing: dict               # LaneBatch.timing() deltas over the window
    build_s: float
    trace: Optional[object]    # tracing.Summary of the traced stretch
    device_kind: str


def build_db(c: corpus_mod.Corpus, cfg: dict, seed: int):
    """The store and the index, through ``NavixDB.create_index``."""
    import jax

    from repro.api import NavixDB
    from repro.core.navix import NavixConfig
    from repro.storage.columnar import GraphStore

    ix = cfg["index"]
    if int(ix["m_l"]) != 2 * int(ix["m_u"]):
        raise ValueError("the system's lower degree is 2 * m_u; the "
                         "configuration's m_l must agree")
    tables = corpus_mod.graph_store_tables(c)
    store = GraphStore()
    for name, (n, cols) in tables["nodes"].items():
        store.add_node_table(name, n, cols)
    for name, (src_t, dst_t, src, dst) in tables["rels"].items():
        store.add_rel_table(name, src_t, dst_t, src, dst)
    db = NavixDB(store)
    config = NavixConfig(m_u=int(ix["m_u"]),
                         ef_construction=int(ix["ef_construction"]),
                         sample_rate=float(ix["sample_rate"]),
                         metric=cfg["metric"],
                         batch_size=int(ix["batch_size"]), seed=seed)
    t0 = time.perf_counter()
    index, _ = db.create_index("emb", "Chunk", column="embedding",
                               vectors=c.X, config=config)
    jax.block_until_ready(index.graph.lower)
    build_s = time.perf_counter() - t0
    if cfg["residency"] == "int8":
        db.quantize_index("emb")
        jax.block_until_ready(db.index("emb").graph.vectors.codes)
    elif cfg["residency"] != "f32":
        raise ValueError(f"unknown residency {cfg['residency']!r}")
    return db, build_s


def _percentile(values: list[float], q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation."""
    if not values:
        return float("nan")
    return float(np.percentile(np.asarray(values, float), q))


def run_cell(cell_name: str, cfg: dict, mix: dict, gen, lim: dict,
             e2e_specs: list[dict], layer_specs: list[dict], seed: int,
             seconds: float, trace: bool, t_start: float,
             device=None, trace_dir: Optional[str] = None,
             annotate: Optional[Callable] = None) -> dict:
    """One run; returns the result line (a dict). ``gen`` is the mix's
    generator (``traffic.generator``); ``device`` is the jax device the
    cell runs on (None: the default device, as in the tests on the
    CPU)."""
    import jax

    if annotate is None:
        from jax.profiler import TraceAnnotation as annotate
    compiles = Compiles()
    k, efs = int(cfg["k"]), int(cfg["efs"])

    t = time.perf_counter()
    # a mix with ``data_seed`` serves one corpus and index in every run
    data_seed = mix.get("data_seed", seed)
    c = corpus_mod.make_corpus(cfg, data_seed)
    count = traffic.request_count(mix, seconds)
    reqs = gen.make_requests(mix, c, cfg, seed, count + 1)
    warm, reqs = reqs[-1], reqs[:-1]
    say(f"corpus: n={c.n} d={c.X.shape[1]} seed={seed} "
        f"{time.perf_counter() - t:.2f} s; {len(reqs)} requests")

    db, build_s = build_db(c, cfg, data_seed)
    say(f"build: {build_s:.2f} s ({c.n / build_s:.1f} rows/s); compiles "
        f"so far {compiles.count} ({compiles.seconds:.2f} s)")
    svc = db.serve(index="emb", k_cap=k, efs_cap=efs,
                   max_batch=int(cfg["lanes"]),
                   step_iters=int(cfg["step_iters"]),
                   queue_size=max(4096, 2 * len(reqs))).start()
    plans: dict = {}

    def submit(req, deadline_s=None):
        plan = plans.get(req.pred)
        if plan is None:
            plan = plans[req.pred] = gen.plan(req.pred, k, efs)
        return svc.submit(req.q, plan=plan, k=k, deadline_s=deadline_s)

    # warm-up: one request whose deadline passes in its first chunk
    # compiles admission, the chunk, finalize and eviction
    t = time.perf_counter()
    submit(warm, deadline_s=0.25).result(timeout=900)
    say(f"warm-up: {time.perf_counter() - t:.2f} s; compiles "
        f"{compiles.count} ({compiles.seconds:.2f} s)")

    closed = "clients" in mix
    if closed:
        t0 = time.perf_counter() + 0.2
        t_close = t0 + float(mix["ramp_s"]) + seconds
        loop = traffic.ClosedLoop(submit, reqs, int(mix["clients"]),
                                  int(mix["recall_per_client"]), t0,
                                  t_close, t_close + float(mix["drain_s"]),
                                  annotate).start()
    else:
        offs = gen.open_offsets(float(mix["rate_qps"]), len(reqs),
                                corpus_mod.rng_for(seed, 2))
        t0 = time.perf_counter() + 0.2
        loop = traffic.OpenLoop(submit, reqs, offs, t0, annotate).start()
    t_open = t0 + float(mix["ramp_s"])
    t_close = t_open + seconds

    def sleep_until(when: float) -> None:
        time.sleep(max(0.0, when - time.perf_counter()))

    sleep_until(t_open)
    setup_s = time.perf_counter() - t_start
    tim0, comp0 = svc.lanes.timing(), compiles.count
    if trace:
        lead = min(TRACE_LEAD_S, seconds / 4)
        sleep_until(t_open + lead)
        jax.profiler.start_trace(trace_dir)
        with annotate(tracing.WINDOW_SPAN):
            sleep_until(t_open + lead + min(TRACE_S, seconds - 2 * lead))
        jax.profiler.stop_trace()
    sleep_until(t_close)
    tim1, comp1 = svc.lanes.timing(), compiles.count
    loop.stop()
    say(f"window: {seconds:.1f} s closed; compiles in the window "
        f"{comp1 - comp0}")

    records = loop.records
    with annotate("chipbench.drain"):
        traffic.wait_all(records, t_close + float(mix["drain_s"]))
    t_drained = time.perf_counter()
    dev = device if device is not None else jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    svc.shutdown(drain=False)
    del svc, db, plans
    gc.collect()
    if trace:
        summary = tracing.summarize(tracing.read_trace(trace_dir))

    # the window's requests: those due in it, timed from the due time
    win = [r for r in records if t_open <= r.due < t_close]
    lat = [(r.t_done if r.answered else t_drained) - r.due for r in win]
    ok_in_window = sum(1 for r in records if r.answered
                       and t_open <= r.t_done < t_close)
    failed = sum(1 for r in win if not r.answered)
    late = [r.lateness_s for r in win if not math.isnan(r.t_submit)]
    # the sender's lateness: how far the load generator ran behind
    say(f"window: {len(win)} requests, {ok_in_window} answered in it "
        f"({ok_in_window / seconds:.3f} q/s), "
        f"{failed} not answered; sender lateness p50 "
        f"{_percentile(late, 50) * 1e3:.3f} ms, max "
        f"{max(late, default=float('nan')) * 1e3:.3f} ms")

    # the check: every answer the service gave, against the reference
    sent = [r for r in records if r.fut is not None or r.error]
    answered = [r for r in sent if r.answered]
    missing = len(sent) - len(answered)
    t = time.perf_counter()
    if answered:
        Q = np.stack([r.req.q for r in answered])
        masks = np.stack([gen.mask(c, r.req.pred)
                          for r in answered])
        got_ids = np.stack([np.asarray(r.resp.ids, np.int64)
                            for r in answered])
        got_d = np.stack([np.asarray(r.resp.dists, np.float32)
                          for r in answered])
        cmp = reference.compare(c.X, Q, masks, got_ids, got_d, k,
                                cfg["metric"])
        rec_of = {id(r): x for r, x in zip(answered, cmp["recall"])}
    else:
        cmp = {"bad_answers": 0, "dist_gap": 0.0, "miss_share": 1.0}
        rec_of = {}
    say(f"reference: {len(answered)} answers checked in "
        f"{time.perf_counter() - t:.2f} s")
    checks = {
        "missing": [missing, lim["missing"]],
        "bad_answers": [cmp["bad_answers"], lim["bad_answers"]],
        "dist_gap": [cmp["dist_gap"], lim["dist_gap"]],
        "miss_share": [cmp["miss_share"], lim["miss_share"]],
    }
    correct = all(v <= limit for v, limit in checks.values())

    # recall: over the window's requests, or in a closed loop over its
    # check set, which is the same whatever the speed
    win_recall = [rec_of[id(r)] for r in (loop.check_set() if closed
                                          else win) if id(r) in rec_of]
    e2e = {
        "qps": (ok_in_window / seconds, "queries/s"),
        "p90_ms": (_percentile(lat, 90) * 1e3, "ms"),
        "recall": (statistics.fmean(win_recall) if win_recall
                   else float("nan"), "share"),
        "setup_s": (setup_s, "s"),
    }
    say(f"latency: p50 {_percentile(lat, 50) * 1e3:.1f} ms, p90 "
        f"{e2e['p90_ms'][0]:.1f} ms over {len(lat)}; recall "
        f"{e2e['recall'][0]:.6f} over {len(win_recall)}; compiles total "
        f"{compiles.count} ({compiles.seconds:.2f} s)")

    device_info = {"platform": dev.platform, "kind": dev.device_kind,
                   "device_kind": dev.device_kind, "count": 1,
                   "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": len(win),
           "failed": failed}
    if trace:
        timing = {key: tim1[key] - tim0[key] for key in tim0}
        ctx = Ctx(cfg=cfg, window=[r for r in win if r.answered],
                  timing=timing, build_s=build_s, trace=summary,
                  device_kind=dev.device_kind)
        metrics = {}
        for spec in layer_specs:
            value = manifest.load_layer(spec["name"]).read(ctx)
            if value is not None:
                metrics[spec["name"]] = {"value": float(value),
                                         "unit": spec["unit"]}
        device_info.update(busy_s=summary.busy_s,
                           window_s=summary.window_s)
        out["metrics"] = metrics
        out["breakdown"] = summary.breakdown()
    else:
        out["metrics"] = {m["name"]: {"value": float(e2e[m["name"]][0]),
                                      "unit": m["unit"]}
                          for m in e2e_specs}
    out["device"] = device_info
    out["checks"] = {name: {"value": v, "limit": limit}
                     for name, (v, limit) in checks.items()}
    for name, (v, limit) in checks.items():
        say(f"check {name}: {v!r} (limit {limit!r})")
    return out


@contextlib.contextmanager
def no_annotation(_name: str):
    yield
