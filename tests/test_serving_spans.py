"""Spans and counters inside the served path.

* ``Response.queue_ms`` runs from enqueue to the admission that popped
  the request, so a request enqueued while a tick waits on the device
  never reads a negative wait, and ``queue_ms + exec_ms`` is unchanged;
* ``Response.iters`` / ``dc`` / ``picks`` are the engine's own
  ``SearchStats``, lane for lane, in the service and the engine, at both
  residencies;
* the engine step's named scopes reach the lowered chunk program and
  leave its optimized instructions unchanged, and the program's map from
  instruction to scope lets the benchmark's readers name a TPU op;
* a CPU profile of a few manual ticks holds every ``navix.*`` host span,
  each nested where it belongs.
"""

import contextlib
import pathlib
import re

import jax
import numpy as np
import pytest

from repro.api.db import NavixDB
from repro.query.operators import Filter, NodeScan
from repro.serving import SearchEngine, SearchService
from repro.serving import spans
from repro.storage.columnar import GraphStore

ROOT = pathlib.Path(__file__).resolve().parents[1]

#: the host spans of one service tick, and of one submit
TICK_SPANS = ("navix.expire", "navix.wait", "navix.finalize",
              "navix.rerank", "navix.evict", "navix.admit",
              "navix.dispatch", "navix.resolve")
SUBMIT_SPANS = ("navix.prefilter", "navix.prep_query", "navix.enqueue")


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def qindex(index):
    return index.quantize_resident()


def _db(idx, n):
    store = GraphStore()
    store.add_node_table("Chunk", n, {"cID": np.arange(n)})
    db = NavixDB(store)
    db.register_index("default", idx)
    return db


def _cut_plan(cut):
    return Filter(NodeScan("Chunk"), "cID", "<", value=cut)


def _drive(svc, futs, max_ticks=500):
    for _ in range(max_ticks):
        if all(f.done() for f in futs):
            return
        svc._tick()
    raise AssertionError("service did not resolve all futures")


def test_span_feeds_its_counter():
    counters = {"x_ms": 1.0}
    with spans.span("navix.test", counters, "x_ms", lanes=3):
        pass
    assert counters["x_ms"] > 1.0


def test_queue_ms_is_stamped_at_admission(index, queries):
    """A request enqueued while the tick waits on the device is admitted
    in that tick: its queue_ms is the wait from enqueue to the admission
    (never negative), and queue_ms + exec_ms is still enqueue -> answer."""
    n = index.graph.n
    clk = FakeClock(0.0)
    svc = SearchService(_db(index, n), k_cap=6, efs_cap=24, max_batch=4,
                        step_iters=2, clock=clk)
    first = svc.submit(queries[0], k=6)
    svc._tick()                                  # admit + dispatch
    late = []
    real_wait = svc.lanes.step_wait

    def wait_and_submit():
        if not late:
            clk.t = 2.0                          # enqueued mid-wait ...
            late.append(svc.submit(queries[1], k=6))
            clk.t = 2.5                          # ... admitted at 2.5
        return real_wait()

    svc.lanes.step_wait = wait_and_submit
    clk.t = 1.0                                  # the tick's own `now`
    svc._tick()
    clk.t = 3.0
    _drive(svc, [first, late[0]])
    r = late[0].result(timeout=0)
    assert r.status == "ok"
    assert r.queue_ms == pytest.approx(500.0)
    assert r.queue_ms + r.exec_ms == pytest.approx(1000.0)
    r0 = first.result(timeout=0)
    assert r0.queue_ms >= 0 and r0.exec_ms >= 0
    svc.shutdown()


def _lane_stats(idx, queries, masks, k, efs):
    res = idx.search_many(queries, k=k, efs=efs,
                          semimask=np.stack(masks))
    st = jax.device_get(res.stats)
    return [(int(st.iters[b]), int(st.t_dc[b] + st.upper_dc[b]),
             tuple(int(x) for x in st.picks[b]))
            for b in range(len(masks))]


@pytest.mark.parametrize("resident", ["f32", "int8"])
@pytest.mark.parametrize("server", ["service", "engine"])
def test_response_work_counters_match_search_many(index, qindex, queries,
                                                  resident, server):
    idx = index if resident == "f32" else qindex
    n = idx.graph.n
    k, efs = 6, 24
    cuts = [n // 8, n // 3, n // 2, n, 2 * n // 3, n // 5]
    masks = [np.arange(n) < c for c in cuts]
    want = _lane_stats(idx, queries[:len(cuts)], masks, k, efs)
    if server == "service":
        svc = SearchService(_db(idx, n), k_cap=k, efs_cap=efs, max_batch=4,
                            step_iters=3)
        futs = [svc.submit(queries[j], plan=_cut_plan(c), k=k)
                for j, c in enumerate(cuts)]
        _drive(svc, futs)
        got = [f.result(timeout=0) for f in futs]
        svc.shutdown()
    else:
        eng = SearchEngine(db=_db(idx, n), efs=efs, max_batch=4,
                           step_iters=3)
        rids = [eng.submit(queries[j], plan=_cut_plan(c), k=k)
                for j, c in enumerate(cuts)]
        by = {r.rid: r for r in eng.drain()}
        got = [by[rid] for rid in rids]
    for r, (iters, dc, picks) in zip(got, want):
        assert r.status == "ok"
        assert (r.iters, r.dc, r.picks) == (iters, dc, picks)
        assert sum(r.picks) == r.iters


def test_rerank_counter_counts_each_finalize(index, qindex, queries):
    for idx, reranks in ((index, False), (qindex, True)):
        n = idx.graph.n
        svc = SearchService(_db(idx, n), k_cap=6, efs_cap=24, max_batch=4,
                            step_iters=3)
        futs = [svc.submit(queries[j], k=6) for j in range(5)]
        _drive(svc, futs)
        t = svc.gauges()["chunks"]
        assert (t["n_rerank"] > 0) == reranks
        assert (t["rerank_ms"] > 0) == reranks
        assert svc.gauges()["submit_max_ms"] > 0
        svc.shutdown()


def test_chunk_program_carries_the_named_scopes(index, queries):
    svc = SearchService(_db(index, index.graph.n), k_cap=6, efs_cap=24,
                        max_batch=4, step_iters=3)
    svc.submit(queries[0], k=6)
    svc._tick()
    text = svc.lanes.lower_step(3).as_text(debug_info=True)
    for scope in ("navix.visited", "navix.semimask", "navix.gather",
                  "navix.merge", "navix.frontier"):
        assert scope in text, scope
    svc.shutdown(drain=False)


def _instructions(text):
    """The optimized HLO's instruction lines without their metadata."""
    return [re.sub(r",? metadata=\{[^}]*\}", "", line)
            for line in text.splitlines()
            if re.match(r"\s*(ROOT )?%\S+ = ", line)]


def test_named_scopes_leave_the_chunk_program_unchanged(index, queries,
                                                        monkeypatch):
    """The engine step compiles to the same instructions, names included,
    with and without its named scopes: they move op metadata only."""
    svc = SearchService(_db(index, index.graph.n), k_cap=6, efs_cap=24,
                        max_batch=4, step_iters=3)
    svc.submit(queries[0], k=6)
    svc._tick()
    texts = [svc.lanes.lower_step(3).compile().as_text()]
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    try:
        jax.clear_caches()
        texts.append(svc.lanes.lower_step(3).compile().as_text())
    finally:
        # later tests must trace the scoped engine again
        monkeypatch.undo()
        jax.clear_caches()
    svc.shutdown(drain=False)
    assert "navix.semimask" in texts[0] and "navix.semimask" not in texts[1]
    assert _instructions(texts[0]) == _instructions(texts[1])


def test_scope_readers_use_the_program_map(index, queries, monkeypatch):
    """A TPU trace's op events name the instruction (with its operands'
    types) but carry no op_name: the scope share readers then take the
    scope from the program's map of its dispatched chunk program."""
    monkeypatch.syspath_prepend(str(ROOT / "chipbench"))
    import manifest
    import tracing
    from harness import Ctx
    svc = SearchService(_db(index, index.graph.n), k_cap=6, efs_cap=24,
                        max_batch=4, step_iters=3)
    svc.submit(queries[0], k=6)
    svc._tick()
    text = svc.lanes.lower_step(3).compile().as_text()
    svc.shutdown(drain=False)
    table = spans.op_scopes()
    assert set(table.values()) >= {"navix.visited", "navix.semimask",
                                   "navix.gather", "navix.merge",
                                   "navix.frontier"}
    lines = {}
    for line in text.splitlines():
        found = re.findall(r'op_name="[^"]*(navix\.\w+)', line)
        if found and re.match(r"\s+%\S+ = \S+ fusion\(", line):
            lines.setdefault(found[-1], line.strip())
    ms, D = 1e6, "/device:TPU:0"

    def as_event(line, start, dur):
        # a profile's op event: no metadata, operands with their types
        head, _, rest = re.sub(r", metadata=\{[^}]*\}.*", "",
                               line).partition(" fusion(")
        rest = re.sub(r"(%[\w.\-]+)", r"u32[8]{0} \1", rest)
        return tracing.Ev(D, "XLA Ops", f"{head} fusion({rest}", start * ms,
                          dur * ms)

    events = [tracing.Ev("/host:CPU", "python", "chipbench.traced", 0,
                         100 * ms),
              as_event(lines["navix.semimask"], 10, 30),
              as_event(lines["navix.visited"], 50, 10),
              tracing.Ev(D, "XLA Ops", "%other.1 = f32[8]{0} add(%a, %b)",
                         70 * ms, 20 * ms)]
    ctx = Ctx(cfg={}, window=[], timing={}, build_s=1.0,
              trace=tracing.summarize(events), device_kind="TPU v5 lite")
    got = [manifest.load_layer(m, ROOT).read(ctx)
           for m in ("engine.semimask_share", "engine.visited_share")]
    assert got == [pytest.approx(50.0), pytest.approx(50.0 / 3)]


def _nested(inner, outer):
    """True where ``inner`` lies inside one of the ``outer`` spans."""
    return any(o.start <= inner.start and inner.end <= o.end
               for o in outer)


def test_cpu_profile_holds_every_span(qindex, queries, tmp_path,
                                     monkeypatch):
    """A few manual ticks of an int8 index under the profiler: every tick
    span lies inside a navix.tick step, every submit span inside
    navix.submit."""
    monkeypatch.syspath_prepend(str(ROOT / "chipbench"))
    import tracing
    n = qindex.graph.n
    svc = SearchService(_db(qindex, n), k_cap=6, efs_cap=24, max_batch=4,
                        step_iters=2)
    warm = svc.submit(queries[0], plan=_cut_plan(n // 2), k=6)
    _drive(svc, [warm])                          # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        futs = [svc.submit(queries[j], plan=_cut_plan(n // (j + 2)), k=6)
                for j in range(6)]
        _drive(svc, futs)
    finally:
        jax.profiler.stop_trace()
    svc.shutdown()
    events = tracing.read_trace(str(tmp_path))
    by_name = {}
    for e in events:
        if e.name.startswith("navix."):
            by_name.setdefault(e.name, []).append(e)
    ticks, submits = by_name.get("navix.tick", []), \
        by_name.get("navix.submit", [])
    assert ticks and len(submits) == 6
    for name in TICK_SPANS:
        assert name in by_name, name
        assert all(_nested(e, ticks) for e in by_name[name]), name
    for name in SUBMIT_SPANS:
        assert name in by_name, name
        assert all(_nested(e, submits) for e in by_name[name]), name
