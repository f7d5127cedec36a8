"""The traffic generator, and the open and closed loops that drive a
service.

A traffic mix is a data file, ``chipbench/traffic/<mix>.json``, read by
the functions here. Its keys:

* ``rate_qps``: the offered rate of the open loop (independent users,
  Poisson arrivals on an absolute schedule);
* or, for a closed loop (callers that each wait for their answer),
  ``clients``, ``recall_per_client`` and ``requests_per_client``: each
  client sends its next request as soon as its previous answer is
  recorded (``ClosedLoop``); the first ``recall_per_client`` requests
  of every client are the check set, which ``recall`` is taken over; and
  the generator makes ``clients * requests_per_client`` requests, more
  than any client can send in a run. A mix with ``clients`` runs closed;
* ``data_seed`` (optional): the corpus and the index build come from
  this seed in every run, not from the run's seed, so every run serves
  one deployment; a mix's own module may draw its request sample from
  it too, and order it by the run's seed;
* ``ramp_s``: seconds of the same traffic before the window opens, so
  that the window starts at steady lane occupancy;
* ``drain_s``: how long past the window's close an answer is waited for;
* ``queries``: where query vectors come from (``global``: the corpus's
  own mixture, ``corpus.sample_queries``);
* ``predicate``: the selection subquery Q_S, ``{"kind": "cid_lt"}``
  (``Chunk.cID < sigma * n``, one Q_S per sigma), and its ``sigmas``.

A mix may also have a module of its own, ``chipbench/traffic/<mix>.py``.
Each of ``make_requests``, ``open_offsets``, ``plan`` and ``mask`` that
it defines takes the place of this module's (``generator``), so a new
kind of Q_S or of arrivals is added as new files. A cell whose mix needs
code of its own is a benchmark change, not data.

Every seed gets the same set of sizes and arrivals in another order:
the gaps are one sample of independent exponential gaps (Poisson
arrivals), the same for every seed, in an order the seed draws
(``open_offsets``); the sigmas are the listed ones, each as often as the
others, in an order the seed draws. The corpus and the query vectors
are drawn afresh, but for what a mix pins with ``data_seed``.

Open-loop requests are timed from their due time, which is absolute
(``t0 + offset``): a sender that runs late does not move later requests,
and its lateness is recorded for every request. A closed-loop request is
due when its client sends it.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
import threading
import time
import types
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Optional

import numpy as np

from corpus import Corpus, rng_for, sample_queries

#: the stream of the gap sample that every seed shares
GAPS_STREAM = 0x6A09E667


@dataclasses.dataclass
class Request:
    idx: int
    q: np.ndarray              # f32[d]
    pred: tuple                # ("cid_lt", threshold), or a mix module's
    sigma: float               # the mix's target selectivity


@dataclasses.dataclass
class Record:
    """One request as the client saw it (host clock, seconds)."""
    req: Request
    due: float
    t_submit: float = float("nan")
    t_done: float = float("nan")
    resp: object = None
    error: Optional[str] = None
    fut: object = None
    settled: threading.Event = dataclasses.field(
        default_factory=threading.Event)   # set once the answer is recorded

    @property
    def answered(self) -> bool:
        return self.resp is not None and getattr(self.resp, "status",
                                                 None) == "ok"

    @property
    def latency_s(self) -> float:
        return self.t_done - self.due

    @property
    def lateness_s(self) -> float:
        return self.t_submit - self.due


def load(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def request_count(mix: dict, seconds: float) -> int:
    """Requests the mix generates for a window of ``seconds``."""
    if "clients" in mix:
        return int(mix["clients"]) * int(mix["requests_per_client"])
    dur = float(mix["ramp_s"]) + float(seconds)
    return max(1, int(round(float(mix["rate_qps"]) * dur)))


def open_offsets(rate: float, count: int,
                 rng: np.random.Generator) -> np.ndarray:
    """Due offsets (s) of ``count`` Poisson arrivals at ``rate``. The
    ``count - 1`` gaps are one sample of independent exponential gaps,
    drawn from a stream that every seed shares and scaled to a mean of
    exactly 1 / rate, in an order drawn from ``rng``. So every seed
    offers the same total work with the same bursts, placed elsewhere.
    The first request is due at 0."""
    gaps = np.random.default_rng([GAPS_STREAM, count]).exponential(
        1.0, count - 1)
    if count > 1:
        gaps *= (count - 1) / gaps.sum() / rate
    return np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))])


def make_requests(mix: dict, corpus: Corpus, cfg: dict, seed: int,
                  count: int) -> list[Request]:
    rng = rng_for(seed, 1)
    Q = sample_queries(corpus, cfg, mix["queries"], count, rng)
    pred = mix["predicate"]
    if pred["kind"] != "cid_lt":
        raise ValueError(f"unknown predicate kind {pred['kind']!r}")
    # each sigma as often as the others, in an order the seed draws
    sig = rng.permutation(np.resize(np.asarray(pred["sigmas"], float),
                                    count))
    return [Request(idx=i, q=Q[i], pred=("cid_lt", int(corpus.n * s)),
                    sigma=float(s)) for i, s in enumerate(sig)]


def mask(c: Corpus, pred: tuple) -> np.ndarray:
    """Q_S as bool[n], evaluated in numpy from the corpus's own columns
    (the reference's side: never the system's plan evaluator)."""
    if pred[0] == "cid_lt":
        return c.cid < pred[1]
    raise ValueError(f"unknown predicate {pred!r}")


def plan(pred: tuple, k: int, efs: int):
    """The system's plan for one request's Q_S (the system's side)."""
    from repro.query.operators import Filter, KnnSearch, NodeScan
    if pred[0] == "cid_lt":
        qs = Filter(NodeScan("Chunk"), "cID", "<", value=pred[1])
    else:
        raise ValueError(pred)
    return KnnSearch(child=qs, k=k, efs=efs)


#: what a mix's own module may define in place of this module's
HOOKS = ("make_requests", "open_offsets", "plan", "mask")


def generator(path) -> types.SimpleNamespace:
    """The generator of the mix at ``path`` (``traffic/<mix>.json``):
    the hooks that ``traffic/<mix>.py`` defines, where that file exists,
    and this module's for the rest."""
    own = None
    py = pathlib.Path(path).with_suffix(".py")
    if py.is_file():
        spec = importlib.util.spec_from_file_location(
            "chipbench_mix_" + py.stem.replace(".", "_"), py)
        own = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(own)
    return types.SimpleNamespace(**{
        name: getattr(own, name, None) or globals()[name] for name in HOOKS})


def _sender(submit: Callable, rec: Record, annotate) -> None:
    rec.t_submit = time.perf_counter()
    try:
        with annotate("chipbench.submit"):
            fut = submit(rec.req)
    except Exception as e:            # QueueFull, ServiceClosed: a failure
        rec.error = repr(e)
        rec.settled.set()
        return

    def done(f):
        rec.t_done = time.perf_counter()
        if f.cancelled():
            rec.error = "cancelled"
        elif f.exception() is not None:
            rec.error = repr(f.exception())
        else:
            rec.resp = f.result()
        rec.settled.set()

    rec.fut = fut
    fut.add_done_callback(done)


class OpenLoop:
    """Sends ``requests[i]`` at ``t0 + offsets[i]`` (absolute) through a
    few sender threads, so that a submit that blocks does not delay the
    next one."""

    def __init__(self, submit: Callable, requests: list[Request],
                 offsets: np.ndarray, t0: float, annotate, senders: int = 4):
        self.submit, self.annotate = submit, annotate
        self.records = [Record(req=r, due=t0 + float(o))
                        for r, o in zip(requests, offsets)]
        self._pool = ThreadPoolExecutor(senders,
                                        thread_name_prefix="chipbench-send")
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run,
                                        name="chipbench-open", daemon=True)

    def start(self) -> "OpenLoop":
        self._thread.start()
        return self

    def _run(self) -> None:
        for rec in self.records:
            wait = rec.due - time.perf_counter()
            if wait > 0 and self._stop.wait(wait):
                return
            self._pool.submit(_sender, self.submit, rec, self.annotate)

    def stop(self) -> None:
        """Stop sending; join the scheduler and the senders."""
        self._stop.set()
        self._thread.join()
        self._pool.shutdown(wait=True)


class ClosedLoop:
    """``clients`` threads, each of which sends its next request as soon
    as the answer (or error) to its previous one is recorded, with no
    think time, so a client never has more than one request outstanding.
    Client ``c`` walks requests ``c, c + clients, c + 2 * clients, ...``
    of ``requests``, from ``t0`` on.

    Sending stops at ``t_close``, except that a client that has not yet
    sent its first ``keep`` requests (the check set: the first
    ``clients * keep`` of ``requests``, the same whatever the speed) goes
    on until it has. No client sends at or after ``t_end``; ``stop``
    waits for the clients until then. A client that runs out of requests
    stops, and ``stop`` raises: the mix has too few."""

    def __init__(self, submit: Callable, requests: list[Request],
                 clients: int, keep: int, t0: float, t_close: float,
                 t_end: float, annotate):
        self.submit, self.annotate = submit, annotate
        self.requests, self.clients, self.keep = requests, clients, keep
        self.t0, self.t_close, self.t_end = t0, t_close, t_end
        self._sent: list[list[Record]] = [[] for _ in range(clients)]
        self.ran_out: list[int] = []
        self._threads = [threading.Thread(target=self._client, args=(c,),
                                          name=f"chipbench-client-{c}",
                                          daemon=True)
                         for c in range(clients)]

    def start(self) -> "ClosedLoop":
        for t in self._threads:
            t.start()
        return self

    def _client(self, c: int) -> None:
        sent, i = self._sent[c], c
        time.sleep(max(0.0, self.t0 - time.perf_counter()))
        while True:
            now = time.perf_counter()
            if now >= self.t_end or (now >= self.t_close
                                     and len(sent) >= self.keep):
                return
            if i >= len(self.requests):
                self.ran_out.append(c)
                return
            rec = Record(req=self.requests[i], due=now)
            sent.append(rec)
            _sender(self.submit, rec, self.annotate)
            if not rec.settled.wait(max(0.0, self.t_end
                                        - time.perf_counter())):
                return
            i += self.clients

    def stop(self) -> None:
        """Wait for the clients until ``t_end``; record every request of
        the check set that was never sent as failed, so that it counts as
        missing. Raises RuntimeError where a client ran out of
        requests."""
        for t in self._threads:
            t.join(max(0.0, self.t_end - time.perf_counter()) + 1.0)
        for c, sent in enumerate(self._sent):
            for i in range(c + len(sent) * self.clients,
                           self.clients * self.keep, self.clients):
                sent.append(Record(req=self.requests[i], due=self.t_end,
                                   error="not sent by the drain's end"))
        done = sorted(self.done_by(self.t_close))
        print(f"closed loop: answers per client before the close: min "
              f"{done[0]}, median {done[len(done) // 2]}, max {done[-1]}; "
              f"check set {self.clients} x {self.keep}", file=sys.stderr,
              flush=True)
        if self.ran_out:
            raise RuntimeError(
                f"closed loop: clients {sorted(self.ran_out)} ran out of "
                f"the mix's {len(self.requests)} requests; raise its "
                f"requests_per_client")

    @property
    def records(self) -> list[Record]:
        return [rec for sent in self._sent for rec in sent]

    def check_set(self) -> list[Record]:
        """The records of every client's first ``keep`` requests."""
        return [rec for sent in self._sent for rec in sent[:self.keep]]

    def done_by(self, t: float) -> list[int]:
        """How many answers each client had received before ``t``."""
        return [sum(1 for rec in sent if rec.t_done < t)
                for sent in self._sent]


def wait_all(records: list[Record], deadline: float) -> None:
    """Wait until every sent request is answered or ``deadline``."""
    for rec in records:
        if rec.fut is None:
            continue
        left = deadline - time.perf_counter()
        if left <= 0 or not rec.settled.wait(left):
            return
