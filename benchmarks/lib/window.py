"""The measured window: one loop where the engine's epoch loop stands,
writers and query clients that wait on its acknowledgements.

The loop is one thread, because the index donates its buffers. A turn
does what ``ExternalIndexNode.process`` does in an epoch: at most one
pending write batch (retract the old keys, embed, add, wait for the
scatter, acknowledge), then every queued query up to the cap in one
``search_batch``. The window closes at the end of the turn in which
``seconds`` ran out, and every rate is over that whole span. Requests
handed over before the close and finished after it are drained, and
their latencies belong to the tails.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time

import numpy as np


@dataclasses.dataclass
class Query:
    text: str
    doc: int
    sent: float
    client: int
    done: float = 0.0
    version: int = -1
    answer: list | None = None
    ready: threading.Event = dataclasses.field(default_factory=threading.Event)


@dataclasses.dataclass
class WriteBatch:
    index: int
    keys: list
    texts: list
    handed: float
    tokens: np.ndarray  # token length of each document, as the generator made it
    visible: float = 0.0
    acked: threading.Event = dataclasses.field(default_factory=threading.Event)


class Spans:
    """The benchmark's own spans around its calls into the program, kept
    in memory. With ``annotate`` they are also written into the
    profiler's trace, on its clock."""

    def __init__(self, annotate: bool = False):
        self.rows: list[tuple[str, float, float]] = []
        self.annotate = annotate

    @contextlib.contextmanager
    def span(self, name: str):
        ctx = contextlib.nullcontext()
        if self.annotate:
            import jax

            ctx = jax.profiler.TraceAnnotation("bench." + name)
        t0 = time.perf_counter()
        with ctx:
            yield
        self.rows.append((name, t0, time.perf_counter()))


class Window:
    def __init__(self, system, traffic, pool_tokens, mix: dict, seed: int, spans: Spans, first_batch: int):
        """``pool_tokens``: token length of each pool document, as the
        configuration's family counts it."""
        self.system, self.traffic, self.mix, self.seed, self.spans = system, traffic, mix, seed, spans
        self.pool_tokens = pool_tokens
        self.cap = int(mix["queries"]["cap"])
        self.lock = threading.Condition()
        self.queue: collections.deque[Query] = collections.deque()
        self.pending: WriteBatch | None = None
        self.version = first_batch  # write batches applied so far
        self.handed = first_batch  # write batches handed over so far
        self.stop = threading.Event()
        self.queries: list[Query] = []  # every query sent, by the loop's order of answering
        self.writes: list[WriteBatch] = []
        self.skipped_ticks = 0
        self.dispatch_sizes: collections.Counter = collections.Counter()
        self.failures: list[str] = []

    # -- the loop ---------------------------------------------------------------

    def turn(self) -> bool:
        """One epoch. False where there was nothing to do."""
        with self.lock:
            batch, self.pending = self.pending, None
            todo = [self.queue.popleft() for _ in range(min(self.cap, len(self.queue)))]
        if batch is not None:
            with self.spans.span("write.remove"):
                self.system.remove(batch.keys)
            with self.spans.span("write.embed_add"):
                self.system.embed_and_add(batch.keys, batch.texts)
            with self.spans.span("write.scatter_wait"):
                self.system.block_until_visible()
            batch.visible = time.perf_counter()
            self.version += 1
            self.writes.append(batch)
            batch.acked.set()
        if todo:
            with self.spans.span("query.search"):
                answers = self.system.search([q.text for q in todo])
            now = time.perf_counter()
            self.dispatch_sizes[len(todo)] += 1
            with self.spans.span("query.answer"):
                for q, answer in zip(todo, answers):
                    q.answer, q.version, q.done = answer, self.version, now
                    self.queries.append(q)
                    q.ready.set()
        return batch is not None or bool(todo)

    def run(self, seconds: float) -> tuple[float, float]:
        """Start the clients, loop for ``seconds``, close at a turn's end,
        drain. -> (start, close) on the ``perf_counter`` clock."""
        threads = [threading.Thread(target=self._writer, daemon=True)]
        threads += [
            threading.Thread(target=self._client, args=(c,), daemon=True)
            for c in range(int(self.mix["queries"]["clients"]))
        ]
        start = time.perf_counter()
        self.t0 = start
        for t in threads:
            t.start()
        while time.perf_counter() - start < seconds:
            if not self.turn():
                with self.spans.span("wait.client"), self.lock:
                    if not self.queue and self.pending is None:
                        self.lock.wait(0.05)
        close = time.perf_counter()
        self.stop.set()
        # what was handed over before the close is finished after it
        deadline = close + 60.0
        while any(t.is_alive() for t in threads) and time.perf_counter() < deadline:
            if not self.turn():
                time.sleep(0.002)
        for t in threads:
            t.join(timeout=1.0)
            if t.is_alive():
                self.failures.append("a client thread did not end")
        return start, close

    # -- the clients -----------------------------------------------------------

    def _writer(self) -> None:
        writer = self.mix["writer"]
        tick = writer["tick_ms"] / 1000.0 if writer["mode"] == "ticked" else 0.0
        n, last = 0, None
        try:
            while not self.stop.is_set():
                if tick:
                    n += 1
                    delay = self.t0 + n * tick - time.perf_counter()
                    if delay > 0 and self.stop.wait(delay):
                        break
                    if last is not None and not last.acked.is_set():
                        self.skipped_ticks += 1
                        continue
                elif last is not None:
                    while not last.acked.wait(0.05):
                        pass
                    if self.stop.is_set():
                        break
                keys, docs = self.traffic.write_batch(self.handed)
                last = WriteBatch(
                    index=self.handed,
                    keys=keys,
                    texts=[self.traffic.pool_texts[d] for d in docs],
                    handed=time.perf_counter(),
                    tokens=self.pool_tokens[docs],
                )
                with self.lock:
                    self.pending = last
                    self.handed += 1
                    self.lock.notify()
            if last is not None and not last.acked.wait(90.0):
                self.failures.append(f"write batch {last.index} was never acknowledged")
        except Exception as e:  # a thread's error must fail the run, not vanish
            self.failures.append(f"writer: {e!r}")

    def _client(self, c: int) -> None:
        rng = np.random.default_rng([self.seed, 1000 + c])
        try:
            while not self.stop.is_set():
                text, doc = self.traffic.query(rng, self.handed)
                q = Query(text=text, doc=doc, sent=time.perf_counter(), client=c)
                with self.lock:
                    self.queue.append(q)
                    self.lock.notify()
                if not q.ready.wait(90.0):
                    self.failures.append(f"a query of client {c} was never answered")
                    return
        except Exception as e:
            self.failures.append(f"client {c}: {e!r}")
