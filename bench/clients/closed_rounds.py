"""A closed loop of one stream per template, in rounds: a step submits one
read of every template in one burst and waits until every answer is held;
the next round starts when the last answer of this one is held."""

from __future__ import annotations

import sys
import time

from bench import reduce, traffic
from bench.harness import ReadRecord, leaves, nbytes


class Client:
    def __init__(self, server, table, build, words, mix, wait_s: float):
        self.server, self.table, self.build = server, table, build
        self.words = words  # template name -> (probe words, build words)
        self.mix = mix
        self.wait_s = wait_s
        self.rounds = None
        self.lateness: list[float] = []  # last answer -> next submit, s
        self.durations: list[float] = []  # first submit -> last answer, s
        self.last_ready: float | None = None

    def warm_up(self, seed: int) -> None:
        self._round(next(self.mix.rounds(seed)), None)

    def start(self, seed: int) -> None:
        self.rounds = self.mix.rounds(seed)
        self.lateness.clear()
        self.durations.clear()
        self.last_ready = None

    def step(self, keep):
        reads = next(self.rounds)
        if self.last_ready is not None:
            self.lateness.append(time.perf_counter() - self.last_ready)
        recs, kept = self._round(reads, keep)
        ready = [r.t_ready for r in recs if r.t_ready is not None]
        self.last_ready = max(ready, default=None)
        if len(ready) == len(recs):
            self.durations.append(self.last_ready - recs[0].t_submit)
        return recs, kept

    def stop(self) -> None:
        pass  # a round has ended when step returns: nothing is in flight

    def report(self) -> list[str]:
        out = []
        if self.lateness:
            out.append(
                "client lateness (last answer to next submit): median "
                f"{reduce.percentile(self.lateness, 50) * 1e3} ms, max "
                f"{max(self.lateness) * 1e3} ms over {len(self.lateness)} "
                "rounds")
        if self.durations:
            out.append(
                f"round seconds: median "
                f"{reduce.percentile(self.durations, 50)}, min "
                f"{min(self.durations)}, max {max(self.durations)}")
        return out

    def _round(self, reads, keep):
        """Serve one round; returns its records and the answers of the
        reads ``keep`` accepts."""
        import jax

        plans = [traffic.build_plan(r, self.table, self.build).build()
                 for r in reads]
        recs, tickets = [], []
        with jax.profiler.TraceAnnotation("bench.submit"):
            for read, p in zip(reads, plans):
                recs.append(ReadRecord(read, time.perf_counter()))
                tickets.append(self.server.submit(p, client=read.name))
        kept = {}
        with jax.profiler.TraceAnnotation("bench.wait"):
            for rec, ticket in zip(recs, tickets):
                try:
                    result = ticket.result(timeout=self.wait_s)
                    parts = leaves(result)
                    jax.block_until_ready(parts)
                except Exception as e:  # a failed read counts, never stops
                    rec.failed = True
                    print(f"read {rec.read.name} failed: {e!r}",
                          file=sys.stderr, flush=True)
                    continue
                rec.t_ready = time.perf_counter()
                rec.admitted_at = ticket.admitted_at
                rec.queue_wait_s = ticket.queue_wait_s
                rec.route = ticket.route
                rec.result_bytes = nbytes(parts)
                rec.probe_words, rec.build_words = self.words[rec.read.name]
                if keep is not None and keep(rec.read.name):
                    kept[rec.read.name] = (rec.read, result)
        return recs, kept
