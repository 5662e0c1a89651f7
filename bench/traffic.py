"""The one traffic generator: reads a mix file and yields rounds of reads.

A mix (``bench/mixes/<name>.json``) names query templates and the kind of
client that sends them (``clients``: a module of ``bench/clients``).  A round
is one read of every template, in the file's order.  Columns are fixed per
template; what a read draws from the seed is its predicate constant, uniform
in ``[k_low, k_high)``.  Templates with the same static shape compile to the
same programs whatever their constants, so the set of programs a cell runs is
fixed by its mix, and set-up can warm every one of them.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent

# independent random streams drawn from one --seed
STREAM_DATA, STREAM_BUILD, STREAM_TRAFFIC, STREAM_SAMPLE = range(4)


def rng(seed: int, stream: int) -> np.random.Generator:
    """The generator of one stream of ``seed`` (any int, negative too)."""
    return np.random.default_rng([seed & (2**64 - 1), stream])


@dataclasses.dataclass(frozen=True)
class Read:
    """One read a client sends: its template and predicate constant."""

    tpl: dict
    k: int | None

    @property
    def name(self) -> str:
        return self.tpl["name"]


@dataclasses.dataclass
class Mix:
    name: str
    clients: str
    templates: list[dict]
    k_low: int
    k_high: int
    sample_before: float

    @staticmethod
    def load(name: str) -> "Mix":
        spec = json.loads((HERE / "mixes" / f"{name}.json").read_text())
        if not (HERE / "clients" / f"{spec['clients']}.py").is_file():
            raise ValueError(f"mix {name}: no bench/clients/"
                             f"{spec['clients']}.py")
        return Mix(name, spec["clients"], spec["templates"], spec["k_low"],
                   spec["k_high"], spec["sample_before"])

    def rounds(self, seed: int):
        """Endless rounds of reads, the same sequence for the same seed."""
        g = rng(seed, STREAM_TRAFFIC)
        while True:
            ks = g.integers(self.k_low, self.k_high, len(self.templates))
            yield [Read(t, int(k) if "pred" in t else None)
                   for t, k in zip(self.templates, ks)]

    def sample_points(self, seed: int) -> dict[str, float]:
        """Per template, the share of the window after which its next read
        is kept for the correctness check (drawn from the seed)."""
        g = rng(seed, STREAM_SAMPLE)
        return {t["name"]: float(g.uniform(0.0, self.sample_before))
                for t in self.templates}


def words_referenced(tpl: dict, probe_word: dict,
                     build_word: dict) -> tuple[set, set]:
    """Row words a read of ``tpl`` references, as word indices of the probe
    and the build table's rows (``*_word`` maps a column to its word)."""
    kind = tpl["kind"]
    cols = set(tpl.get("columns", ()))
    if "pred" in tpl:
        cols.add(tpl["pred"][0])
    build: set = set()
    if kind in ("sum", "groupby_avg"):
        cols.add(tpl["agg"])
    if kind == "groupby_avg":
        cols.add(tpl["group"])
    if kind == "join":
        cols.update((tpl["key"], tpl["left"]))
        build = {build_word[tpl["key"]], build_word[tpl["right"]]}
    return {probe_word[c] for c in cols}, build


def build_plan(read: Read, table, build_table):
    """The read as a logical plan of the engine under test."""
    from repro.core import plan

    tpl, k = read.tpl, read.k
    q = plan(table)
    if "pred" in tpl:
        col, op = tpl["pred"]
        q = q.filter(col, op, k)
    kind = tpl["kind"]
    if kind == "sum":
        return q.sum(tpl["agg"])
    if kind in ("project", "filter"):
        return q.project(*tpl["columns"])
    if kind == "groupby_avg":
        return q.groupby(tpl["group"], tpl["agg"], "avg", tpl["groups"])
    if kind == "join":
        return q.join(build_table, key=tpl["key"], left_proj=tpl["left"],
                      right_proj=tpl["right"])
    raise ValueError(f"unknown template kind {kind!r}")
