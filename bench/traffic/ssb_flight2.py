"""Traffic kind ``ssb_flight2``: SSB flight 2 queries over fixed tables.

Set-up makes the four tables on the host at the configuration's sizes
with the SSB spec's distributions (only the columns flight 2 reads).  As
dbgen's output is fixed for a scale factor, the data set is fixed by the
configuration's ``data_seed``; a row order permutes the rows of every
table, so every order serves the same sizes and a checkout compiles each
program once.  Set-up runs each query of the rotation once, ``clients``
at a time as the window does, over the rows in the order drawn from
(``WARMUP_SEED``, stream 0); the window's tables are the rows in the
order drawn from (``--seed``, stream 1), so no fingerprint or filtered
dimension the window builds was seen in warm-up.
Query ``i`` of the window is ``rotation[i % len(rotation)]``, built fresh
as the program's ``Query`` (its own filtered tables, as a new SQL query
would be) and run by ``PipelineExecutor.run_optimized``; one executor per
client over the one service.  A query is timed until its grouped rows
are on the host.

After the window every answer is compared with the configuration's NumPy
reference of its query: each group's keys and int64 sum, and the number
of joined rows the last join stage produced.
"""
from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

GROUP_BY = ("date.d_year", "part.p_brand1")
AGGREGATE = ("sum", "lineorder.lo_revenue")
SUM_COLUMN = "~sum(lineorder.lo_revenue)"
# The control is the program itself with its int32 sum path switched on
# (``ControlCell``), one precision below the configuration's int64.
CONTROL = "program"
# Exact answers: every group with its exact sum, every joined row.
LIMITS = {"groups_wrong": 0, "joined_rows_gap": 0}
WARMUP, WINDOW = 0, 1
WARMUP_SEED = 20090601


def retail_price_cents(partkey: np.ndarray) -> np.ndarray:
    """p_retailprice in cents (TPC-H / SSB dbgen formula)."""
    pk = partkey.astype(np.int64)
    return 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)


def generate_tables(config: dict, seed: int, stream: int = WINDOW) -> dict:
    """``{table: {column: int32 array}}``: the configuration's data set
    (from its ``data_seed``) with each table's rows in an order drawn from
    (``seed``, ``stream``)."""
    return permute(_data_set(config), seed, stream)


def permute(data: dict, seed: int, stream: int) -> dict:
    """A copy of ``data`` with each table's rows in an order drawn from
    (``seed``, ``stream``)."""
    rng = np.random.default_rng([seed % (1 << 64), stream])
    out = {}
    for name, cols in data.items():
        order = rng.permutation(next(iter(cols.values())).shape[0])
        out[name] = {c: v[order] for c, v in cols.items()}
    return out


def _data_set(config: dict) -> dict:
    rng = np.random.default_rng([int(config["data_seed"]), 0])
    n = config["tables"]
    days = np.datetime64("1992-01-01") + np.arange(n["date"])
    year = days.astype("datetime64[Y]").astype(np.int64) + 1970
    month = days.astype("datetime64[M]").astype(np.int64) % 12 + 1
    dom = (days - days.astype("datetime64[M]")).astype(np.int64) + 1
    datekey = (year * 10000 + month * 100 + dom).astype(np.int32)
    order_days = int((np.datetime64("1998-08-02")
                      - np.datetime64("1992-01-01")).astype(np.int64)) + 1
    order_days = min(order_days, n["date"])

    p = n["part"]
    category = (rng.integers(1, 6, p) - 1) * 5 + (rng.integers(1, 6, p) - 1)
    brand = category * 40 + (rng.integers(1, 41, p) - 1)
    s = n["supplier"]
    lines = n["lineorder"]
    lo_partkey = rng.integers(1, p + 1, lines, dtype=np.int32)
    qty = rng.integers(1, 51, lines, dtype=np.int32).astype(np.int64)
    disc = rng.integers(0, 11, lines, dtype=np.int32).astype(np.int64)
    revenue = qty * retail_price_cents(lo_partkey) * (100 - disc) // 100
    del qty, disc
    return {
        "lineorder": {
            "lo_orderdate": datekey[rng.integers(0, order_days, lines)],
            "lo_partkey": lo_partkey,
            "lo_suppkey": rng.integers(1, s + 1, lines, dtype=np.int32),
            "lo_revenue": revenue.astype(np.int32)},
        "part": {"p_partkey": np.arange(1, p + 1, dtype=np.int32),
                 "p_category": category.astype(np.int32),
                 "p_brand1": brand.astype(np.int32)},
        "supplier": {"s_suppkey": np.arange(1, s + 1, dtype=np.int32),
                     "s_region": rng.integers(0, 5, s, dtype=np.int32)},
        "date": {"d_datekey": datekey, "d_year": year.astype(np.int32)},
    }


def make_query(tables: dict, template: dict, *, wrap32: bool = False):
    """The program's ``Query`` for one flight-2 template."""
    from repro.queries import Filter, Join, Query, Table

    def table(name, pred=None):
        filters = () if pred is None else (Filter(pred[0], pred[1], pred[2]),)
        return Table(name, tables[name], filters)

    return Query(
        tables={"lineorder": table("lineorder"),
                "part": table("part", template["part"]),
                "supplier": table("supplier", template["supplier"]),
                "date": table("date")},
        joins=(Join("lineorder", "lo_partkey", "part", "p_partkey"),
               Join("lineorder", "lo_suppkey", "supplier", "s_suppkey"),
               Join("lineorder", "lo_orderdate", "date", "d_datekey")),
        aggregate=AGGREGATE, group_by=GROUP_BY, wrap32=wrap32)


def answer_rows(columns: dict) -> np.ndarray:
    """(d_year, p_brand1, sum) rows of a grouped answer, int64."""
    return np.stack([np.asarray(columns[c]).astype(np.int64)
                     for c in (*GROUP_BY, SUM_COLUMN)], axis=1)


class Cell:
    wrap32 = False            # the program's own int32 path (the control)

    def __init__(self, ctx):
        self.ctx = ctx
        self.rotation = list(ctx.params["rotation"])
        self.clients = int(ctx.params["clients"])
        self.service = None
        self._local = threading.local()

    def setup(self) -> None:
        data = _data_set(self.ctx.config)
        self.tables = permute(data, WARMUP_SEED, WARMUP)
        self.service = self.ctx.service()
        # Warm-up: one round of the rotation, run as the window runs it
        # (``clients`` at once), so the plans it compiles are the window's.
        with ThreadPoolExecutor(self.clients) as pool:
            list(pool.map(lambda k: self.execute(self.prepare(k)),
                          range(len(self.rotation))))
        self.tables = None
        self.tables = permute(data, self.ctx.seed, WINDOW)

    def _executor(self):
        from repro.queries import PipelineExecutor

        ex = getattr(self._local, "ex", None)
        if ex is None or ex.service is not self.service:
            ex = self._local.ex = PipelineExecutor(service=self.service)
        return ex

    def prepare(self, index: int):
        k = index % len(self.rotation)
        return k, make_query(self.tables, self.rotation[k],
                             wrap32=self.wrap32)

    def execute(self, item):
        import jax

        k, query = item
        with jax.profiler.TraceAnnotation("bench.run_optimized"):
            physical, res = self._executor().run_optimized(query)
        with jax.profiler.TraceAnnotation("bench.fetch"):
            rows = answer_rows(res.columns)
        last = res.outcomes[len(physical.stages) - 1]
        return {"template": k, "rows": rows,
                "joined": int(last.result.count), "physical": physical,
                "outcomes": list(res.outcomes)}

    @staticmethod
    def reference_answers(value) -> int:
        """Outcomes of this query the NumPy reference path answered."""
        return sum(bool(o.timing.notes.get("reference_path"))
                   for o in value["outcomes"])

    def plans(self, value) -> list[str]:
        name = self.rotation[value["template"]]["name"]
        stages = value["physical"].stages
        out = []
        for i, o in enumerate(value["outcomes"]):
            what = str(stages[i].join) if i < len(stages) else "group-by"
            p = o.plan
            sched = (f" schedule={list(p.schedule)}" if p.schedule else "")
            out.append(f"{name} {what}: {p.algorithm}/{p.scheme}{sched}")
        return out

    @staticmethod
    def layer(value) -> dict:
        return {"coprocess_s": float(sum(sum(o.timing.phase_s.values())
                                         for o in value["outcomes"]))}

    def release(self) -> None:
        self.close()

    def check(self, done) -> list[tuple[str, int, int]]:
        ref = self.ctx.reference
        found = ref.lookups(self.tables)
        want = {}
        wrong = gap = 0
        for r in done:
            k = r.value["template"]
            if k not in want:
                want[k] = ref.flight2(self.tables, self.rotation[k],
                                      found=found)
            rows, joined = want[k]
            wrong += ref.compare(r.value["rows"], rows)
            gap += abs(r.value["joined"] - joined)
        print(f"checked {len(done)} answers against the reference of "
              f"{len(want)} queries", flush=True)
        return [("groups_wrong", wrong, LIMITS["groups_wrong"]),
                ("joined_rows_gap", gap, LIMITS["joined_rows_gap"])]

    def close(self) -> None:
        if self.service is not None:
            self.service.close()
            self.service = None


class ControlCell(Cell):
    """The cell with the program's ``wrap32`` (int32 sums) path on."""

    wrap32 = True
