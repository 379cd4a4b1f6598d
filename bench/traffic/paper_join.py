"""Traffic kind ``paper_join``: cold binary equi-joins of fresh relations.

Every query joins a new build relation R and probe relation S of the
configuration's sizes, keys uniform in ``[0, key_range)`` and ``rid`` the
row position.  They are made on the device by one jitted generator from
(seed, stream, query index) before the query is submitted, outside its
latency.  The window draws from (``--seed``, stream 1).  Warm-up draws
from (``WARMUP_SEED``, stream 0), the same relations in every run, so the
shapes it compiles (some of them fixed by the data the program sees) are
in the persistent cache after a checkout's first run, and the window's
relations are never run before it.  Each query is submitted to
``JoinQueryService`` and timed until its result arrays are ready.

After the window every query's result is compared pair for pair with
the configuration's NumPy reference over the same relations, made again
from the same seeds.
"""
from __future__ import annotations

from functools import partial

import numpy as np

WARMUP, WINDOW = 0, 1
WARMUP_SEED = 20130601
# The control takes the program's place with the configuration's
# reference, its key compare cut to the low bits (see ``control_checks``).
CONTROL = "reference"
# Exact answers: no pair missing, no pair extra.
LIMITS = {"pairs_missing": 0, "pairs_extra": 0}


def seed_words(seed: int, stream: int) -> np.ndarray:
    """Two uint32 words of key data for (seed, stream)."""
    return np.random.SeedSequence([seed % (1 << 64), stream]).generate_state(
        2, dtype=np.uint32)


def generate(key_data, index, *, n_build: int, n_probe: int,
             key_range: int):
    """(build key, probe key) for one query, on the device."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.wrap_key_data(key_data), index)
    kb, kp = jax.random.split(key)
    return (jax.random.randint(kb, (n_build,), 0, key_range, jnp.int32),
            jax.random.randint(kp, (n_probe,), 0, key_range, jnp.int32))


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        rel = ctx.config["relations"]
        p = ctx.params
        self.n_build = int(rel["build_tuples"])
        self.n_probe = int(rel["probe_tuples"])
        self.key_range = int(rel["key_range"])
        self.max_out = int(p["max_out"])
        self.clients = int(p["clients"])
        self.warmup = int(p["warmup_queries"])
        self.service = None
        self._kept: dict = {}

    # -- set-up -----------------------------------------------------------
    def setup(self) -> None:
        import jax

        self._jax = jax
        dev = self.ctx.devices[0]
        self._gen = jax.jit(partial(generate, n_build=self.n_build,
                                    n_probe=self.n_probe,
                                    key_range=self.key_range),
                            out_shardings=jax.sharding.SingleDeviceSharding(
                                dev))
        self._words = {
            WARMUP: jax.device_put(seed_words(WARMUP_SEED, WARMUP), dev),
            WINDOW: jax.device_put(seed_words(self.ctx.seed, WINDOW), dev)}
        self.service = self.ctx.service()
        for j in range(self.warmup):
            self.execute(self._make(WARMUP, j))
        self._kept.clear()

    def _relations(self, stream: int, index: int):
        from repro.core import Relation

        jnp = self._jax.numpy
        bk, pk = self._gen(self._words[stream], np.uint32(index))
        build = Relation(jnp.arange(self.n_build, dtype=jnp.int32), bk)
        probe = Relation(jnp.arange(self.n_probe, dtype=jnp.int32), pk)
        return build, probe

    def _make(self, stream: int, index: int):
        from repro.engine import JoinQuery

        build, probe = self._relations(stream, index)
        self._jax.block_until_ready((build, probe))
        return index, JoinQuery(build=build, probe=probe, tag="paper_join",
                                max_out=self.max_out, query_id=index)

    # -- the window -------------------------------------------------------
    def prepare(self, index: int):
        return self._make(WINDOW, index)

    def execute(self, item):
        index, q = item
        prof = self._jax.profiler
        with prof.TraceAnnotation("bench.submit"):
            wait = self.service.submit(q)
        with prof.TraceAnnotation("bench.wait"):
            outcome = wait()
        with prof.TraceAnnotation("bench.fetch"):
            self._jax.block_until_ready(outcome.result)
        self._kept[index] = outcome.result
        return index, outcome

    # -- what the harness reads -------------------------------------------
    @staticmethod
    def reference_answers(value) -> int:
        """Outcomes of this query the NumPy reference path answered."""
        return int(bool(value[1].timing.notes.get("reference_path")))

    @staticmethod
    def plans(value) -> list[str]:
        p = value[1].plan
        return [f"{p.algorithm}/{p.scheme} schedule="
                f"{list(p.schedule or [])} ratios=(partition "
                f"{p.partition_ratio:.3f}, join {p.join_ratio:.3f})"]

    @staticmethod
    def layer(value) -> dict:
        return {"coprocess_s": float(sum(value[1].timing.phase_s.values()))}

    def release(self) -> None:
        """Pull the answers to the host and free the program's state
        before the reference runs."""
        host = {}
        for i, res in self._kept.items():
            c = int(res.count)
            host[i] = np.sort(self.ctx.reference.encode(
                np.asarray(res.probe_rid[:c]), np.asarray(res.build_rid[:c])))
        self._kept = host
        self.close()

    def check(self, done) -> list[tuple[str, int, int]]:
        ref = self.ctx.reference
        missing = extra = 0
        for r in done:
            index = r.value[0]
            build, probe = self._relations(WINDOW, index)
            want = ref.join_codes(np.asarray(build.key), np.asarray(build.rid),
                                  np.asarray(probe.key), np.asarray(probe.rid))
            m, e = ref.compare(self._kept[index], want)
            missing, extra = missing + m, extra + e
        print(f"checked {len(done)} answers pair for pair", flush=True)
        return [("pairs_missing", missing, LIMITS["pairs_missing"]),
                ("pairs_extra", extra, LIMITS["pairs_extra"])]

    def close(self) -> None:
        if self.service is not None:
            self.service.close()
            self.service = None


def control_checks(ctx, index: int = 0) -> list[tuple[str, int]]:
    """The control at the cell's size: window query ``index`` of this
    seed answered by the reference with its key compare cut short, in the
    program's place, compared as a run compares."""
    cell = Cell(ctx)
    import jax

    cell._jax = jax
    dev = ctx.devices[0]
    cell._gen = jax.jit(partial(generate, n_build=cell.n_build,
                                n_probe=cell.n_probe,
                                key_range=cell.key_range))
    cell._words = {WINDOW: jax.device_put(seed_words(ctx.seed, WINDOW), dev)}
    build, probe = cell._relations(WINDOW, index)
    args = (np.asarray(build.key), np.asarray(build.rid),
            np.asarray(probe.key), np.asarray(probe.rid))
    ref = ctx.reference
    # One key bit below the key range: every key shares its compare with
    # one other key.
    key_bits = (cell.key_range - 1).bit_length() - 1
    missing, extra = ref.compare(ref.control_codes(*args, key_bits=key_bits),
                                 ref.join_codes(*args))
    return [("pairs_missing", missing), ("pairs_extra", extra)]
