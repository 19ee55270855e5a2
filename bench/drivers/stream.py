"""Stream traffic: one instance streamed through
``repro.api.iter_edge_chunks`` into the benchmark's on-device consumer,
restarted from a new plan each time it ends.

Traffic parameters (``bench/traffic/<mix>.json``): ``log_n`` (n =
2^log_n; else the configuration's ``log_n``), ``P`` (virtual PEs), ``chunks`` (the virtual chunk grid, where
the family has one), ``batch`` and ``prefetch`` (the wave stream's);
the configuration's reference reads its own keys (what it samples).
The configuration gives the family (``spec.family``, a class of
``repro.api``), its fixed keyword arguments (``spec.kwargs``) and, for
a family sized by edges, ``edges_per_vertex`` (m = edges_per_vertex *
n).

The reference module's ``StreamCheck`` supplies the consumer (jitted
``bench_*`` programs, given the chunk and its position in the pass,
whose first output is the chunk's [valid edges, self-loops, digest]),
the comparison after the window, and the control source.

End-to-end: ``edges_per_s`` (valid edges the consumer took over the
window's seconds, the window ending when the last consumer result is
ready) and ``first_chunk_s`` (mean over passes begun in the window of
the time from asking for the instance to the consumer's result on its
first chunk).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from ..harness.spans import span


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, reference,
                 control: bool = False, seconds: float = 0.0):
        from repro import api

        self.n = 1 << int(traffic.get("log_n", config.get("log_n")))
        self.P = int(traffic["P"])
        self.batch = int(traffic.get("batch", 1))
        self.prefetch = int(traffic.get("prefetch", 2))
        self.seed = int(seed)
        self.control = control
        args = dict(config["spec"].get("kwargs", {}), n=self.n, seed=self.seed)
        if "edges_per_vertex" in config:
            args["m"] = int(config["edges_per_vertex"]) * self.n
        if "chunks" in traffic:
            args["chunks"] = int(traffic["chunks"])
        self.spec = getattr(api, config["spec"]["family"])(**args)
        self.ref = reference.StreamCheck(args, traffic)
        self._iter = api.iter_edge_chunks
        self._gen = None
        self._pass = 0
        self._index = 0         # position of the next chunk in its pass
        self.complete = set()   # passes streamed to their end
        self.rows = []          # (pass, pe, consumer output) of every chunk
        self.first_chunk = []   # (window id, seconds)
        self.windows = []       # (id, seconds, edges, chunks, slots)
        self._win = 0

    def _source(self):
        if self.control:
            return self.ref.control_source()
        return self._iter(self.spec, self.P, batch=self.batch,
                          prefetch=self.prefetch)

    # ------------------------------------------------------------ phases

    def setup(self) -> None:
        """Plan and compile the instance once and consume one wave."""
        with span("bench/setup"):
            gen = self._source()
            c = next(gen)
            jax.block_until_ready(self.ref.consume(c.buffer, c.mask, 0))
            gen.close()

    def window(self, seconds: float, fresh: bool = False) -> None:
        """Stream for ``seconds``; ``fresh`` starts a new pass first."""
        if fresh and self._gen is not None:
            self._gen.close()
            self._gen = None
        win, self._win = self._win, self._win + 1
        mine, slots = [], 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        with span("bench/window"):
            while True:
                first = self._gen is None
                if first:
                    t_ask = time.perf_counter()
                    self._pass += 1
                    self._gen = self._source()
                    self._index = 0
                with span("bench/next_chunk"):
                    c = next(self._gen, None)
                if c is None:
                    self.complete.add(self._pass)
                    self._gen = None
                    continue
                with span("bench/consume"):
                    out = self.ref.consume(c.buffer, c.mask, self._index)
                self._index += 1
                mine.append(out[0])
                slots += int(np.prod(c.mask.shape))
                self.rows.append((self._pass, int(c.pe), out))
                if first:
                    with span("bench/first_chunk"):
                        out[0].block_until_ready()
                    self.first_chunk.append((win, time.perf_counter() - t_ask))
                if time.perf_counter() >= deadline:
                    break
            with span("bench/drain"):
                jax.block_until_ready(mine[-1])
        secs = time.perf_counter() - t0
        edges = int(np.stack(jax.device_get(mine))[:, 0].sum())
        self.windows.append((win, secs, edges, len(mine), slots))

    def close(self) -> None:
        if self._gen is not None:
            self._gen.close()
            self._gen = None

    # ------------------------------------------------------------ readings

    def end_to_end(self) -> dict:
        secs = sum(w[1] for w in self.windows)
        edges = sum(w[2] for w in self.windows)
        fc = [s for _, s in self.first_chunk]
        return {"edges_per_s": edges / secs,
                "first_chunk_s": float(np.mean(fc))}

    def counters(self, win: int) -> dict:
        """What the per-layer readers need of window ``win``."""
        w = [x for x in self.windows if x[0] == win][0]
        return {"seconds": w[1], "edges": w[2], "chunks": w[3],
                "waves": w[3], "passes": sum(1 for x, _ in self.first_chunk
                                             if x == win),
                "slots": w[4]}

    def check(self):
        return self.ref.check(self.rows, self.complete)
