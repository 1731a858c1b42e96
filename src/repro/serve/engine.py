"""Synchronous serving facades (thin; the serving *system* lives in the
sibling modules of ``repro.serve``).

``LMDecoder``       — KV-cache decode loop around decode_step (greedy or
                      temperature sampling) with batched requests.
``SeismicServer``   — offline-batch retrieval: pads a whole request
                      batch to a fixed size and chunks it through the
                      jitted pipeline. Kept for back-compat and bulk
                      jobs; online traffic should use
                      ``repro.serve.batcher.AsyncSeismicServer``, which
                      micro-batches in-flight queries instead of
                      padding each call.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import TransformerConfig
from repro.core.types import SeismicIndex
from repro.retrieval import SearchParams, search_pipeline
from repro.models.transformer import lm
from repro.obs.phase import phase
from repro.serve.telemetry import ServerTelemetry
from repro.sparse.ops import PaddedSparse


class LMDecoder:
    def __init__(self, params, cfg: TransformerConfig, batch: int,
                 max_seq: int):
        self.params = params
        self.cfg = cfg
        self.max_seq = max_seq
        self.cache = lm.init_cache(cfg, batch, max_seq)
        self._step = jax.jit(
            lambda p, c, t, pos: lm.decode_step(p, c, t, pos, cfg))

    def generate(self, prompts: np.ndarray, n_steps: int, *,
                 greedy: bool = True, seed: int = 0) -> np.ndarray:
        """prompts [B, P] int32 -> tokens [B, P + n_steps]."""
        b, plen = prompts.shape
        key = jax.random.PRNGKey(seed)
        toks = [prompts[:, i] for i in range(plen)]
        # prefill by stepping (keeps one compiled program)
        logits = None
        for i in range(plen):
            logits, self.cache = self._step(
                self.params, self.cache,
                jnp.asarray(toks[i][:, None], jnp.int32),
                jnp.asarray(i, jnp.int32))
        for j in range(n_steps):
            if greedy:
                nxt = jnp.argmax(logits, axis=-1)
            else:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, logits)
            toks.append(np.asarray(nxt, np.int32))
            logits, self.cache = self._step(
                self.params, self.cache,
                jnp.asarray(nxt[:, None], jnp.int32),
                jnp.asarray(plen + j, jnp.int32))
        return np.stack(toks, axis=1)


@dataclasses.dataclass
class RetrievalResult:
    ids: np.ndarray
    scores: np.ndarray
    docs_evaluated: np.ndarray


class SeismicServer:
    """Fixed-batch jitted retrieval front-end over the shared staged
    pipeline (repro.retrieval): pads request batches to ``max_batch``
    so the jitted pipeline never recompiles."""

    def __init__(self, index: SeismicIndex, params: SearchParams,
                 max_batch: int = 256, *,
                 telemetry: ServerTelemetry | None = None, obs=None,
                 auditor=None):
        from repro.graph.refine import validate_refine_params
        from repro.tune.policy import validate_tuned_index
        validate_refine_params(index, params)   # fail before first launch
        validate_tuned_index(index)             # stale TunedPolicy -> now
        self.index = index
        self.params = params
        self.max_batch = max_batch
        if telemetry is None and obs is not None:
            telemetry = ServerTelemetry(registry=obs.registry)
        self.telemetry = telemetry
        self.obs = obs
        self.auditor = auditor if auditor is not None \
            else getattr(obs, "auditor", None)
        self._fns = None
        need_staged = self.auditor is not None or (
            obs is not None and obs.stage_sample_every > 0)
        if need_staged:
            from repro.retrieval.pipeline import stage_fns
            self._fns = stage_fns(index, params)
        self._launch_seq = 0
        # serving generation; bumped on every swap_index (no result
        # cache here, but callers key their own memoization on it)
        self.epoch = 0
        if self.telemetry is not None:
            self.telemetry.registry.gauge(
                "seismic_index_epoch",
                "Generation of the index being served (bumped on "
                "every swap_index / mutation publish)").labels() \
                .set_fn(lambda: self.epoch)

    def swap_index(self, index: SeismicIndex,
                   params: SearchParams | None = None) -> int:
        """Publish a new index (and optionally new params); returns the
        new serving epoch. The facade is synchronous — callers serialize
        ``search``/``swap_index`` themselves — so the swap is a plain
        field update plus revalidation and staged-fns rebuild."""
        from repro.graph.refine import validate_refine_params
        from repro.tune.policy import validate_tuned_index
        params = self.params if params is None else params
        validate_refine_params(index, params)
        validate_tuned_index(index)
        if self._fns is not None:
            from repro.retrieval.pipeline import stage_fns
            self._fns = stage_fns(index, params)
        self.index = index
        self.params = params
        self.epoch += 1
        return self.epoch

    def apply_mutation(self, mutable, mutate_fn=None) -> int:
        """Optionally run ``mutate_fn(mutable)`` (inserts / deletes /
        compaction on a ``repro.core.mutate.MutableSeismicIndex``),
        then publish its current snapshot via :meth:`swap_index`."""
        if mutate_fn is not None:
            mutate_fn(mutable)
        return self.swap_index(mutable.index)

    def _search_staged(self, chunk: PaddedSparse, n_real: int,
                       audit_rows: tuple[int, ...] = ()):
        """One sampled (or audited) chunk through the staged pipeline:
        emits a ``launch`` trace with per-stage (and per-refine-round)
        child spans and feeds the shadow auditor the planned rows.
        Bit-exact with the fused path."""
        from repro.retrieval.pipeline import run_pipeline_staged
        from repro.serve.batcher import attach_stage_spans
        tracer = self.obs.tracer if self.obs is not None else None
        triples: list[tuple[str, float, float]] = []
        probed: dict[str, object] = {}
        tel = self.telemetry
        t0 = time.monotonic()
        out = run_pipeline_staged(
            self.index, chunk.coords, chunk.vals, self.params,
            fns=self._fns,
            record=(lambda s, dt: tel.record_latency(f"stage_{s}", dt))
            if tel is not None else None,
            span_cb=lambda name, a, b: triples.append((name, a, b)),
            split_refine=True, probe=probed.__setitem__,
            audit=bool(audit_rows))
        t1 = time.monotonic()
        a_span = None
        if audit_rows:
            coords = np.asarray(chunk.coords)
            vals = np.asarray(chunk.vals)
            ids = np.asarray(out[1])
            a0 = time.monotonic()
            for i in audit_rows:
                self.auditor.feed(coords[i], vals[i], ids[i],
                                  captures=probed, row=i)
            a_span = (a0, time.monotonic())
        if tracer is not None:
            tr = tracer.start_trace("launch", t0,
                                    width=chunk.coords.shape[0],
                                    occupancy=n_real, sync=True)
            attach_stage_spans(tracer, tr, tr.root, triples)
            if a_span is not None:
                tracer.add_span(tr, "audit", a_span[0], a_span[1])
            tracer.end_trace(tr, a_span[1] if a_span is not None else t1,
                             status="done")
        return out, t1 - t0

    def search(self, queries: PaddedSparse) -> RetrievalResult:
        """Top-k of every row of ``queries``, in ``max_batch`` chunks.

        Without telemetry the chunks dispatch asynchronously and the
        device is waited on once, in the final conversion; with it each
        chunk is timed to its completion as a ``launch``. Each chunk's
        call is a ``seismic.dispatch`` annotation, the conversion a
        ``seismic.to_host`` one (histograms when telemetry is on)."""
        q = queries
        n = q.coords.shape[0]
        if n == 0:
            return RetrievalResult(
                ids=np.zeros((0, self.params.k), np.int32),
                scores=np.zeros((0, self.params.k), np.float32),
                docs_evaluated=np.zeros((0,), np.int32))
        pad = (-n) % self.max_batch
        if pad:
            coords = jnp.pad(q.coords, ((0, pad), (0, 0)))
            vals = jnp.pad(q.vals, ((0, pad), (0, 0)))
            q = PaddedSparse(coords, vals, q.dim)
        outs = []
        for s in range(0, q.coords.shape[0], self.max_batch):
            chunk = PaddedSparse(q.coords[s:s + self.max_batch],
                                 q.vals[s:s + self.max_batch], q.dim)
            seq = self._launch_seq
            self._launch_seq += 1
            n_chunk = min(self.max_batch, n - s)
            audit_rows = self.auditor.plan(n_chunk) \
                if self.auditor is not None else ()
            sampled = (self.obs is not None
                       and self.obs.sample_stages(seq))
            if self._fns is not None and (sampled or audit_rows):
                out, dt = self._search_staged(chunk, n_chunk,
                                              audit_rows)
                if self.telemetry is not None:
                    self.telemetry.record_latency("launch", dt)
                    self.telemetry.inc("batches")
                    self.telemetry.observe_occupancy(
                        min(self.max_batch, n - s))
                outs.append(out)
                continue
            t0 = time.perf_counter()
            with phase(self.telemetry, "dispatch"):
                out = search_pipeline(self.index, chunk, self.params)
            if self.telemetry is not None:
                out = jax.block_until_ready(out)
                self.telemetry.record_latency(
                    "launch", time.perf_counter() - t0)
                self.telemetry.inc("batches")
                self.telemetry.observe_occupancy(
                    min(self.max_batch, n - s))
            outs.append(out)
        with phase(self.telemetry, "to_host"):
            scores = np.concatenate([np.asarray(o[0]) for o in outs])[:n]
            ids = np.concatenate([np.asarray(o[1]) for o in outs])[:n]
            ev = np.concatenate([np.asarray(o[2]) for o in outs])[:n]
        if self.telemetry is not None:
            self.telemetry.inc("requests", n)
            self.telemetry.inc("served", n)
        return RetrievalResult(ids=ids, scores=scores, docs_evaluated=ev)
