"""PredictionEngine — chunked, jitted GP prediction from a PosteriorArtifact.

The paper's serving claim (Table 2: sub-second predictions at n > 10^6 once
the caches exist) operationalized: restore an artifact onto ANY registered
KernelOperator backend (dense / partitioned / pallas / sharded extensions)
and serve `predict(Xstar)` with

  * a FIXED chunk size over the test set — every device launch sees the same
    (chunk_size, d) shape, so there is exactly one jit compilation no matter
    how request sizes vary (`repro.core.partitioned.map_row_chunks` pads the
    tail chunk);
  * streaming memory — one chunk's (chunk, r) cross-products are live at a
    time; the (n*, n) kernel block is never materialized, so 10^5-point test
    batches stream against million-point train sets;
  * optional bf16 cross-MVMs — `compute_dtype="bfloat16"` re-binds the
    operator with the mixed fast path (bf16 operands, fp32 MXU accumulation;
    see EXPERIMENTS.md §Mixed precision). Cache state stays fp32 regardless.

Throughput for many small concurrent requests comes from the companion
micro-batcher (`repro.serve.batching.MicroBatcher`), which rides this same
predict path.
"""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.operators import make_operator
from repro.core.partitioned import map_row_chunks
from repro.core.predcache import predict_mean, predict_var_cached
from repro.sparse import morton_order

from .artifact import PosteriorArtifact, load_artifact

_KEEP = "__keep__"  # sentinel: inherit the artifact's compute_dtype


class PredictionEngine:
    """Serves mean + variance predictions from a restored artifact.

    Args:
      artifact: a PosteriorArtifact (in-process or `load_artifact`-restored).
      backend: KernelOperator registry key override; None = the backend the
        artifact was fit under. Restore is backend-agnostic because caches
        are plain arrays — only the cross-MVMs re-bind.
      compute_dtype: override for the operator's matmul dtype ("bfloat16"
        for the MXU fast path, None for the exact path); default inherits
        the artifact's policy.
      chunk_size: fixed test-set chunk (rows per launch). Prefer a multiple
        of 128 to keep MXU-aligned tiles on the Pallas backend.
      include_noise: add sigma^2 to returned variances (predictive vs latent).
      sort_queries: Morton-sort each request batch before chunking (results
        come back in request order). Defaults on for a compactly-supported
        blocksparse backend, where it makes chunks spatially local so the
        operator's runtime cross-covariance tile pruning actually bites;
        off otherwise (sorting is pure overhead for dense backends).
    """

    def __init__(self, artifact: PosteriorArtifact, *,
                 backend: str | None = None,
                 compute_dtype: str | None = _KEEP,
                 chunk_size: int = 1024,
                 include_noise: bool = True,
                 sort_queries: bool | None = None):
        config = artifact.config._replace(geom=None)
        if backend is not None:
            config = config._replace(backend=backend)
        if compute_dtype is not _KEEP:
            config = config._replace(compute_dtype=compute_dtype)
        self.artifact = artifact
        self.config = config
        self.chunk_size = int(chunk_size)
        self.include_noise = include_noise
        self.op = make_operator(config, artifact.X, artifact.params)
        self._cache = artifact.cache()
        if sort_queries is None:
            plan = getattr(self.op, "plan", None)
            sort_queries = plan is not None and plan.compact
        self.sort_queries = bool(sort_queries)
        # launch counters (exported by the latency benchmark / CLI). The
        # continuous scheduler drives one engine from several worker
        # threads, and a bare `+=` is a read-modify-write that drops
        # increments under contention — updates go through _count().
        self.chunks_run = 0
        self.rows_served = 0
        self._counter_lock = threading.Lock()

        # the artifact's arrays enter the compiled chunk program as
        # arguments, not closure constants: as constants XLA folds the
        # hyperparameter transforms on the host (the served values then
        # drift ulps away from the same math run on the device) and
        # compiles the whole training set and Lanczos cache into the program
        op_config = self.op.config  # carries the plan a blocksparse op built

        def _chunk(X, params, cache, Xc):
            op = make_operator(op_config, X, params)
            mean = predict_mean(op, Xc, cache)
            var = predict_var_cached(op, Xc, cache,
                                     include_noise=include_noise)
            return mean, var

        chunk_fn = jax.jit(_chunk)
        self._predict_chunk = lambda Xc: chunk_fn(
            self.op.X, self.op.params, self._cache, Xc)

    @classmethod
    def from_dir(cls, directory: str, **kwargs) -> "PredictionEngine":
        return cls(load_artifact(directory), **kwargs)

    @property
    def backend(self) -> str:
        return self.config.backend

    def _count(self, chunks: int, rows: int) -> None:
        with self._counter_lock:
            self.chunks_run += chunks
            self.rows_served += rows

    def warmup(self) -> None:
        """Compile the chunk program before traffic arrives (one launch)."""
        d = self.artifact.X.shape[1]
        dummy = jnp.zeros((self.chunk_size, d), self.op.dtype)
        jax.block_until_ready(self._predict_chunk(dummy))

    def predict(self, Xstar) -> tuple[jax.Array, jax.Array]:
        """(mean, var) for (m, d) query points; any m, one compiled shape."""
        t0 = time.perf_counter()
        with obs.span("serve_predict"):
            Xstar = jnp.asarray(Xstar, self.op.dtype)
            if Xstar.ndim == 1:
                Xstar = Xstar[None, :]
            m = Xstar.shape[0]
            inv = None
            if self.sort_queries and m > 1:
                # spatially local chunks let the blocksparse operator skip
                # cross-covariance tiles; results return in request order.
                # The inverse permutation is a device-side scatter — no
                # numpy rebuild or host round-trip on the hot path.
                order = jnp.asarray(morton_order(np.asarray(Xstar)))
                inv = jnp.zeros((m,), order.dtype).at[order].set(
                    jnp.arange(m, dtype=order.dtype))
                Xstar = Xstar[order]
            out = map_row_chunks(self._predict_chunk, Xstar, self.chunk_size)
            if inv is not None:
                out = jax.tree.map(lambda a: a[inv], out)
            if obs.tracing_enabled():
                jax.block_until_ready(out)
        self._count(-(-max(m, 1) // self.chunk_size), m)
        obs.histogram("serve.predict_ms").observe(
            (time.perf_counter() - t0) * 1e3)
        obs.histogram("serve.predict_rows").observe(m)
        return out

    def predict_mean(self, Xstar) -> jax.Array:
        return self.predict(Xstar)[0]
