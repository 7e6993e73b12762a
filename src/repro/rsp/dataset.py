"""``RSPDataset`` -- the one-object facade over the RSP pipeline.

The paper's workflow is a single conceptual pipeline: randomize, partition
into RSP blocks (Algorithm 1), store, block-sample (Definition 4), then
estimate (Sec. 8) or ensemble-learn (Sec. 9, Algorithm 2).  This class
exposes that pipeline as chainable methods over one carrier object:

    ds = rsp.partition(data, blocks=64, seed=1, num_classes=2)
    ds.save("/data/corpus.rsp")
    stats = ds.moments(g=5)                       # from per-block sketches
    ens, hist = ds.ensemble(make_logreg(28, 2), eval_x=xe, eval_y=ye, g=5)

Construction dispatches through the backend registry (numpy streaming, jit
jax, shard_map collective, Pallas kernel); the resulting dataset carries its
``RSPSpec``, lazy block access (in-memory or store-backed), and per-block
summary statistics computed once at partition time.

All block movement goes through one ``repro.rsp.engine.BlockExecutor``
(``ds.executor``): a pluggable fetcher (``fetcher="auto" | "memory" |
"store" | "mmap"``) behind a bounded thread-pool prefetch pipeline with an
LRU block cache, so estimation, ensemble learning, similarity probes, and
the training loader share the same fast read path.  Block *selection* is a
pluggable ``SamplingPolicy`` (``policy="uniform" | "weighted" |
"stratified"``): the non-uniform policies use the partition-time sketches to
bias selection and expose Horvitz-Thompson weights that keep the moment
estimates unbiased.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import numpy as np

from repro import obs
from repro.core.ensemble import (
    BaseLearner,
    Ensemble,
    EnsembleHistory,
    asymptotic_ensemble_learn,
)
from repro.core.estimators import BlockLevelEstimator, MomentStats, streaming_estimate
from repro.core.registry import RSPStore
from repro.core.schema import Schema
from repro.core.sampler import (
    BlockSampler,
    HostAssignment,
    SamplingPolicy,
    deal_blocks,
    make_policy,
)
from repro.core.similarity import ks_statistic, max_label_divergence, mmd_block_vs_data
from repro.core.types import RSPSpec
from repro.rsp.backends import AUTO, PartitionRequest, run_partition
from repro.rsp.ingest import resolve_stream_source
from repro.rsp.engine import (
    BlockExecutor,
    BlockFetcher,
    MemoryFetcher,
    MmapFetcher,
    StoreFetcher,
    as_fetcher,
)
from repro.rsp.sketch import SketchSuite, load_summaries, sketch_schema_descriptor
from repro.rsp.summaries import (
    BlockSummary,
    combine_summaries,
    max_divergence_from_summaries,
    summarize_blocks,
)


class RSPDataset:
    """A materialized Random Sample Partition with chainable analysis ops."""

    def __init__(
        self,
        spec: RSPSpec,
        *,
        blocks: np.ndarray | None = None,
        store: RSPStore | None = None,
        backend: str = "np",
        summaries: list[SketchSuite] | list[BlockSummary] | None = None,
        num_classes: int | None = None,
        label_column: int = -1,
        fetcher: str | BlockFetcher = "auto",
        prefetch: int = 4,
        cache_blocks: int = 8,
    ):
        if blocks is None and store is None:
            raise ValueError("provide in-memory blocks and/or a store")
        self.spec = spec
        self.backend = backend
        self.num_classes = num_classes
        self.label_column = label_column
        self._blocks = None if blocks is None else np.asarray(blocks)
        self._store = store
        self._summaries = summaries
        self._fetcher_mode = fetcher
        self._prefetch = prefetch
        self._cache_blocks = cache_blocks
        self._executor: BlockExecutor | None = None

    # ------------------------------------------------------------------
    # Construction: Algorithm 1 through the backend registry
    # ------------------------------------------------------------------
    @classmethod
    def partition(
        cls,
        data: Any,
        blocks: int,
        *,
        original_blocks: int | None = None,
        seed: int = 0,
        backend: str = AUTO,
        mesh: jax.sharding.Mesh | None = None,
        mesh_axis: str = "data",
        permute_assignment: bool = True,
        num_classes: int | None = None,
        label_column: int = -1,
        summaries: bool = True,
        out: str | None = None,
        chunk_records: int | None = None,
        schema: Schema | Sequence[dict] | None = None,
    ) -> "RSPDataset":
        """Partition ``data`` [N, ...] into an RSP of ``blocks`` blocks.

        ``data`` may be an in-memory array, or any streaming source
        ``repro.rsp.ingest.as_chunk_source`` adapts (a ``.npy`` path read
        via mmap, a directory of chunk files, a record-batch
        ``ChunkSource``, a memmap) -- streaming sources never load the
        corpus whole.  ``backend="auto"`` picks shard_map when ``mesh`` is
        supplied, the Pallas kernel when its shape constraints hold on a
        TPU host, the out-of-core ``np_stream`` scatter for streaming
        sources and whenever ``out=`` is given, and the in-memory numpy
        path otherwise; pass an explicit name to force one.

        ``out`` writes the partition directly into a store at that path:
        the streaming backend scatters chunk slices straight to their
        block-file offsets (peak memory O(chunk), the corpus never
        materializes) and the returned dataset is store-backed; in-memory
        backends save their result there.  ``num_classes`` marks column
        ``label_column`` as a class label so label histograms join the
        per-block summaries and ``.ensemble`` / ``.label_divergence`` know
        how to split records.

        ``schema`` (a :class:`~repro.core.schema.Schema`, or its list of
        column dicts) makes a typed table: ``data`` is then ``[N, F]`` int32
        codes, one column per schema column, stored bit-exact, and queries
        name columns, compare literals in their units, sum expressions and
        group by dictionary columns (see :class:`repro.rsp.query.Query`).
        """
        if schema is not None and not isinstance(schema, Schema):
            schema = Schema.from_list(schema)
        # memmaps are ndarrays: when an in-memory backend is forced they stay
        # raw (it serves them fine); under auto/np_stream they stream
        src = None
        if not isinstance(data, np.ndarray) or backend in (AUTO, "np_stream"):
            src = resolve_stream_source(data, chunk_records=chunk_records)
        if src is not None:
            data = src
            n = src.num_records
            record_shape = tuple(src.record_shape)
            dtype = str(np.dtype(src.dtype))
        else:
            n = np.shape(data)[0]
            record_shape = tuple(np.shape(data)[1:])
            dtype = str(np.dtype(getattr(data, "dtype", np.float32)))
        spec = RSPSpec(
            num_records=n,
            num_blocks=blocks,
            num_original_blocks=blocks if original_blocks is None else original_blocks,
            record_shape=record_shape,
            dtype=dtype,
            seed=seed,
            schema=schema,
        )
        request = PartitionRequest(
            data=data,
            spec=spec,
            mesh=mesh,
            mesh_axis=mesh_axis,
            permute_assignment=permute_assignment,
            out=out,
            with_summaries=summaries,
            num_classes=num_classes,
            label_column=label_column,
            chunk_records=chunk_records,
        )
        result, chosen = run_partition(request, backend=backend)
        if isinstance(result, RSPStore):
            # streaming backend wrote directly to the store; prefer the
            # suites folded during the write (in-memory handoff) over
            # re-parsing the sketch sidecar it just streamed out
            folded = result.last_ingest_summaries
            if folded is None:
                raw = result.summaries()
                folded = None if raw is None else load_summaries(raw)
            return cls(
                spec,
                store=result,
                backend=chosen,
                summaries=folded,
                num_classes=num_classes,
                label_column=label_column,
            )
        ds = cls(
            spec,
            blocks=result,
            backend=chosen,
            num_classes=num_classes,
            label_column=label_column,
        )
        if summaries:
            with obs.span("partition.sketch", blocks=blocks):
                ds._summaries = ds._compute_summaries()
        if out is not None:
            ds.save(out)
        return ds

    @classmethod
    def from_source(
        cls,
        source: Any,
        blocks: int,
        *,
        out: str | None = None,
        original_blocks: int | None = None,
        seed: int = 0,
        permute_assignment: bool = True,
        num_classes: int | None = None,
        label_column: int = -1,
        summaries: bool = True,
        chunk_records: int | None = None,
    ) -> "RSPDataset":
        """Build an RSP from a chunked source with bounded memory (the
        out-of-core ingest path, forced).  ``source`` is anything
        ``as_chunk_source`` adapts; with ``out`` set the corpus streams
        straight into a stored RSP whose manifest carries the
        partition-time sketches -- peak memory stays O(chunk + write
        buffers) no matter how large the corpus is."""
        return cls.partition(
            source,
            blocks,
            original_blocks=original_blocks,
            seed=seed,
            backend="np_stream",
            permute_assignment=permute_assignment,
            num_classes=num_classes,
            label_column=label_column,
            summaries=summaries,
            out=out,
            chunk_records=chunk_records,
        )

    # ------------------------------------------------------------------
    # Block access: one executor owns all block movement
    # ------------------------------------------------------------------
    @property
    def num_blocks(self) -> int:
        return self.spec.num_blocks

    @property
    def block_size(self) -> int:
        return self.spec.block_size

    def __len__(self) -> int:
        return self.num_blocks

    @property
    def executor(self) -> BlockExecutor:
        """The dataset's :class:`BlockExecutor` (built lazily): prefetch
        pipeline + LRU cache over the configured fetcher."""
        if self._executor is None:
            self._executor = BlockExecutor(
                self._make_fetcher(),
                prefetch=self._prefetch,
                cache_blocks=self._cache_blocks,
            )
        return self._executor

    def _make_fetcher(self) -> BlockFetcher:
        mode = self._fetcher_mode
        if not isinstance(mode, str):
            return as_fetcher(mode)
        if mode == "auto":
            if self._blocks is not None:
                return MemoryFetcher(self._blocks)
            return StoreFetcher(self._store)
        if mode == "memory":
            if self._blocks is None:
                # materialize directly from the store -- self.stacked() would
                # recurse through self.executor, which is being built here
                with BlockExecutor(
                    StoreFetcher(self._store), prefetch=self._prefetch, cache_blocks=0
                ) as loadall:
                    self._blocks = loadall.take(range(self.num_blocks))
            return MemoryFetcher(self._blocks)
        if mode in ("store", "mmap"):
            if self._store is None:
                raise ValueError(f"fetcher={mode!r} needs a store-backed dataset")
            return StoreFetcher(self._store) if mode == "store" else MmapFetcher(self._store)
        raise ValueError(
            f"unknown fetcher {mode!r} (auto | memory | store | mmap | BlockFetcher)"
        )

    def close(self) -> None:
        """Release the executor's worker threads (optional; idle otherwise)."""
        if self._executor is not None:
            self._executor.close()
            self._executor = None

    def block(self, block_id: int) -> np.ndarray:
        if not 0 <= block_id < self.num_blocks:
            raise IndexError(f"block {block_id} out of range [0, {self.num_blocks})")
        return self.executor.fetch(block_id)

    def __getitem__(self, block_id: int) -> np.ndarray:
        return self.block(block_id)

    def take(self, block_ids: Sequence[int]) -> np.ndarray:
        """Stack the given blocks -> [g, n, ...] (prefetched)."""
        return self.executor.take(block_ids)

    def stacked(self) -> np.ndarray:
        """All blocks as one [K, n, ...] array (loads everything)."""
        if self._blocks is None:
            self._blocks = self.executor.take(range(self.num_blocks))
        return self._blocks

    # ------------------------------------------------------------------
    # Per-block summary statistics (partition-time sketches)
    # ------------------------------------------------------------------
    @property
    def summaries(self) -> list[SketchSuite]:
        if self._summaries is None:
            self._summaries = self._compute_summaries()
        return self._summaries

    @property
    def has_summaries(self) -> bool:
        """Whether partition-time sketches are already materialized (without
        triggering the full-corpus pass that computes them)."""
        return self._summaries is not None

    def _compute_summaries(self, counter=None) -> list[SketchSuite]:
        label_column = self.label_column if self.num_classes is not None else None
        return summarize_blocks(
            self.executor.map_blocks(None, range(self.num_blocks), counter=counter),
            label_column=label_column,
            num_classes=self.num_classes,
        )

    # ------------------------------------------------------------------
    # Storage (re-plumbs RSPStore)
    # ------------------------------------------------------------------
    def save(self, path: str) -> "RSPDataset":
        """Materialize to ``path`` (blocks + manifest with sketches); chainable."""
        store = RSPStore(path)
        summaries = self.summaries
        with obs.span("store.write", blocks=self.num_blocks):
            schema = (
                sketch_schema_descriptor(summaries)
                if summaries and isinstance(summaries[0], SketchSuite)
                else None
            )
            store.write_partition(
                self.stacked(),
                self.spec,
                summaries=summaries,
                meta={
                    "backend": self.backend,
                    "num_classes": self.num_classes,
                    "label_column": self.label_column,
                },
                sketch_schema=schema,
            )
        self._store = store
        return self

    @classmethod
    def open(
        cls,
        path: str,
        *,
        fetcher: str | BlockFetcher = "auto",
        prefetch: int = 4,
        cache_blocks: int = 8,
    ) -> "RSPDataset":
        """Open a stored RSP; blocks load lazily, sketches from the manifest.

        ``fetcher="mmap"`` memory-maps blocks instead of materializing them
        (for corpora larger than RAM); ``prefetch``/``cache_blocks`` size the
        executor's pipeline.
        """
        store = RSPStore(path)
        meta = store.meta()
        raw = store.summaries()
        return cls(
            store.spec(),
            store=store,
            backend=str(meta.get("backend", "np")),
            summaries=None if raw is None else load_summaries(raw),
            num_classes=meta.get("num_classes"),
            label_column=int(meta.get("label_column", -1)),
            fetcher=fetcher,
            prefetch=prefetch,
            cache_blocks=cache_blocks,
        )

    @property
    def store(self) -> RSPStore | None:
        return self._store

    # ------------------------------------------------------------------
    # Block-level sampling (Definition 4 + sketch-guided policies)
    # ------------------------------------------------------------------
    def sampler(self, seed: int = 0) -> BlockSampler:
        return BlockSampler(self.num_blocks, seed=seed)

    def policy(
        self, policy: str | SamplingPolicy = "uniform", *, seed: int = 0, **kwargs
    ) -> SamplingPolicy:
        """Resolve a block-selection policy over this dataset.  ``weighted``,
        ``stratified`` and ``query_aware`` read the partition-time sketches;
        ``query_aware`` additionally accepts the query context
        (``predicates=``, ``feature=``, ``by_label=``) it scores blocks
        against."""
        needs_sketches = isinstance(policy, str) and policy != "uniform"
        return make_policy(
            policy,
            self.num_blocks,
            seed=seed,
            summaries=self.summaries if needs_sketches else None,
            **kwargs,
        )

    def sample(
        self, g: int, *, seed: int = 0, policy: str | SamplingPolicy = "uniform"
    ) -> list[int]:
        """One block-level sample: g block ids (without replacement for
        ``uniform``; PPS-with-replacement for ``weighted``; proportional
        strata draws for ``stratified``)."""
        return self.policy(policy, seed=seed).sample(g)

    def deal(self, num_hosts: int, *, seed: int = 0, epoch: int = 0) -> HostAssignment:
        """Deal block ids across hosts for one epoch (multi-host training)."""
        return deal_blocks(self.num_blocks, num_hosts, seed=seed, epoch=epoch)

    # ------------------------------------------------------------------
    # Estimation (Sec. 8)
    # ------------------------------------------------------------------
    def moments(
        self,
        g: int | None = None,
        *,
        seed: int = 0,
        ids: Sequence[int] | None = None,
        policy: str | SamplingPolicy = "uniform",
    ) -> MomentStats:
        """Corpus moments estimated from a block-level sample of ``g`` blocks
        (``ids`` if given, all blocks when both are None) -- combined from the
        partition-time sketches, so no block data is read.  A non-uniform
        ``policy`` selects blocks by their sketches and Horvitz-Thompson
        reweights the combine, so the estimate stays unbiased."""
        summaries = self.summaries
        non_uniform = isinstance(policy, SamplingPolicy) or policy != "uniform"
        if ids is not None and non_uniform:
            raise ValueError(
                "pass either ids or a non-uniform policy, not both: explicit ids"
                " have no selection probabilities to HT-reweight by"
            )
        if non_uniform:
            if g is None:
                raise ValueError("non-uniform policies need g")
            pol = self.policy(policy, seed=seed)
            ids = pol.sample(g)
            return combine_summaries(
                [summaries[k] for k in ids],
                weights=pol.weights(ids),
                total_count=self.spec.num_records,
            )
        if ids is None:
            ids = range(self.num_blocks) if g is None else self.sample(g, seed=seed)
        return combine_summaries([summaries[k] for k in ids])

    def estimator(
        self,
        g: int | None = None,
        *,
        seed: int = 0,
        ids: Sequence[int] | None = None,
        rel_tol: float | None = None,
    ) -> BlockLevelEstimator:
        """A ``BlockLevelEstimator`` fed through the executor's prefetched
        block stream -- use when the convergence history / plateau detector
        is wanted.  ``rel_tol`` stops the scan at the plateau."""
        if ids is None:
            ids = range(self.num_blocks) if g is None else self.sample(g, seed=seed)
        return streaming_estimate(self.executor, ids, rel_tol=rel_tol)

    def estimate(
        self,
        fn: Callable[[np.ndarray], Any],
        g: int | None = None,
        *,
        seed: int = 0,
        policy: str | SamplingPolicy = "uniform",
    ) -> Any:
        """Block-level estimate of an arbitrary statistic: mean of ``fn(block)``
        over a block-level sample (each block is a random sample, so the
        average is an unbiased estimate of the corpus statistic).  ``fn`` runs
        on the executor's workers, overlapping with the fetch of later blocks.
        Non-uniform policies contribute self-normalized HT weights."""
        pol = None
        if isinstance(policy, SamplingPolicy) or policy != "uniform":
            if g is None:
                raise ValueError("non-uniform policies need g")
            pol = self.policy(policy, seed=seed)
            ids = pol.sample(g)
        else:
            ids = (
                list(range(self.num_blocks)) if g is None else self.sample(g, seed=seed)
            )
        values = [np.asarray(v) for v in self.executor.map_blocks(fn, ids)]
        weights = pol.weights(ids) if pol is not None else None
        return np.average(values, axis=0, weights=weights)

    # ------------------------------------------------------------------
    # Declarative queries (progressive, anytime CIs)
    # ------------------------------------------------------------------
    def query(self, aggregates="mean", **kwargs):
        """Answer a declarative aggregate query with anytime confidence
        intervals, reading as few blocks as the stopping rule allows.

        ``aggregates`` is a ``Query``, an aggregate spec (``"mean"``,
        ``"p95"``, ``Aggregate("quantile", q=0.5, by_label=True)``, ...), or
        a sequence of specs; stopping-rule kwargs (``target_rel_err=``,
        ``confidence=``, ``max_blocks=``, ``policy=``, ...) are forwarded to
        :class:`repro.rsp.query.Query`.  ``where=`` restricts the query to
        rows passing column predicates (``where="c3 > 0.5"``) and
        ``columns=`` projects the answer onto a feature subset -- both run
        through the plan-compiled fused kernels, one filtered pass per
        block.  Moment/label-count-only queries *without* predicates are
        answered from the partition-time sketches with zero block reads;
        everything else streams blocks through the executor and stops early
        once every CI is tighter than ``target_rel_err``.  Returns the final
        :class:`repro.rsp.query.QueryResult`.
        """
        from repro.rsp.query import QueryExecutor, as_query

        return QueryExecutor(self, as_query(aggregates, **kwargs)).run()

    def query_stream(self, aggregates="mean", **kwargs):
        """Progressive variant of :meth:`query`: yields one anytime
        ``QueryResult`` per block read (a single result for sketch-only
        queries), so callers can watch the intervals narrow."""
        from repro.rsp.query import QueryExecutor, as_query

        return QueryExecutor(self, as_query(aggregates, **kwargs)).stream()

    def distribute(self, transport, *, ownership=None, **kwargs):
        """This dataset as one host of a mesh: a
        :class:`~repro.distributed.DistributedDataset` whose queries fan
        block work out over ``transport`` (a
        :class:`~repro.distributed.mesh.Transport`), with this host reading
        only its owned blocks.  ``ownership`` defaults to the deterministic
        deal of ``num_blocks`` over ``transport.num_hosts`` seeded by the
        partition seed; ``straggler_grace=`` / ``poll_interval=`` forward to
        ``DistributedDataset``.  Requires materialized partition-time
        sketches (open a store that carries them, or partition with
        ``summaries=True``)."""
        from repro.distributed.rsp import DistributedDataset

        return DistributedDataset(self, transport, ownership=ownership, **kwargs)

    def serve(self, **kwargs):
        """A concurrent multi-tenant :class:`~repro.serve.QueryService` over
        this dataset: many simultaneous queries share this dataset's
        ``BlockExecutor`` block cache, an admission controller bounds
        in-flight block-I/O demand, a deadline-aware scheduler interleaves
        one-block progressive steps across tenants, and every query can
        return an anytime result when its deadline fires.  Keyword arguments
        (``capacity=``, ``max_queue=``, ``workers=``, ``seed=``,
        ``default_deadline_ms=``) forward to ``QueryService``.  Use as a
        context manager or call ``close()`` to release the worker threads.
        """
        from repro.serve.query_service import QueryService

        return QueryService(self, **kwargs)

    # ------------------------------------------------------------------
    # Ensemble learning (Sec. 9, Algorithm 2)
    # ------------------------------------------------------------------
    def ensemble(
        self,
        learner: BaseLearner,
        *,
        eval_x: Any,
        eval_y: Any,
        g: int = 5,
        batches: int | None = None,
        seed: int = 0,
        improvement_tol: float = 1e-3,
        patience: int = 2,
    ) -> tuple[Ensemble, EnsembleHistory]:
        """Asymptotic ensemble learning over block-level samples.  Records
        are split into features/label via ``label_column`` (set
        ``num_classes`` at partition time).  Blocks stream through the
        executor per batch, so a store-backed dataset only reads the sampled
        blocks -- prefetched while the previous batch trains."""
        import jax.numpy as jnp

        if self.num_classes is None:
            raise ValueError("ensemble needs num_classes (set it at partition time)")

        def fetch(ids):
            xs, ys = self._split_xy(self.executor.take(ids))
            return jnp.asarray(xs), jnp.asarray(ys)

        return asymptotic_ensemble_learn(
            learner=learner,
            eval_x=jnp.asarray(eval_x),
            eval_y=jnp.asarray(eval_y),
            g=g,
            seed=seed,
            improvement_tol=improvement_tol,
            patience=patience,
            max_batches=batches,
            num_blocks=self.num_blocks,
            fetch_blocks=fetch,
        )

    def _split_xy(self, stacked: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        col = self.label_column % stacked.shape[-1]
        ys = stacked[..., col].astype(np.int32)
        xs = np.delete(stacked, col, axis=-1)
        return xs, ys

    # ------------------------------------------------------------------
    # Similarity / diagnostics (Sec. 7)
    # ------------------------------------------------------------------
    def similarity(
        self,
        block_id: int,
        *,
        metric: str = "mmd",
        feature: int = 0,
        max_points: int = 1024,
        seed: int = 0,
    ) -> float:
        """How close block ``block_id`` is to the full corpus.

        ``metric="mmd"``: unbiased MMD^2 (RBF, median-heuristic bandwidth);
        ``metric="ks"``: two-sample KS statistic on one feature column;
        ``metric="labels"``: L-inf label-distribution distance (needs
        ``num_classes``).

        The corpus reference is the full in-memory partition when available
        (the probed block is legitimately a 1/K fraction of it); for
        store-backed datasets it is a bounded block-level sample (valid by
        Lemma 1 -- each block is a random sample) that *excludes* the probed
        block, since a small reference that contained the probe would
        overweight it far beyond its 1/K corpus share and shrink every
        distance.
        """
        block = self.block(block_id)
        corpus = self._corpus_reference(
            max(max_points, 4096), seed=seed, exclude=block_id
        )
        if metric == "mmd":
            return mmd_block_vs_data(block, corpus, max_points=max_points, seed=seed)
        if metric == "ks":
            return ks_statistic(block[:, feature], corpus[:, feature])
        if metric == "labels":
            if self.num_classes is None:
                raise ValueError("metric='labels' needs num_classes")
            col = self.label_column
            return max_label_divergence(block[:, col], corpus[:, col], self.num_classes)
        raise ValueError(f"unknown metric {metric!r} (mmd | ks | labels)")

    def _corpus_reference(
        self, max_records: int, *, seed: int = 0, exclude: int | None = None
    ) -> np.ndarray:
        """Flat [M, ...] corpus sample for similarity comparisons: the whole
        partition when in memory, else >= ``max_records`` records from a
        block-level sample (no full-corpus load).  ``exclude`` keeps a probed
        block out of its own reference set (self-inclusion shrinks any
        block-vs-corpus distance)."""
        if self._blocks is not None:
            return self._blocks.reshape(-1, *self.spec.record_shape)
        g = min(self.num_blocks, max(1, -(-max_records // self.block_size)))
        request = min(self.num_blocks, g + (1 if exclude is not None else 0))
        ids = self.sample(request, seed=seed)
        if exclude is not None:
            ids = [i for i in ids if i != exclude][:g]
            if not ids:
                # single-block store: the probe IS the corpus (degenerate)
                ids = [exclude]
        return self.executor.take(ids).reshape(-1, *self.spec.record_shape)

    def label_divergence(self) -> float:
        """Worst block-vs-corpus label L-inf distance, from the sketches alone."""
        return max_divergence_from_summaries(self.summaries)

    # ------------------------------------------------------------------
    # Training pipeline
    # ------------------------------------------------------------------
    def loader(
        self,
        batch_size: int,
        *,
        seed: int = 0,
        policy: str | SamplingPolicy = "uniform",
        prefetch: int = 2,
        **kwargs,
    ):
        """An ``RSPLoader`` over this dataset (block-level sampled batches,
        prefetched through the engine; ``policy`` selects blocks)."""
        from repro.data.loader import BlockSource, RSPLoader

        # the loader gets the dataset's configured fetcher (memory / store /
        # mmap / custom) but its own cache-free executor: blocks stream in
        # one hop, not through this dataset's executor and LRU cache (which
        # would retain single-use training blocks)
        return RSPLoader(
            BlockSource(dataset=self),
            batch_size=batch_size,
            seed=seed,
            policy=policy,
            prefetch=prefetch,
            fetcher=self._make_fetcher(),
            **kwargs,
        )

    def __repr__(self) -> str:
        src = "memory" if self._blocks is not None else f"store:{self._store.root}"
        return (
            f"RSPDataset(K={self.num_blocks}, n={self.block_size}, "
            f"record_shape={self.spec.record_shape}, backend={self.backend!r}, "
            f"source={src})"
        )
