"""Host-side asynchronous seed/feature staging.

PR 2's double-buffered prefetch overlapped the *device* half of minibatch
preparation (sampling + feature ``all_to_all``) with model compute, but
every step still blocked on host work: ``SeedStream.seeds(k)`` runs the
hash-rank seed draw over the labeled nodes on the host, and its result is
synchronously transferred to the device before the prepare can even be
dispatched.  SALIENT ("Accelerating Training and Inference of GNNs with
Fast Sampling and Pipelining", arXiv 2110.08450) shows this host-side
batch-preparation pipeline is worth a large factor on top of device-side
overlap — the host must ride *ahead* of the device, not in lockstep.

``SeedStager`` is that host-side pipeline stage: a background worker
thread computes ``SeedStream.seeds(k)`` / ``salt(k)`` for future step
indices off the critical path and eagerly starts their H2D transfers via
``jax.device_put``, keeping a bounded ring of ``depth + lead`` staged
slots warm.  Drivers then consume already-resident device arrays:

  * ``depth``  — how many prepared batches the prefetch driver keeps in
                 flight (``PrefetchSpec.depth``); the stager must cover
                 them so a refill never blocks on the host.
  * ``lead``   — extra slots staged beyond the driver's own lookahead
                 (``PrefetchSpec.lead``); this is the actual host-side
                 overlap margin.

Determinism: the stager changes *when* seeds are computed, never *what*
they are — every slot is ``(stream.seeds(k), stream.salt(k))`` for a
concrete step index ``k``, and ``SeedStream`` is a pure function of
``k``.  Staged execution is therefore bit-identical to unstaged execution
for any placement scheme, executor, and prefetch depth
(``tests/test_staging.py`` asserts it).

Consumption is index-checked: ``get(k)`` serves the ring head only when
the head *is* step ``k``; any out-of-sequence request (a driver restart,
an explicit ``step_idx`` jump) drains the ring and refills it from ``k``
— exactly mirroring the prefetch drivers' queue-refill semantics, so
restarts replay the continuous run bit-for-bit.
"""
from __future__ import annotations

import collections
import threading

import jax
import numpy as np

from repro.obs import trace as _trace


class SeedStager:
    """Background staging of per-step seeds/salt with eager H2D transfer.

    Parameters
    ----------
    stream : repro.pipeline.prefetch.SeedStream
        The deterministic seed stream; the stager calls its pure host
        half (``seeds_host`` / ``salt_int``) off-thread — no JAX tracing
        state is touched on the worker thread beyond ``device_put``.
    depth : int, default 0
        The consuming driver's prefetch depth (``0`` for the sync
        driver).  Sizes the ring so queue refills are fully covered.
    lead : int, default 1
        Extra staged slots beyond ``depth`` — how far the host runs ahead
        of the device.  Must be >= 1 (a zero-slot ring stages nothing).
    sharding : jax.sharding.Sharding, optional
        Placement for the staged ``(P, batch)`` seed arrays (e.g. the
        shard_map executor's worker-axis ``NamedSharding``).  ``None``
        commits to the default device.

    Examples
    --------
    >>> stager = SeedStager(stream, depth=1, lead=2)     # doctest: +SKIP
    >>> seeds, salt = stager.get(0)                      # doctest: +SKIP
    >>> stager.close()                                   # doctest: +SKIP
    """

    def __init__(self, stream, *, depth: int = 0, lead: int = 1,
                 sharding=None):
        if depth < 0:
            raise ValueError(f"depth must be >= 0, got {depth}")
        if lead < 1:
            raise ValueError(
                f"staging lead must be >= 1 (got {lead}); lead 0 would "
                f"stage nothing ahead of the driver's own lookahead")
        self.stream = stream
        self.slots = int(depth) + int(lead)
        self.sharding = sharding
        self._cv = threading.Condition()
        self._ring: collections.deque = collections.deque()
        self._want: int | None = None     # next index the worker produces
        self._gen = 0                     # bumped on drain/refill (seek)
        self._error: BaseException | None = None
        self._closed = False
        self._thread = threading.Thread(target=self._worker, daemon=True,
                                        name="repro-seed-stager")
        self._thread.start()

    # ------------------------------------------------------------ producer

    def _produce(self, k: int):
        """Compute step ``k``'s seeds/salt on the host and start their
        device transfer.  Runs on the worker thread; the host half is
        pure numpy (``SeedStream.seeds_host``), then ``jax.device_put``
        enqueues the (async where supported) H2D copy.

        Under the multi-process executor the sharding spans devices this
        process cannot address; ``jax.make_array_from_callback`` then
        assembles the global array from this rank's addressable rows
        (every rank computes the identical full ``(P, batch)`` host
        table, so the rows are consistent by construction).

        Spans recorded here land on this worker thread's own trace
        track (the tracer's span stacks are thread-local)."""
        with _trace.span("stager/produce", cat="stager", step=k):
            with _trace.span("stager/seeds_host", cat="stager"):
                seeds_np = self.stream.seeds_host(k)
            salt_np = np.uint32(self.stream.salt_int(k))
            with _trace.span("stager/h2d", cat="stager"):
                seeds = self._put(seeds_np)
                salt = jax.device_put(salt_np)
        return seeds, salt

    def _put(self, host_array):
        """Start ``host_array``'s transfer to ``self.sharding`` (or the
        default device); handles non-fully-addressable shardings via the
        callback assembly path (see ``_produce``).  Works for any array
        whose leading axis is the worker axis."""
        if self.sharding is not None \
                and not self.sharding.is_fully_addressable:
            return jax.make_array_from_callback(
                host_array.shape, self.sharding,
                lambda idx: host_array[idx])
        return jax.device_put(host_array, self.sharding)

    def _worker(self) -> None:
        while True:
            with self._cv:
                while not self._closed and (
                        self._want is None
                        or len(self._ring) >= self.slots
                        or self._error is not None):
                    self._cv.wait()
                if self._closed:
                    return
                gen, k = self._gen, self._want
            try:
                item = self._produce(k)
            except BaseException as e:  # surfaced by the next get()
                with self._cv:
                    if self._gen == gen:
                        self._error = e
                        self._cv.notify_all()
                continue
            with self._cv:
                if self._gen != gen or self._closed:
                    continue            # stale: a seek raced the produce
                self._ring.append((k, item))
                self._want = k + 1
                self._cv.notify_all()

    # ------------------------------------------------------------ consumer

    def _seek_locked(self, k: int) -> None:
        self._gen += 1
        self._ring.clear()
        self._error = None
        self._want = int(k)
        self._cv.notify_all()

    def seek(self, k: int) -> None:
        """Drain the ring and restart staging from step ``k`` (also what
        an out-of-sequence ``get`` does implicitly)."""
        with self._cv:
            self._seek_locked(k)

    def get(self, k: int):
        """Staged ``(seeds, salt)`` device arrays for step ``k``.

        Serves the ring head when it is step ``k``; otherwise drains and
        refills from ``k`` (restart semantics).  Blocks until the slot is
        staged; re-raises any error the worker thread hit.  The
        ``stager/get`` span covers any such wait — a long one in a trace
        means the ring is not riding far enough ahead (raise
        ``PrefetchSpec.lead``).
        """
        k = int(k)
        with _trace.span("stager/get", cat="stager", step=k), self._cv:
            if self._closed:
                raise RuntimeError("SeedStager is closed")
            head = self._ring[0][0] if self._ring else self._want
            if head != k:
                self._seek_locked(k)
            while not self._ring:
                if self._error is not None:
                    err, self._error = self._error, None
                    self._cv.notify_all()   # let the worker retry
                    raise err
                if self._closed:
                    raise RuntimeError("SeedStager is closed")
                self._cv.wait()
            _, item = self._ring.popleft()
            self._cv.notify_all()           # a slot freed: keep staging
            return item

    # ----------------------------------------------------------- lifecycle

    @property
    def staged(self) -> int:
        """Number of slots currently staged (ready, transfer enqueued)."""
        with self._cv:
            return len(self._ring)

    def close(self) -> None:
        """Stop the worker thread and drop staged slots (idempotent)."""
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._ring.clear()
            self._cv.notify_all()
        self._thread.join(timeout=10.0)

    def __enter__(self) -> "SeedStager":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _aligned_zeros(shape, dtype, align: int = 64) -> np.ndarray:
    """Zeroed array whose data pointer is ``align``-byte aligned.

    XLA:CPU only adopts an external (dlpack) buffer zero-copy when it
    meets its 64-byte alignment requirement; numpy's allocator makes no
    such promise, and a misaligned staged-row buffer would silently fall
    back to a full copy at first use — costing more than the gather it
    feeds."""
    size = int(np.prod(shape)) * np.dtype(dtype).itemsize
    raw = np.zeros(size + align, np.uint8)
    off = (-raw.ctypes.data) % align
    return raw[off:off + size].view(dtype).reshape(shape)


_U32 = 0xFFFFFFFF
_SENTINEL32 = np.iinfo(np.int32).max


def _np_hash_u32(x: np.ndarray, salt: int) -> np.ndarray:
    """Numpy transcription of ``repro.core.sampler.hash_u32`` —
    SplitMix32-style, bit-identical (uint32 wraparound semantics)."""
    x = x.astype(np.uint32) + np.uint32((salt * 0x9E3779B9) & _U32)
    x = (x ^ (x >> np.uint32(16))) * np.uint32(0x85EBCA6B)
    x = (x ^ (x >> np.uint32(13))) * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def _frontier_src_nodes_host(indptr: np.ndarray, indices: np.ndarray,
                             seeds: np.ndarray, fanouts, salt: int
                             ) -> np.ndarray:
    """One worker's final-level frontier, replayed in pure numpy.

    Transcribes ``sample_neighbors`` + the ``src_nodes`` half of
    ``relabel`` (``repro.core.sampler``) level by level: same SplitMix
    draws, same sort-based unique, same -1 padding — the returned array
    is bit-identical to ``sample_mfgs(...)[-1].src_nodes``
    (``tests/test_staging.py`` asserts it).  Pure numpy so the staging
    thread never enqueues device work.
    """
    cur = np.asarray(seeds, np.int32)
    for depth, fanout in enumerate(fanouts):
        lsalt = (int(salt) * 1000003 + depth) & _U32
        seed_ok = cur >= 0
        v = np.where(seed_ok, cur, 0)
        start = indptr[v].astype(np.int64)
        deg = indptr[v + 1].astype(np.int64) - start
        cols = np.arange(fanout, dtype=np.int64)[None, :]
        bits = _np_hash_u32(
            v[:, None].astype(np.uint32) * np.uint32(2654435761)
            + np.arange(fanout, dtype=np.uint32)[None, :], lsalt)
        rand_idx = (bits % np.maximum(deg, 1)[:, None].astype(np.uint32)
                    ).astype(np.int64)
        col = np.where((deg <= fanout)[:, None], cols, rand_idx)
        valid = (cols < np.minimum(deg, fanout)[:, None]) \
            & seed_ok[:, None]
        # out-of-window reads only happen on masked slots; clamp like
        # XLA's gather does so they stay in bounds
        idx = np.clip(start[:, None] + col, 0, indices.shape[0] - 1)
        samples = np.where(valid, indices[idx], -1).astype(np.int32)

        S = cur.shape[0]
        flat = samples.ravel()
        fv = valid.ravel()
        seeds_sorted = np.sort(np.where(seed_ok, cur, _SENTINEL32))
        pos = np.clip(np.searchsorted(seeds_sorted, flat), 0, S - 1)
        is_seed = (seeds_sorted[pos] == flat) & fv
        ns_sorted = np.sort(np.where(fv & ~is_seed, flat, _SENTINEL32))
        is_new = np.concatenate(
            [np.ones(1, bool), ns_sorted[1:] != ns_sorted[:-1]])
        is_new &= ns_sorted != _SENTINEL32
        new_nodes = np.full(flat.shape[0], -1, np.int32)
        n_new = int(is_new.sum())
        new_nodes[:n_new] = ns_sorted[is_new]
        cur = np.concatenate([np.where(seed_ok, cur, -1), new_nodes])
    return cur


class FeatureStager(SeedStager):
    """A ``SeedStager`` that additionally stages the step's feature rows.

    The ``staged`` feature store (``repro.core.feature_store``) removes
    the feature ``all_to_all`` from the traced program entirely; the rows
    have to come from somewhere, and this is it.  For each staged step
    ``k`` the worker thread:

      1. computes ``(seeds, salt)`` exactly like ``SeedStager``;
      2. **replays the sampler on the host** — a pure-numpy transcription
         of ``sample_mfgs`` on the full relabeled topology with the same
         ``(seeds, salt)`` (``_frontier_src_nodes_host``).  The sampler
         is a pure function of ``(seeds, salt)`` (stateless SplitMix
         hashing, paper §4.2) and every placement scheme draws the
         *bit-identical* minibatch, so one hybrid-style replay yields the
         exact frontier the device program will sample, for any scheme.
         Numpy (not a jitted replay) on purpose: on single-device
         backends a producer-thread device program would serialize behind
         the in-flight training step and stall the ring;
      3. gathers the frontier's feature rows from the host copy of the
         full ``(P, n_max, D)`` table with one fancy index (rows of
         ``-1``-padded slots are zeroed, matching ``fetch_features``'s
         masking — the value equality, not just numerical closeness, is
         asserted in ``tests/test_feature_store.py``);
      4. zeroes slots the pinned cache will serve (cold-only staging —
         the store's ``jnp.where`` picks cache rows at hit positions, so
         the zeroes are never read as data).  Only when the store's
         ``hot_rows_from_cache`` says hits really come from the device
         cache; a host-combine ``StagedStore`` stages hot rows too;
      5. starts the H2D transfer of ``(seeds, salt, rows)``.

    The (P, S, D) row buffers come from a small recycled pool rather
    than a fresh allocation per step: at wide D a fresh buffer is
    hundreds of MB whose page-fault + unmap traffic costs tens of ms per
    step — more than the gather itself.  Reuse makes the write pattern
    incremental (gather live slots, zero only slots that were live last
    cycle), so the bytes touched track the live frontier, not the padded
    capacity.  Because ``_put_rows`` hands the buffer to the device
    zero-copy (dlpack), recycling is only sound once the pooled buffer's
    previous reader is done; see ``recycles_buffers`` for the fence
    contract and ``_stage_rows`` for the pool-distance argument.

    ``get(k)`` therefore returns a 3-tuple.  How the rows reach the
    store's ``fetch`` is executor-specific: the shard_map runner threads
    them through ``prepare(seeds, salt, staged_rows)`` (they land in the
    fused donated-FIFO program directly), while the vmap runner attaches
    them to the prepared batch *outside* the traced prepare half —
    passing a (P, N, D) array through prepare would copy it once more at
    the prepare -> consume jit boundary.

    Requires a full feature layout (``local_parts=None``) — a rank-local
    build never materializes remote rows, so the host gather cannot run.
    """

    #: Staged row buffers are recycled (see class docstring).  A driver
    #: consuming this stager must not let a step's device reads stay
    #: in flight for more than one step after ``step`` returns — the
    #: prefetch drivers guarantee it by materializing each step's loss
    #: before returning when this flag is set.
    recycles_buffers = True

    def __init__(self, stream, *, pipeline, depth: int = 0, lead: int = 1,
                 sharding=None):
        layout = pipeline.layout
        if getattr(layout, "local_parts", None) is not None:
            raise ValueError(
                "the staged feature store needs the full feature layout: "
                "a rank-local build (local_parts) never materializes "
                "remote partitions' rows, so the host-side gather cannot "
                "serve the frontier.  Build with local_parts=None.")
        graph = pipeline.graph_replicated
        if graph is None:
            graph = layout.graph
        self._fanouts = tuple(int(f) for f in pipeline.spec.sampler.fanouts)
        # pure-numpy replay state: the producer thread must never enqueue
        # device programs of its own (on single-device backends they would
        # serialize behind the training step it is trying to run ahead of)
        self._indptr_np = np.asarray(graph.indptr)
        self._indices_np = np.asarray(graph.indices)
        self._offsets_np = np.asarray(layout.offsets)
        self._feats_np = np.asarray(layout.features)
        cache = pipeline.cache
        # cold-only staging (zero the slots the pinned cache will
        # serve) only when the store actually serves hits from the
        # cache; a host-combine StagedStore wants the full rows staged
        store = getattr(pipeline, "feature_store", None)
        skip_hits = (cache is not None
                     and getattr(store, "hot_rows_from_cache", True))
        self._cache_ids_np = np.asarray(cache.ids) if skip_hits else None
        # recycled row-buffer pool (see _stage_rows for sizing): buffers
        # and their previous cycle's live mask, allocated lazily at the
        # first produce (the frontier capacity is only known then)
        self._pool_n = 2 * int(depth) + int(lead) + 1
        self._pool: list | None = None
        self._pool_valid: list | None = None
        self._last_k: int | None = None
        super().__init__(stream, depth=depth, lead=lead, sharding=sharding)

    def _stage_rows(self, k: int, frontier: np.ndarray) -> np.ndarray:
        """Gather the (P, S) frontier's rows into a pooled (P, S, D)
        buffer, writing only what changed.

        Live slots (valid ids the pinned cache will not serve) get their
        row; slots live last cycle but not now are re-zeroed; everything
        else is untouched — so the bytes written scale with the live
        fraction, not the padded frontier capacity.

        Pool sizing: buffer for step ``k`` is rewritten at step
        ``k + pool_n``, whose produce starts only after the driver popped
        item ``k + pool_n - (depth + lead)`` from the ring, i.e. during
        driver step ``k + pool_n - 2*depth - lead``.  The vmap runner
        dispatches step ``k``'s consume (the buffer's last reader) during
        driver step ``k``, and a driver consuming a recycling stager
        materializes each step's loss before returning (the
        ``recycles_buffers`` contract) — so ``pool_n = 2*depth + lead +
        1`` puts at least one fully-synced driver step between the last
        read and the rewrite.  Any discontinuity (seek/restart) drops the
        pool instead of reasoning about in-flight readers; the dlpack
        handles keep the orphaned buffers alive until the device is done
        with them.
        """
        valid = frontier >= 0
        ids = self._cache_ids_np
        if ids is not None:
            K = ids.shape[1]
            for p in range(ids.shape[0]):
                pos = np.clip(np.searchsorted(ids[p], frontier[p]),
                              0, K - 1)
                valid[p] &= ~((ids[p][pos] == frontier[p]) & valid[p])
        shape = frontier.shape + (self._feats_np.shape[2],)
        if (self._pool is None or self._last_k is None
                or k != self._last_k + 1 or self._pool[0].shape != shape):
            self._pool = [_aligned_zeros(shape, self._feats_np.dtype)
                          for _ in range(self._pool_n)]
            self._pool_valid = [None] * self._pool_n
        self._last_k = k
        slot = k % self._pool_n
        rows, prev = self._pool[slot], self._pool_valid[slot]
        if prev is not None:
            rows[prev & ~valid] = 0.0
        src = frontier[valid]
        own = np.searchsorted(self._offsets_np, src, side="right") - 1
        rows[valid] = self._feats_np[own, src - self._offsets_np[own]]
        self._pool_valid[slot] = valid
        return rows

    def _produce(self, k: int):
        with _trace.span("stager/produce", cat="stager", step=k):
            with _trace.span("stager/seeds_host", cat="stager"):
                seeds_np = self.stream.seeds_host(k)
            salt_int = self.stream.salt_int(k)
            with _trace.span("stager/frontier_replay", cat="stager"):
                frontier = np.stack([
                    _frontier_src_nodes_host(
                        self._indptr_np, self._indices_np, seeds_np[p],
                        self._fanouts, salt_int)
                    for p in range(seeds_np.shape[0])])
            with _trace.span("stager/gather_rows", cat="stager"):
                rows_np = self._stage_rows(k, frontier)
            with _trace.span("stager/h2d", cat="stager"):
                seeds = self._put(seeds_np)
                rows = self._put_rows(rows_np)
                salt = jax.device_put(np.uint32(salt_int))
        return seeds, salt, rows

    def _put_rows(self, rows_np: np.ndarray):
        """Transfer the staged rows, zero-copy where the backend allows.

        On an unsharded CPU backend a dlpack import hands the pooled
        buffer over without copying (~half the staging cost at wide D);
        the pool distance plus the driver's per-step sync (the
        ``recycles_buffers`` contract) guarantee the aliased buffer is
        not rewritten while the device still reads it.  Anywhere else —
        an accelerator, where the import would yield a CPU-device array,
        or a sharded / multi-host placement — the rows take the ``_put``
        transfer paths."""
        if self.sharding is None and jax.default_backend() == "cpu":
            try:
                return jax.dlpack.from_dlpack(rows_np)
            except Exception:       # non-importable layout: copy instead
                pass
        return self._put(rows_np)


def make_stager(staging, stream, *, depth: int, spec, executor, pipeline):
    """Resolve a driver's ``staging`` argument into ``(stager, owned)``.

    ``staging`` may be ``None`` (defer to ``spec.prefetch.staging``), a
    bool, or an already-built ``SeedStager`` (advanced callers sharing a
    stager across drivers — adopted, not owned, so the driver's
    ``close()`` leaves it running).  When a stager is built here
    (``owned=True``), the executor's ``seed_sharding(pipeline)`` hook
    (when present) chooses where the staged seeds land — e.g. the
    shard_map executor pre-shards them along the worker axis so the
    jitted program never reshards.

    Pipelines whose feature store stages rows externally
    (``store.external_rows``, i.e. the ``staged`` store) *require* a
    ``FeatureStager`` — the traced program performs no feature exchange,
    so the rows must come from the ring.  For them a ``FeatureStager`` is
    built even when ``staging`` is falsy, and an adopted plain
    ``SeedStager`` is rejected.
    """
    store = getattr(pipeline, "feature_store", None) \
        if pipeline is not None else None
    wants_rows = bool(getattr(store, "external_rows", False))
    if staging is None:
        staging = spec.prefetch.staging
    if isinstance(staging, SeedStager):
        if wants_rows and not isinstance(staging, FeatureStager):
            raise ValueError(
                "the staged feature store needs a FeatureStager (its "
                "slots carry the step's feature rows); got a seed-only "
                "SeedStager")
        return staging, False
    if not staging and not wants_rows:
        return None, False
    sharding = None
    hook = getattr(executor, "seed_sharding", None)
    if hook is not None:
        sharding = hook(pipeline)
    if wants_rows:
        return FeatureStager(stream, pipeline=pipeline, depth=depth,
                             lead=spec.prefetch.lead,
                             sharding=sharding), True
    return SeedStager(stream, depth=depth, lead=spec.prefetch.lead,
                      sharding=sharding), True
