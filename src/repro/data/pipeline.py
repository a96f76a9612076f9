"""Staged ingestion pipeline: stream → adaptive filter → tokenize → pack.

This is where the paper's operator becomes a first-class framework feature:
the filter stage is an ``AdaptiveFilter`` (or a static one — drop-in), its
``OrderState`` is part of the pipeline checkpoint (adaptive ranks survive
restarts, per DESIGN §6), and every host/shard runs its own instance — the
paper's per-executor scope by construction.

Two deployment shapes, both thin iterators over ONE ``FilterSession``
(``make_pipeline(build_session(plan), ...)`` picks the right one):

  ``Pipeline``        — one stream, one session (one host process = one
                        executor; run N processes for N executors).
  ``ShardedPipeline`` — one process drives a whole data mesh: S per-shard
                        ``LogStream``s feed ONE sharded session step per
                        iteration (shard_map over the mesh's data axis,
                        per-shard OrderState, scope-controlled stat
                        exchange — see ``core.sharded``).

All per-step driving — capacity resolution, deferred exchange, auto
retune, overflow warnings, metrics — lives in ``FilterSession.step``; the
pipelines only assemble batches and emit fixed-shape LM examples.

Both emit fixed-shape LM batches {"tokens": i32[B, S], "labels": i32[B, S]}
ready for ``train_step``, checkpoint/restore bit-identically (the
fault-tolerance tests restart mid-stream and compare batch sequences), and
honour ``compact_output``: survivors then arrive as padded on-device
buffers + counts and the host never boolean-indexes a batch. With
``device_tokenize=True`` (needs ``compact_output``) the tokenize/pack stage
consumes those padded buffers ON DEVICE too (``tokenizer.tokens_from_padded``
— valid-count-masked hash + O(N) cumsum pack), so one ingestion iteration
moves exactly one dense token buffer to the host: stream → filter →
compact → tokenize is a single device-resident pass. Deferred-exchange
epoch boundaries (``AdaptiveFilterConfig.exchange``) and auto capacity
re-tuning (``compact_capacity="auto"``) are driven from here, after each
step.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Iterator, Sequence

import numpy as np

log = logging.getLogger(__name__)

from repro.core.plan import TokenizeSpec
from repro.data import tokenizer
from repro.data.stream import LogStream


def _as_session(filt, device_tokenize: bool, vocab_size: int,
                tokens_per_row: int):
    """Normalize a pipeline's filter argument to ONE ``FilterSession``.

    Accepts a ``FilterSession`` (the plan-first path), a ``GuardedSession``
    (the self-healing wrapper — it proxies the full session surface, so the
    pipeline drives it identically and gains quarantine/retry/rollback for
    free), or a legacy ``AdaptiveFilter`` / ``ShardedAdaptiveFilter``
    instance (adopted under a synthesized plan). Returns
    (session, device_tokenize) with the tokenize stage attached to the
    session when requested — all combination validation happens in
    ``FilterPlan``, not here.
    """
    from repro.core.session import FilterSession

    if isinstance(filt, FilterSession) \
            or getattr(filt, "is_guarded_session", False):
        session = filt
    else:
        session = FilterSession.from_filter(filt)
    spec = session.plan.tokenize
    if spec is None and device_tokenize:
        spec = TokenizeSpec(vocab_size, tokens_per_row)
        session = session.with_tokenize(spec)
    if spec is not None and (spec.vocab_size != vocab_size
                             or spec.tokens_per_row != tokens_per_row):
        raise ValueError(
            f"pipeline tokenize params (vocab={vocab_size}, "
            f"tokens_per_row={tokens_per_row}) disagree with the plan's "
            f"TokenizeSpec {spec}")
    return session, spec is not None


def fstate_to_arrays(fstate) -> dict:
    """OrderState → flat dict of numpy arrays (checkpoint encoding).

    Works for single [P]-shaped states and stacked [S, P] sharded states —
    leaves are stored verbatim, stats fields under a ``stats.`` prefix
    (``stats.term_cut`` only for trees).
    """
    return {k: np.asarray(v) for k, v in fstate._asdict().items()
            if k != "stats"} \
        | {f"stats.{k}": np.asarray(v) for k, v in
           fstate.stats._asdict().items() if v is not None}


def fstate_from_arrays(fs: dict):
    """Inverse of ``fstate_to_arrays`` (jnp leaves).

    Pre-CNF checkpoints lack the group fields; for flat chains group_cut ≡
    num_cut accumulators start at zero and group_perm is the identity, so
    the defaults restore them losslessly (shape-generic: the identity is
    broadcast over any leading shard axis).
    """
    import jax.numpy as jnp

    from repro.core.ordering import OrderState
    from repro.core.stats import FilterStats

    adj = np.asarray(fs["adj_rank"])
    n_groups = int(adj.shape[-1])
    stats = FilterStats(jnp.asarray(fs["stats.num_cut"]),
                        jnp.asarray(fs["stats.cost_acc"]),
                        jnp.asarray(fs["stats.n_monitored"]),
                        jnp.asarray(fs.get("stats.group_cut",
                                           fs["stats.num_cut"])),
                        None if "stats.term_cut" not in fs
                        else jnp.asarray(fs["stats.term_cut"]))
    default_gperm = np.broadcast_to(
        np.arange(n_groups, dtype=np.int32), adj.shape)
    return OrderState(
        perm=jnp.asarray(fs["perm"]), adj_rank=jnp.asarray(fs["adj_rank"]),
        stats=stats, rows_into_epoch=jnp.asarray(fs["rows_into_epoch"]),
        sample_phase=jnp.asarray(fs["sample_phase"]),
        epoch=jnp.asarray(fs["epoch"]),
        group_perm=jnp.asarray(fs.get("group_perm", default_gperm)))


@dataclasses.dataclass
class PipelineState:
    stream_cursor: int
    filter_state: dict          # versioned session blob (schema v2);
                                # pre-session raw-array (v1) dicts restore too
    buffer: np.ndarray          # leftover tokens not yet emitted
    batches_emitted: int
    rows_in: int
    rows_pass: int


class _LMBatchEmitter:
    """Shared session-step + tokenize-buffer-emit tail of both pipelines.

    Expects ``batch_size``, ``seq_len``, ``vocab_size``, ``tokens_per_row``,
    ``_session``, ``_fstate``, ``_buffer``, and ``batches_emitted`` on self.
    """

    def _emit_tokens(self, toks: np.ndarray) -> Iterator[dict]:
        self._buffer = np.concatenate([self._buffer, toks])
        need = self.batch_size * (self.seq_len + 1)
        while self._buffer.size >= need:
            chunk, self._buffer = self._buffer[:need], self._buffer[need:]
            seq = chunk.reshape(self.batch_size, self.seq_len + 1)
            self.batches_emitted += 1
            yield {"tokens": seq[:, :-1].astype(np.int32),
                   "labels": seq[:, 1:].astype(np.int32)}

    def _emit(self, survivors: np.ndarray) -> Iterator[dict]:
        yield from self._emit_tokens(tokenizer.rows_to_tokens(
            survivors, self.vocab_size, self.tokens_per_row))

    def _filter_step(self, columns: np.ndarray):
        """ONE session step; returns (payload, n_pass).

        ``payload`` is the dense token stream under device tokenization
        (the rows never come back to the host), otherwise the surviving
        rows (sliced from the packed device buffer under compaction, a host
        boolean index otherwise). All driving — capacity resolution,
        deferred exchange, auto retune, overflow warning, metrics — is the
        session's; ``last_metrics`` is its uniform JSON encoding, with
        per-shard ``n_dropped`` alongside the sum for sharded sessions.
        """
        self._fstate, res = self._session.step(self._fstate, columns)
        self.last_metrics = res.metrics_dict()
        if self._device_tokenize:
            return res.host_tokens(), res.n_pass
        return res.survivors(columns), res.n_pass


class Pipeline(_LMBatchEmitter):
    def __init__(self, stream: LogStream, filt,
                 batch_size: int, seq_len: int, vocab_size: int,
                 tokens_per_row: int = 8, device_tokenize: bool = False):
        self.stream = stream
        self._session, self._device_tokenize = _as_session(
            filt, device_tokenize, vocab_size, tokens_per_row)
        self.filt = self._session.filter
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.vocab_size = vocab_size
        self.tokens_per_row = tokens_per_row
        self._fstate = self._session.init_state()
        self._buffer = np.zeros((0,), np.int32)
        self.batches_emitted = 0
        self.rows_in = 0
        self.rows_pass = 0
        self.last_metrics: dict = {}

    # ------------------------------------------------------------- checkpoint
    def state(self) -> PipelineState:
        return PipelineState(
            stream_cursor=self.stream.cursor,
            filter_state=self._session.save_state(self._fstate),
            buffer=self._buffer.copy(),
            batches_emitted=self.batches_emitted,
            rows_in=self.rows_in,
            rows_pass=self.rows_pass,
        )

    def restore(self, st: PipelineState) -> None:
        self.stream.cursor = st.stream_cursor
        self._fstate = self._session.restore_state(st.filter_state)
        self._buffer = st.buffer.copy()
        self.batches_emitted = st.batches_emitted
        self.rows_in = st.rows_in
        self.rows_pass = st.rows_pass

    # -------------------------------------------------------------- iteration
    def __iter__(self) -> Iterator[dict]:
        for rb in self.stream:
            payload, n_pass = self._filter_step(rb.columns)
            self.rows_in += rb.n_rows
            self.rows_pass += n_pass
            if self._device_tokenize:
                yield from self._emit_tokens(payload)
            else:
                yield from self._emit(payload)


# =============================================================== sharded
@dataclasses.dataclass
class ShardedPipelineState:
    stream_cursors: list        # one LogStream cursor per shard
    filter_state: dict          # versioned session blob (v2; stacked
                                # [S, ...] arrays inside), v1 loads too
    buffer: np.ndarray
    batches_emitted: int
    rows_in: int
    rows_pass: int


class ShardedPipeline(_LMBatchEmitter):
    """Multi-shard ingestion: S per-shard streams → one shard_map step.

    ``streams[i]`` must be the i-th round-robin partition of one logical
    stream (``LogStream(shard_id=i, num_shards=S)``) — like Spark partitions
    spread over executors. Each iteration pulls one batch per shard,
    block-concatenates them into the [C, S·R] layout ``ShardedAdaptiveFilter``
    expects (shard i owns rows [i·R, (i+1)·R)), runs ONE jitted sharded
    step, and packs survivors shard-major into LM batches. The stacked
    per-shard ``OrderState`` checkpoints/restores as a whole, so every
    shard's adaptive ranks survive a restart.
    """

    def __init__(self, streams: Sequence[LogStream], filt,
                 batch_size: int, seq_len: int,
                 vocab_size: int, tokens_per_row: int = 8,
                 device_tokenize: bool = False):
        self._session, self._device_tokenize = _as_session(
            filt, device_tokenize, vocab_size, tokens_per_row)
        self.filt = self._session.filter
        if len(streams) != self._session.num_shards:
            raise ValueError(f"{len(streams)} streams for "
                             f"{self._session.num_shards} shards")
        self.streams = list(streams)
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.vocab_size = vocab_size
        self.tokens_per_row = tokens_per_row
        self._fstate = self._session.init_state()
        self._buffer = np.zeros((0,), np.int32)
        self.batches_emitted = 0
        self.rows_in = 0
        self.rows_pass = 0
        self.last_metrics: dict = {}

    # ------------------------------------------------------------- checkpoint
    def state(self) -> ShardedPipelineState:
        return ShardedPipelineState(
            stream_cursors=[s.cursor for s in self.streams],
            filter_state=self._session.save_state(self._fstate),
            buffer=self._buffer.copy(),
            batches_emitted=self.batches_emitted,
            rows_in=self.rows_in,
            rows_pass=self.rows_pass,
        )

    def restore(self, st: ShardedPipelineState) -> None:
        if len(st.stream_cursors) != len(self.streams):
            # elastic S→S′ rescale: the filter state reshards through the
            # session (accumulators split/merged — sums, so exact; see
            # core.session); every new round-robin stream partition resumes
            # at the next unconsumed GLOBAL batch index (the max cursor —
            # all source shards have walked the indices below it).
            cursor = max(int(c) for c in st.stream_cursors)
            for stream in self.streams:
                stream.cursor = cursor
        else:
            for stream, cur in zip(self.streams, st.stream_cursors):
                stream.cursor = int(cur)
        self._fstate = self._session.restore_state(st.filter_state)
        self._buffer = st.buffer.copy()
        self.batches_emitted = st.batches_emitted
        self.rows_in = st.rows_in
        self.rows_pass = st.rows_pass

    # -------------------------------------------------------------- iteration
    def __iter__(self) -> Iterator[dict]:
        iters = [iter(s) for s in self.streams]
        while True:
            rbs = []
            for it in iters:
                rb = next(it, None)
                if rb is None:          # a shard ran dry → stream over
                    return
                rbs.append(rb)
            cols = np.concatenate([rb.columns for rb in rbs], axis=1)
            payload, n_pass = self._filter_step(cols)
            self.rows_in += cols.shape[1]
            self.rows_pass += n_pass
            if self._device_tokenize:
                yield from self._emit_tokens(payload)
            else:
                yield from self._emit(payload)


def make_pipeline(session, *, total_rows: int, batch_rows: int,
                  batch_size: int, seq_len: int, vocab_size: int | None = None,
                  seed: int = 0, drift=None, tokens_per_row: int | None = None):
    """One ``FilterSession`` → its ingestion pipeline.

    Builds one round-robin ``LogStream`` partition per plan shard and
    returns a ``Pipeline`` (1 shard) or ``ShardedPipeline`` (shard_map over
    the session's mesh). Device tokenization follows the plan's
    ``tokenize`` spec — there is nothing to wire by hand:
    ``vocab_size``/``tokens_per_row`` default from it (they are only
    required here when the plan has no tokenize stage and the host
    tokenizer needs them).
    """
    from repro.data.stream import DriftConfig

    spec = session.plan.tokenize
    if vocab_size is None:
        if spec is None:
            raise ValueError("vocab_size is required when the plan has no "
                             "TokenizeSpec to default it from")
        vocab_size = spec.vocab_size
    if tokens_per_row is None:
        tokens_per_row = spec.tokens_per_row if spec is not None else 8
    drift = drift or DriftConfig()
    n = session.num_shards if session.sharded else 1
    streams = [LogStream(total_rows=total_rows, batch_rows=batch_rows,
                         seed=seed, drift=drift, shard_id=i, num_shards=n)
               for i in range(n)]
    kw = dict(batch_size=batch_size, seq_len=seq_len, vocab_size=vocab_size,
              tokens_per_row=tokens_per_row)
    if session.sharded:
        return ShardedPipeline(streams, session, **kw)
    return Pipeline(streams[0], session, **kw)
