"""Multithreaded file-backed shuffle mode.

Reference: SURVEY.md §2.10 — RapidsShuffleThreadedWriterBase:228 /
ReaderBase:504 (thread-pooled parallel writers/readers over Spark shuffle
files, with BytesInFlightLimiter:574). This is the middle of the three
shuffle modes: rows leave the device once (serialize), land in per-
(mapper, reducer) framed files via the writer pool, and reducers decode
with a reader pool — the shape that scales past one process and feeds the
DCN path, with the in-flight byte limiter bounding host memory.
"""

from __future__ import annotations

import concurrent.futures as cf
import os
import threading
import uuid
from typing import Iterator, List, Optional

import jax

from ..batch import ColumnarBatch, Schema, bucket_capacity
from ..exec.base import Exec
from ..exec.common import concat_batches
from ..expressions.base import EvalContext
from .exchange import PartitioningExchangeExec
from .partitioning import Partitioning, RangePartitioning
from .serializer import deserialize_batch, serialize_batch
from .transport import BlockMissingError, PeerUnreachableError


class BytesInFlightLimiter:
    """Bounds serialized bytes buffered across the writer pool
    (reference: BytesInFlightLimiter — backpressure, not a hard error)."""

    def __init__(self, limit: int = 512 << 20):
        self.limit = limit
        self._used = 0
        self._cv = threading.Condition()

    def acquire(self, n: int) -> None:
        with self._cv:
            while self._used + n > self.limit and self._used > 0:
                self._cv.wait()
            self._used += n

    def release(self, n: int) -> None:
        with self._cv:
            self._used -= n
            self._cv.notify_all()


class MultithreadedShuffleExchangeExec(PartitioningExchangeExec):
    """Shuffle through framed spill files with writer/reader thread pools."""

    def __init__(self, partitioning: Partitioning, child: Exec,
                 shuffle_dir: Optional[str] = None,
                 num_threads: int = 8,
                 reader_threads: Optional[int] = None,
                 max_in_flight_fetches: Optional[int] = None,
                 max_bytes_in_flight: int = 512 << 20,
                 ctx: Optional[EvalContext] = None,
                 transport=None,
                 read_transport=None,
                 codec: Optional[str] = None,
                 replicas: int = 0,
                 lineage_enabled: bool = True,
                 lineage_registry=None):
        super().__init__(partitioning, child, ctx)
        self.shuffle_dir = shuffle_dir or os.path.join(
            "/tmp/rapids_tpu_shuffle", uuid.uuid4().hex)
        self.num_threads = num_threads
        self.reader_threads = reader_threads or num_threads
        #: bound on concurrently outstanding transport fetches
        #: (spark.rapids.tpu.shuffle.transport.maxInFlightFetches)
        self.max_in_flight_fetches = \
            max_in_flight_fetches or self.reader_threads
        self.codec = codec
        self.limiter = BytesInFlightLimiter(max_bytes_in_flight)
        self._written = False
        self._write_lock = threading.Lock()
        # blocks ride a pluggable transport (reference:
        # RapidsShuffleTransport); default = shared-filesystem blocks
        if transport is None:
            from .transport import LocalFsTransport
            transport = LocalFsTransport(self.shuffle_dir)
            self._owns_transport = True
        else:
            self._owns_transport = False
        self.transport = transport
        # cross-process shape: the map side publishes into ``transport``
        # (this executor's block server) while reducers pull through
        # ``read_transport`` — a fetching client whose peer table sees
        # the map side over the wire. Defaults to the same transport
        # (single-process: local fast path).
        self.read_transport = read_transport or transport
        # random 63-bit id: per-process counters COLLIDE when two
        # processes share one transport root (cross-process mode)
        self.shuffle_id = uuid.uuid4().int & ((1 << 63) - 1)
        #: conf-gated map-output replication (shuffle.replicas): pieces
        #: are pushed to K peers at publish so a dead primary's blocks
        #: are served by failover, with recompute as the floor
        self.replicas = max(int(replicas), 0)
        # lineage (shuffle.lineage.enabled): every map output records
        # its producing fragment so the read side can recompute a lost
        # block deterministically once transport failover is exhausted.
        # The recompute contract: the CHILD must be re-executable (true
        # of the data plane's execs — scans re-read, exchanges re-fetch
        # their still-published blocks).
        if lineage_enabled:
            from .lineage import lineage_registry as _global_registry
            self._lineage = lineage_registry or _global_registry()
        else:
            self._lineage = None

    # ------------------------------------------------------------------
    # write side (map tasks)
    # ------------------------------------------------------------------

    def _write_all(self) -> None:
        from ..trace import call_attached, capture, span
        with self._write_lock:
            if self._written:
                return
            schema = self.output_schema
            from ..trace import name_thread
            pool = cf.ThreadPoolExecutor(self.num_threads,
                                         thread_name_prefix="shuffle-write",
                                         initializer=name_thread,
                                         initargs=("rtpu-shufw",))
            futures = []
            # writer-pool tasks inherit this thread's trace context so
            # their serializer.pack / transport.replicate spans join the
            # query's tree (tok is None — and the shim free — untraced)
            tok = capture()
            # map_id identifies one INPUT BATCH (child partition cp,
            # batch index bi) — the recompute unit: lineage re-executes
            # that fragment ONCE and re-slices every lost reduce
            # partition from it. Per-batch ids keep (map, reduce) keys
            # unique and preserve the read side's sorted concat order.
            if self._lineage is not None:
                # even a zero-batch child marks the shuffle as tracked:
                # an empty shuffle behind a dead peer must read as
                # provably empty, not fail its listing
                self._lineage.register_shuffle(self.shuffle_id)
            with span("shuffle.write", kind="shuffle",
                      shuffleId=self.shuffle_id):
                m = 0
                for cp in range(self.child.num_partitions):
                    bi = 0
                    for batch in self.child.execute_partition(cp):
                        if self._lineage is not None:
                            self._lineage.register_fragment(
                                self.shuffle_id, m,
                                self._make_recompute(cp, bi),
                                input_digest=self._fragment_digest(
                                    cp, bi))
                        for p, piece, _rows in self.split(batch):
                            futures.append(pool.submit(
                                call_attached, tok, self._write_piece,
                                piece, schema, m, p))
                        m += 1
                        bi += 1
                for f in futures:
                    f.result()
                pool.shutdown()
            self._written = True

    def _fragment_digest(self, cp: int, bi: int) -> str:
        """Input-split digest of one map fragment (the PR-10 fingerprint
        machinery): fragment coordinates + output schema — it names the
        recompute recipe in LineageVerificationError reports, so a
        nondeterministic fragment is identifiable across shuffles and
        plan shapes. The schema leg is hashed once per exchange, not
        per input batch (the registration runs on the write hot path)."""
        from ..plan.plancache import _hash
        sig = getattr(self, "_schema_sig", None)
        if sig is None:
            sig = _hash([[getattr(f, "name", str(i)), str(f.dtype)]
                         for i, f in enumerate(self.output_schema)])
            self._schema_sig = sig
        return f"{sig}:s{self.shuffle_id}:f{cp}.{bi}"

    def _make_recompute(self, cp: int, bi: int):
        """Deterministic recompute of lost blocks: re-execute the child
        partition stream to batch ``bi`` ONCE, slice every asked reduce
        partition from it with the SAME jitted kernels, serialize with
        the same codec — bit-for-bit the published bytes (hash
        partitioning and the frame format are both deterministic; the
        registry verifies the publish-time digests to prove it)."""
        schema = self.output_schema

        def recompute(reduce_ids):
            for i, batch in enumerate(self.child.execute_partition(cp)):
                if i == bi:
                    pieces = {p: piece for p, piece, _ in self.split(batch)}
                    return {r: serialize_batch(pieces[r], schema, self.codec)
                            if r in pieces else None for r in reduce_ids}
            return {}

        return recompute

    def _write_piece(self, piece: ColumnarBatch, schema: Schema,
                     map_id: int, reduce_id: int) -> None:
        data = serialize_batch(piece, schema,
                               self.codec)   # D2H + frame + compress
        self.limiter.acquire(len(data))
        try:
            if self._lineage is not None:
                # digest BEFORE publish: a peer death any time after the
                # block becomes fetchable must find its lineage complete
                self._lineage.note_block(self.shuffle_id, map_id,
                                         reduce_id, data)
            self.transport.publish(self.shuffle_id, map_id, reduce_id,
                                   data)
            if self.replicas > 0:
                self.transport.replicate(self.shuffle_id, map_id,
                                         reduce_id, data, self.replicas)
        finally:
            self.limiter.release(len(data))

    # ------------------------------------------------------------------
    # read side (reduce tasks)
    # ------------------------------------------------------------------

    def _reduce_blocks(self, p: int):
        """Block listing for one reducer: the transport's live listing
        UNIONED with lineage's authoritative set. The union is what
        makes a dead peer a recovery event instead of silent row loss —
        blocks the heartbeat registry stopped listing (dead executor)
        still surface here and get recomputed; and when the ONLY serving
        peer is unreachable, the lineage listing stands in for the raise
        the strict transport listing would otherwise be right to make."""
        lineage_blocks = [] if self._lineage is None else \
            self._lineage.blocks(self.shuffle_id, p)
        try:
            listed = self.read_transport.list_blocks(self.shuffle_id, p)
        except (BlockMissingError, PeerUnreachableError):
            if self._lineage is None or \
                    not self._lineage.knows_shuffle(self.shuffle_id):
                raise
            # lineage registered this shuffle: its listing is
            # authoritative even when EMPTY (a reducer that genuinely
            # received no rows) — the strict transport listing's raise
            # is survivable because no row can be silently dropped
            listed = []
        return sorted(set(listed) | set(lineage_blocks))

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        self._write_all()
        blocks = self._reduce_blocks(p)
        if not blocks:
            return
        schema = self.output_schema
        # pipelined fetch: decode each block the moment its bytes land
        # while later fetches keep streaming. With lineage on, a fetch
        # that exhausts failover recomputes the lost partition (riding
        # with_retry) and resumes bit-for-bit instead of raising; the
        # server's cancel flag (stop()/watchdog) is captured HERE on the
        # query thread and polled by the recovery loop.
        from ..trace import span
        if self._lineage is not None:
            from .lineage import current_cancel, fetch_many_with_recovery
            fetched = fetch_many_with_recovery(
                self.read_transport, blocks, self._lineage,
                max_in_flight=self.max_in_flight_fetches,
                republish=self.read_transport.publish,
                cancel=current_cancel())
        else:
            fetched = self.read_transport.fetch_many(
                blocks, max_in_flight=self.max_in_flight_fetches)
        with span("shuffle.read", kind="shuffle", partition=p,
                  blocks=len(blocks)):
            batches = [deserialize_batch(data, schema)
                       for _, data in fetched]
        total = sum(int(b.num_rows) for b in batches)
        if total == 0:
            return
        if len(batches) == 1:
            yield batches[0]
        else:
            yield concat_batches(batches, bucket_capacity(total))

    def cleanup(self) -> None:
        # always drop this shuffle's blocks (and their lineage — the
        # recompute closures pin the child exec tree otherwise); close
        # the transport only if this exec created it (an injected
        # transport may serve peers)
        self.transport.remove_shuffle(self.shuffle_id)
        if self.read_transport is not self.transport:
            # recovered blocks were republished into the reading
            # transport's local store; drop them with the shuffle
            self.read_transport.remove_shuffle(self.shuffle_id)
        if self._lineage is not None:
            self._lineage.remove_shuffle(self.shuffle_id)
        if self._owns_transport:
            self.transport.close()

    def do_close(self) -> None:
        self.cleanup()
