"""Chunked writes/reads for arrays larger than the chunk budget.

TPU-native analogue of the reference's
``torchsnapshot/io_preparers/chunked_tensor.py``
(/root/reference/torchsnapshot/io_preparers/chunked_tensor.py:35-128): arrays
above 512 MB (knob) split along dim 0 into chunk views, each written via the
array preparer to ``<path>_<offsets>``.  Chunking caps both staging-buffer
size (admission granularity for the memory budget) and per-file size, and —
crucially for replicated state — gives the partitioner sub-array units to
load-balance across ranks.

For jax device arrays the chunk view is a handle (``_LazyDeviceSlice``): the
stager slices on the device and transfers per chunk, on its worker, keeping
peak host memory at one chunk, not the whole array, and HBM at one slice a
staging worker beside the state.
"""

from __future__ import annotations

import time
from typing import Any, Iterable, List, Optional, Tuple

import numpy as np

from .. import phase_stats, serialization
from ..compression import is_framed
from ..io_types import Future, ReadReq, WriteReq
from ..manifest import Chunk, ChunkedTensorEntry, Shard, TensorEntry
from .array import ArrayAssembly, ArrayBufferConsumer, ArrayIOPreparer


class _LazySlice:
    """A dim-0 slice ``base[start:stop]`` of a jax.Array that is taken only
    when it is staged.  Exposes dtype/shape so write planning never touches
    the data."""

    def __init__(self, base: Any, start: int, stop: int) -> None:
        self._base = base
        self._start = start
        self._stop = min(stop, base.shape[0])

    @property
    def dtype(self):
        return np.dtype(self._base.dtype)

    @property
    def shape(self):
        return (self._stop - self._start,) + tuple(self._base.shape[1:])


class _LazyHostSlice(_LazySlice):
    """Of a host-resident array: ``np.asarray`` → numpy view of the cached
    host copy."""

    def __array__(self, dtype=None, copy=None):
        begin = time.monotonic()
        out = np.asarray(self._base)[self._start : self._stop]
        # Attributed as d2h: materializing the cached host copy is where a
        # host-offloaded chunked array's transfer cost actually lands (the
        # stager's np.asarray path has no other attribution point).  The
        # first chunk pays the base array's full read; byte counts are per
        # slice, so the totals reconcile across all chunks.
        phase_stats.add("d2h", time.monotonic() - begin, out.nbytes)
        if dtype is not None and out.dtype != np.dtype(dtype):
            out = out.astype(dtype)
        return out


class _LazyDeviceSlice(_LazySlice):
    """Of a device-resident array.  ``base[start:stop]`` is no view: it runs
    on the device and its result is a buffer of its own, so slicing every
    chunk at plan time kept a second copy of every chunked leaf in HBM for
    the length of the save (at Brumby widths 6.42 GB of slices beside a 7.77
    GB state: the save, not the restore, set the process's HBM peak;
    PERF.md, PR 31).  ``to_host`` runs on a staging worker: slice, transfer,
    let the slice go, so the slices alive are one a worker."""

    def to_host(self) -> np.ndarray:
        from .. import staging

        return staging.to_host(self._base[self._start : self._stop])

    def __array__(self, dtype=None, copy=None):
        out = self.to_host()
        return out if dtype is None else out.astype(dtype, copy=False)


def count_chunked(counter: str, entries: Iterable[Any]) -> None:
    """One occurrence of the counter ``chunked_read`` (a stateful's read
    plan) or ``chunked_write`` (a take's write plan): the ``bytes`` and
    ``leaves`` of the plan's chunked entries and how many ``chunks`` they are
    in.  A plan with none counts too, with zeros: ``n`` is plans."""
    chunked = [e for e in entries if isinstance(e, ChunkedTensorEntry)]
    phase_stats.add_counter(
        counter,
        0.0,
        sum(serialization.array_nbytes(e.shape, e.dtype) for e in chunked),
        leaves=len(chunked),
        chunks=sum(len(e.chunks) for e in chunked),
    )


class _ChunkedAssembly(ArrayAssembly):
    """A chunked leaf's assembly, with the phase ``chunk_assemble``: one
    interval a leaf, from the arrival of the first of its chunks (a consume
    begins) to the arrival of the last, when the buffer is whole and about
    to be handed on (to the H2D batcher for a jax target).  Both ends are on
    the read pipeline's loop thread.  The leaf's host buffer, a range of the
    restore's arena, is held from before the first and until its landing:
    ``host_buffer_wait`` and the chunks' reads come before this interval,
    ``h2d_window_wait``, ``h2d_dispatch`` and ``h2d_land`` after it."""

    _assembling: Optional[phase_stats.open_interval] = None

    def chunk_arrived(self) -> None:
        if self._assembling is None:
            self._assembling = phase_stats.open_interval("chunk_assemble")

    def finalize(self) -> None:
        if self._assembling is not None:
            self._assembling.close(self._nbytes)
        super().finalize()


class _ChunkConsumer(ArrayBufferConsumer):
    def consume_landed(self, buf: Any) -> bool:
        # The chunk has arrived either way (``consume_buffer`` comes next if
        # this says False, and a second call opens nothing).
        self._assembly.chunk_arrived()
        return super().consume_landed(buf)

    async def consume_buffer(self, buf: Any, executor: Optional[Any] = None) -> None:
        self._assembly.chunk_arrived()
        await super().consume_buffer(buf, executor)


class ChunkedArrayIOPreparer:
    @staticmethod
    def chunk_instructions(
        shape: List[int], dtype: Any, chunk_size_bytes: int
    ) -> List[Chunk]:
        """Split along dim 0 into pieces of at most ``chunk_size_bytes``
        (reference chunk_tensor, chunked_tensor.py:37-65).  0-d and arrays
        with an unsplittable dim-0 produce a single chunk."""
        dtype_str = serialization.dtype_to_string(np.dtype(dtype))
        total = serialization.array_nbytes(shape, dtype_str)
        if not shape or shape[0] <= 1 or total <= chunk_size_bytes:
            return [Chunk(offsets=[0] * len(shape), sizes=list(shape), dtype=dtype_str)]
        row_bytes = total // shape[0]
        rows_per_chunk = max(1, chunk_size_bytes // max(row_bytes, 1))
        chunks: List[Chunk] = []
        for start in range(0, shape[0], rows_per_chunk):
            rows = min(rows_per_chunk, shape[0] - start)
            chunks.append(
                Chunk(
                    offsets=[start] + [0] * (len(shape) - 1),
                    sizes=[rows] + list(shape[1:]),
                    dtype=dtype_str,
                )
            )
        return chunks

    @staticmethod
    def _slice0(obj: Any, start: int, stop: int) -> Any:
        from .. import staging
        from ..utils.host_offload import is_host_resident

        if staging.is_jax_array(obj) and is_host_resident(obj):
            # Device-slicing a pinned_host array is a mixed-memory-space
            # gather (rejected by XLA); materializing it here would stall
            # the caller with a full transfer.  Defer to staging time: jax
            # caches the base array's host copy, so N chunk slices cost one
            # read total.
            return _LazyHostSlice(obj, start, stop)
        if staging.is_jax_array(obj):
            return _LazyDeviceSlice(obj, start, stop)
        return obj[start:stop]

    @classmethod
    def prepare_write(
        cls,
        storage_path: str,
        obj: Any,
        chunking_instruction: List[Chunk],
        is_async_snapshot: bool = False,
    ) -> Tuple[ChunkedTensorEntry, List[WriteReq]]:
        write_reqs: List[WriteReq] = []
        chunks: List[Shard] = []
        for chunk in chunking_instruction:
            suffix = "_".join(str(x) for x in chunk.offsets)
            view = (
                cls._slice0(obj, chunk.offsets[0], chunk.offsets[0] + chunk.sizes[0])
                if chunk.offsets
                else obj
            )
            chunk_entry, chunk_write_reqs = ArrayIOPreparer.prepare_write(
                storage_path=f"{storage_path}_{suffix}",
                obj=view,
                is_async_snapshot=is_async_snapshot,
            )
            chunks.append(
                Shard(offsets=chunk.offsets, sizes=chunk.sizes, tensor=chunk_entry)
            )
            write_reqs += chunk_write_reqs
        dtype_str = chunks[0].tensor.dtype
        return (
            ChunkedTensorEntry(
                dtype=dtype_str,
                shape=list(np.shape(obj)),
                chunks=chunks,
                replicated=False,
            ),
            write_reqs,
        )

    @classmethod
    def prepare_read(
        cls,
        entry: ChunkedTensorEntry,
        obj_out: Optional[Any] = None,
        buffer_size_limit_bytes: Optional[int] = None,
        h2d_batch: Optional[Any] = None,
    ) -> Tuple[List[ReadReq], Future]:
        """Assemble all chunks into one host buffer / in-place target, then
        finalize (device_put for jax targets) once — mirrors reference
        chunked_tensor.py:111-128 with the jax H2D finalize added.
        ``h2d_batch``: the upload joins the cross-array batcher so its
        landing is paced and attributed like dense arrays' (without it, a
        chunked array's H2D landed outside every phase — the r4 blind spot,
        reintroduced via this path)."""
        pseudo_entry = TensorEntry(
            location="<chunked>",
            serializer=serialization.Serializer.BUFFER_PROTOCOL.value,
            dtype=entry.dtype,
            shape=entry.shape,
            replicated=entry.replicated,
        )
        assembly = _ChunkedAssembly(
            entry=pseudo_entry, obj_out=obj_out, h2d_batch=h2d_batch
        )
        itemsize = serialization.per_element_nbytes(entry.dtype)
        row_elems = int(np.prod(entry.shape[1:])) if len(entry.shape) > 1 else 1
        read_reqs: List[ReadReq] = []
        for chunk in entry.chunks:
            # dim-0 chunks are contiguous in the flat buffer
            if any(off != 0 for off in chunk.offsets[1:]):
                raise ValueError(
                    "ChunkedTensorEntry with non-dim-0 chunking is not supported"
                )
            flat_offset = chunk.offsets[0] * row_elems * itemsize if chunk.offsets else 0
            nbytes = serialization.array_nbytes(chunk.sizes, entry.dtype)
            tensor_entry = chunk.tensor
            # Read-into-place: dim-0 chunks map to contiguous slices of the
            # assembly, so storage can land the bytes directly (assembly
            # owns the policy — small chunks keep the slab merge path).
            # Framed (compressed) chunks can't: the stored frame is not the
            # payload bytes, so they read whole and decompress on consume.
            into = (
                None
                if is_framed(tensor_entry)
                else assembly.into_view(flat_offset, nbytes)
            )
            read_reqs.append(
                ReadReq(
                    path=tensor_entry.location,
                    byte_range=tensor_entry.byte_range,
                    buffer_consumer=_ChunkConsumer(
                        assembly=assembly,
                        flat_offset=flat_offset,
                        nbytes=nbytes,
                        checksum=tensor_entry.checksum,
                        location=tensor_entry.location,
                        into=into,
                        codec=tensor_entry.codec,
                        frame_nbytes=tensor_entry.compressed_nbytes,
                    ),
                    into=into,
                )
            )
        assembly.expect(len(read_reqs))
        return read_reqs, assembly.fut
