"""GSPMD-sharded array write planning + overlap-region resharding reads.

TPU-native replacement for BOTH of the reference's sharded preparers —
``torchsnapshot/io_preparers/sharded_tensor.py`` (:47-333) and
``torchsnapshot/io_preparers/dtensor.py`` (:62-278) — because in JAX every
distributed array is one thing: a ``jax.Array`` whose sharding maps global
index-boxes to devices.  There is no ShardedTensor/DTensor split to mirror.

Write: each process plans writes for its *addressable* distinct shards
(replicated copies of the same global box appear once).  Shards above the
shard-size knob are subdivided along their largest dim (reference
subdivide_shard, sharded_tensor.py:49-78) so staging granularity and file
size stay bounded; each piece is staged as a lazy device-slice so peak host
memory is one piece, and D2H DMAs for different pieces overlap.

Read: the resharding engine.  For every local target shard of ``obj_out`` we
compute the overlap box with every saved shard (pure index arithmetic, the
same math as the reference's
``_shards_get_overlap_region_wrt_saved_tensor``, sharded_tensor.py:81-127).
Each overlapping saved piece is read ONCE and scattered into all overlapping
target views (reference groups by location, sharded_tensor.py:197-271,
ShardedTensorBufferConsumer:301-333).  Targets: a sharded jax.Array (restored
via per-device ``device_put`` + ``make_array_from_single_device_arrays``), a
plain numpy array (assembled in place, reference :212-224), or None (fresh
host array).  Arbitrary source→target resharding falls out of the overlap
math, which is what makes elastic restore work (SURVEY.md §3.5).
"""

from __future__ import annotations

import asyncio
from concurrent.futures import Executor
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import knobs, serialization, staging
from ..compression import is_framed
from ..io_types import (
    BufferConsumer,
    BufferType,
    Future,
    IntoView,
    ReadReq,
    WriteReq,
)
from ..manifest import Shard, ShardedArrayEntry, TensorEntry
from ..serialization import Serializer
from .array import ArrayIOPreparer, _INTO_PLACE_MIN_BYTES


def _subdivide(
    offsets: Sequence[int],
    sizes: Sequence[int],
    dtype_str: str,
    max_shard_sz_bytes: int,
) -> List[Tuple[List[int], List[int]]]:
    """Split one shard box into pieces <= max_shard_sz_bytes along its largest
    dim (reference subdivide_shard, sharded_tensor.py:49-78)."""
    total = serialization.array_nbytes(list(sizes), dtype_str)
    if total <= max_shard_sz_bytes or not sizes:
        return [(list(offsets), list(sizes))]
    dim = int(np.argmax(sizes))
    if sizes[dim] <= 1:
        return [(list(offsets), list(sizes))]
    slice_bytes = total // sizes[dim]
    n_per_piece = max(1, max_shard_sz_bytes // max(slice_bytes, 1))
    pieces = []
    for start in range(0, sizes[dim], n_per_piece):
        n = min(n_per_piece, sizes[dim] - start)
        p_off = list(offsets)
        p_off[dim] += start
        p_sz = list(sizes)
        p_sz[dim] = n
        pieces.append((p_off, p_sz))
    return pieces


def _overlap(
    a_off: Sequence[int],
    a_sz: Sequence[int],
    b_off: Sequence[int],
    b_sz: Sequence[int],
) -> Optional[Tuple[List[int], List[int]]]:
    """Intersection box (offsets, sizes) of two boxes, or None (the
    reference's overlap-region math, sharded_tensor.py:81-127)."""
    starts, sizes = [], []
    for ao, asz, bo, bsz in zip(a_off, a_sz, b_off, b_sz):
        start = max(ao, bo)
        end = min(ao + asz, bo + bsz)
        if end <= start:
            return None
        starts.append(start)
        sizes.append(end - start)
    return starts, sizes


def _box_slices(
    box_off: Sequence[int], box_sz: Sequence[int], base_off: Sequence[int]
) -> Tuple[slice, ...]:
    return tuple(
        slice(o - b, o - b + s) for o, s, b in zip(box_off, box_sz, base_off)
    )


class ShardedArrayIOPreparer:
    @staticmethod
    def storage_path_for_piece(storage_path: str, offsets: Sequence[int]) -> str:
        return f"{storage_path}.{'_'.join(str(x) for x in offsets)}"

    @classmethod
    def prepare_write(
        cls,
        storage_path: str,
        obj: Any,
        is_async_snapshot: bool = False,
    ) -> Tuple[ShardedArrayEntry, List[WriteReq]]:
        from ..telemetry import trace as ttrace

        dtype_str = serialization.dtype_to_string(np.dtype(obj.dtype))
        max_shard_sz = knobs.get_max_shard_size_bytes()
        shards: List[Shard] = []
        write_reqs: List[WriteReq] = []
        with ttrace.span("plan_sharded", path=storage_path):
            for offsets, data in staging.local_shards(obj):
                sizes = list(data.shape)
                for p_off, p_sz in _subdivide(
                    offsets, sizes, dtype_str, max_shard_sz
                ):
                    if list(p_off) == list(offsets) and p_sz == sizes:
                        piece = data  # whole shard: no device slice dispatch
                    else:
                        piece = data[_box_slices(p_off, p_sz, offsets)]
                    location = cls.storage_path_for_piece(storage_path, p_off)
                    tensor_entry, piece_reqs = ArrayIOPreparer.prepare_write(
                        storage_path=location,
                        obj=piece,
                        is_async_snapshot=is_async_snapshot,
                    )
                    shards.append(
                        Shard(offsets=p_off, sizes=p_sz, tensor=tensor_entry)
                    )
                    write_reqs += piece_reqs

        spec = staging.partition_spec_of(obj)
        mesh_shape, axis_names, partition_spec = spec if spec else (None, None, None)
        entry = ShardedArrayEntry(
            dtype=dtype_str,
            shape=list(obj.shape),
            shards=shards,
            mesh_shape=mesh_shape,
            axis_names=axis_names,
            partition_spec=partition_spec,
        )
        return entry, write_reqs

    @classmethod
    def prepare_read(
        cls,
        entry: ShardedArrayEntry,
        obj_out: Optional[Any] = None,
    ) -> Tuple[List[ReadReq], Future]:
        if obj_out is not None and staging.is_jax_array(obj_out) and staging.is_sharded(obj_out):
            return cls._prepare_read_sharded(entry, obj_out)
        # Non-sharded target: assemble the full global array host-side
        # (reference sharded_tensor.py:212-224).
        restore = _ShardedRestore(entry=entry, obj_out=obj_out)
        target_off = [0] * len(entry.shape)
        restore.add_target(tuple(target_off), list(entry.shape))
        return cls._plan_reads(entry, restore)

    @classmethod
    def _prepare_read_sharded(
        cls, entry: ShardedArrayEntry, obj_out: Any
    ) -> Tuple[List[ReadReq], Future]:
        restore = _ShardedRestore(entry=entry, obj_out=obj_out)
        for offsets, data in staging.local_shards(obj_out):
            restore.add_target(tuple(offsets), list(data.shape))
        return cls._plan_reads(entry, restore)

    @classmethod
    def _scatter_for(
        cls,
        shard_offsets: Sequence[int],
        shard_sizes: Sequence[int],
        restore: "_ShardedRestore",
    ) -> List[Tuple[Tuple[int, ...], Tuple[slice, ...], Tuple[slice, ...]]]:
        scatter: List[
            Tuple[Tuple[int, ...], Tuple[slice, ...], Tuple[slice, ...]]
        ] = []
        for t_off, t_sz in restore.targets():
            ov = _overlap(shard_offsets, shard_sizes, t_off, t_sz)
            if ov is None:
                continue
            ov_off, ov_sz = ov
            scatter.append(
                (
                    t_off,
                    _box_slices(ov_off, ov_sz, shard_offsets),  # src view
                    _box_slices(ov_off, ov_sz, t_off),  # dst view
                )
            )
        return scatter

    @staticmethod
    def _partial_shard(shard: Shard, scatter) -> Optional[Shard]:
        """Shrink a saved piece to the contiguous dim-0 row span this
        rank's shard plan actually intersects — the plan-driven partial
        read.  A worker restoring a 1/64th slice of a replicated snapshot
        then issues a ranged read for 1/64th of the piece's bytes instead
        of paying for the whole entry (ROADMAP item 2; the resharding
        engine already computed the extents, this threads them down to
        the storage request).

        Returns the sub-piece as a new :class:`Shard` whose tensor entry
        carries the narrowed byte range, or None when the full read is the
        right call: raw buffer-protocol bytes only (a compression frame
        must be read whole to decode), row spans only (C-order makes a
        dim-0 span the one contiguous sub-box), and only when the saving
        clears the knob floor — the sub-entry drops its checksum (the
        recorded digest covers bytes this read skips), so tiny savings
        are not worth forgoing verification."""
        from .. import knobs

        tensor = shard.tensor
        if not knobs.partial_reads_enabled():
            return None
        if not shard.sizes or shard.sizes[0] <= 1:
            return None
        if tensor.serializer != Serializer.BUFFER_PROTOCOL.value:
            return None
        if is_framed(tensor):
            return None
        if list(tensor.shape) != list(shard.sizes):
            return None  # geometry mismatch: don't reason about its bytes
        r_lo = min(sv[0].start for _, sv, _ in scatter)
        r_hi = max(sv[0].stop for _, sv, _ in scatter)
        if r_lo <= 0 and r_hi >= shard.sizes[0]:
            return None  # the plan needs (nearly) every row anyway
        try:
            nbytes = serialization.array_nbytes(
                list(shard.sizes), tensor.dtype
            )
        except ValueError:
            return None
        row_bytes = nbytes // shard.sizes[0]
        if row_bytes * shard.sizes[0] != nbytes:
            return None
        saved = (shard.sizes[0] - (r_hi - r_lo)) * row_bytes
        if saved < knobs.get_partial_read_min_saved_bytes():
            return None
        if tensor.byte_range is not None and (
            tensor.byte_range[1] - tensor.byte_range[0] != nbytes
        ):
            return None  # stored extent disagrees with geometry
        base = tensor.byte_range[0] if tensor.byte_range is not None else 0
        sub_sizes = [r_hi - r_lo] + list(shard.sizes[1:])
        sub_offsets = list(shard.offsets)
        sub_offsets[0] += r_lo
        sub_tensor = TensorEntry(
            location=tensor.location,
            serializer=tensor.serializer,
            dtype=tensor.dtype,
            shape=sub_sizes,
            replicated=tensor.replicated,
            byte_range=[base + r_lo * row_bytes, base + r_hi * row_bytes],
            # The recorded digest covers the WHOLE stored payload; these
            # bytes are a strict subset, so there is nothing to verify
            # against (integrity.py's tiled-read precedent).
            checksum=None,
        )
        return Shard(offsets=sub_offsets, sizes=sub_sizes, tensor=sub_tensor)

    @classmethod
    def _plan_reads(
        cls, entry: ShardedArrayEntry, restore: "_ShardedRestore"
    ) -> Tuple[List[ReadReq], Future]:
        read_reqs: List[ReadReq] = []
        n_pieces = 0
        for shard in entry.shards:
            scatter = cls._scatter_for(shard.offsets, shard.sizes, restore)
            if not scatter:
                continue
            sub = cls._partial_shard(shard, scatter)
            if sub is not None:
                # Recompute the overlap views against the sub-piece box so
                # src slices index the (smaller) buffer the read returns.
                shard = sub
                scatter = cls._scatter_for(
                    shard.offsets, shard.sizes, restore
                )
            n_pieces += 1
            into = cls._into_view(restore, shard, scatter)
            read_reqs.append(
                ReadReq(
                    path=shard.tensor.location,
                    byte_range=shard.tensor.byte_range,
                    buffer_consumer=_ShardedArrayBufferConsumer(
                        restore=restore,
                        piece_entry=shard.tensor,
                        piece_offsets=list(shard.offsets),
                        piece_sizes=list(shard.sizes),
                        scatter=scatter,
                        into=into,
                    ),
                    into=IntoView(into) if into is not None else None,
                )
            )
        restore.expect(n_pieces)
        return read_reqs, restore.fut

    @staticmethod
    def _into_view(
        restore: "_ShardedRestore", shard: Shard, scatter
    ) -> Optional[memoryview]:
        """Read-into-place for the common resume-same-topology case: a saved
        piece that lands whole into one contiguous region of one target
        buffer (exact shard match, or a dim-0 subdivision of it) is read by
        storage directly into that memory — no deserialize, no scatter copy.
        Resharding restores (partial overlaps, multiple targets) keep the
        general scatter path."""
        if len(scatter) != 1:
            return None
        if shard.tensor.serializer != Serializer.BUFFER_PROTOCOL.value:
            return None
        if is_framed(shard.tensor):
            # Framed piece: the stored bytes are a compression frame, not
            # the payload — it must be read whole and decoded on consume.
            return None
        nbytes = serialization.array_nbytes(
            list(shard.sizes), shard.tensor.dtype
        )
        if nbytes < _INTO_PLACE_MIN_BYTES:
            return None
        t_off, src_view, dst_view = scatter[0]
        if any(
            s.start != 0 or s.stop != sz
            for s, sz in zip(src_view, shard.sizes)
        ):
            return None  # piece only partially consumed
        target = restore.buffer(t_off)
        dst = target[dst_view]
        if not dst.flags.c_contiguous or dst.nbytes != nbytes:
            return None
        try:
            return memoryview(dst).cast("B")
        except (TypeError, ValueError):
            return None


class _ShardedRestore:
    """Owns per-target-shard host assembly buffers; finalizes into the
    caller's target exactly once."""

    def __init__(self, entry: ShardedArrayEntry, obj_out: Optional[Any]) -> None:
        self.entry = entry
        self.obj_out = obj_out
        self.fut: Future = Future()
        self._buffers: Dict[Tuple[int, ...], np.ndarray] = {}
        self._target_sizes: Dict[Tuple[int, ...], List[int]] = {}
        self._pending = 0
        self._saved_dtype = serialization.string_to_dtype(entry.dtype)
        self._inplace_np = (
            isinstance(obj_out, np.ndarray)
            and obj_out.flags.writeable
            and obj_out.flags.c_contiguous
            and list(obj_out.shape) == list(entry.shape)
            and obj_out.dtype == self._saved_dtype
        )

    def add_target(self, offsets: Tuple[int, ...], sizes: List[int]) -> None:
        if offsets in self._buffers:
            return
        if self._inplace_np:
            self._buffers[offsets] = self.obj_out
        else:
            self._buffers[offsets] = np.empty(sizes, dtype=self._saved_dtype)
        self._target_sizes[offsets] = sizes

    def targets(self):
        return list(self._target_sizes.items())

    def buffer(self, offsets: Tuple[int, ...]) -> np.ndarray:
        return self._buffers[offsets]

    def expect(self, n: int) -> None:
        self._pending = n
        if n == 0:
            self.finalize()

    def piece_done(self) -> None:
        self._pending -= 1
        if self._pending == 0:
            self.finalize()

    def finalize(self) -> None:
        obj_out = self.obj_out
        if obj_out is None:
            self.fut.obj = self._buffers[tuple([0] * len(self.entry.shape))]
            return
        if isinstance(obj_out, np.ndarray):
            buf = self._buffers[tuple([0] * len(self.entry.shape))]
            if buf is not obj_out:
                if (
                    obj_out.flags.writeable
                    and list(obj_out.shape) == list(self.entry.shape)
                ):
                    np.copyto(obj_out, buf.astype(obj_out.dtype, copy=False))
                else:
                    self.fut.obj = buf
                    return
            self.fut.obj = obj_out
            return
        if staging.is_jax_array(obj_out):
            import jax

            if staging.is_sharded(obj_out):
                target_dtype = np.dtype(obj_out.dtype)
                memory_kind = getattr(obj_out.sharding, "memory_kind", None)
                shards = obj_out.addressable_shards
                bufs, targets = [], []
                for shard in shards:
                    offsets = tuple(
                        (idx.start or 0) if isinstance(idx, slice) else 0
                        for idx in shard.index
                    )
                    if len(shard.index) < obj_out.ndim:
                        offsets = tuple(0 for _ in range(obj_out.ndim))
                    buf = self._buffers[offsets]
                    if buf.dtype != target_dtype:
                        buf = buf.astype(target_dtype)
                    bufs.append(buf)
                    if memory_kind in (None, "device"):
                        targets.append(shard.device)
                    else:
                        # Preserve non-default memory kinds (pinned_host
                        # offloaded embeddings/optimizer state) exactly.
                        targets.append(
                            jax.sharding.SingleDeviceSharding(
                                shard.device, memory_kind=memory_kind
                            )
                        )
                from .. import phase_stats

                with phase_stats.timed(
                    "h2d_dispatch", sum(b.nbytes for b in bufs)
                ):
                    per_device = jax.device_put(bufs, targets)
                self.fut.obj = jax.make_array_from_single_device_arrays(
                    tuple(self.entry.shape), obj_out.sharding, per_device
                )
            else:
                buf = self._buffers[tuple([0] * len(self.entry.shape))]
                target_dtype = np.dtype(obj_out.dtype)
                if buf.dtype != target_dtype:
                    buf = buf.astype(target_dtype)
                self.fut.obj = jax.device_put(buf, obj_out.sharding)
            return
        self.fut.obj = self._buffers[tuple([0] * len(self.entry.shape))]


class _ShardedArrayBufferConsumer(BufferConsumer):
    """Deserializes one saved piece and scatters every overlap view into the
    target assembly buffers (reference ShardedTensorBufferConsumer,
    sharded_tensor.py:301-333)."""

    # Leaf consumer (1 read : 1 piece payload): read-fused digests apply.
    accepts_hash64 = True

    def __init__(
        self,
        restore: _ShardedRestore,
        piece_entry: TensorEntry,
        piece_offsets: List[int],
        piece_sizes: List[int],
        scatter: List[Tuple[Tuple[int, ...], Tuple[slice, ...], Tuple[slice, ...]]],
        into: Optional[memoryview] = None,
    ) -> None:
        self._restore = restore
        self._piece_entry = piece_entry
        self._piece_offsets = piece_offsets
        self._piece_sizes = piece_sizes
        self._scatter = scatter
        self._into = into
        self.precomputed_hash64: Optional[int] = None
        self.wants_read_hash = piece_entry.checksum is not None
        from .. import integrity

        self.hash_algo = integrity.hash_algo_of(piece_entry.checksum)

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        in_place = self._into is not None and buf is self._into

        def _work() -> None:
            from .. import integrity, phase_stats

            integrity.verify(
                buf,
                self._piece_entry.checksum,
                self._piece_entry.location,
                precomputed=self.precomputed_hash64,
            )
            if in_place:
                return  # storage already read the bytes into the target
            payload = memoryview(buf)
            if is_framed(self._piece_entry):
                # Checksum verified the frame (the stored bytes); decode it
                # back to the piece's payload before the overlap scatter.
                payload = serialization.decompress_staged(
                    buf,
                    serialization.array_nbytes(
                        self._piece_sizes, self._piece_entry.dtype
                    ),
                    self._piece_entry.location,
                )
            piece = serialization.array_from_memoryview(
                payload, self._piece_entry.dtype, self._piece_sizes
            )
            with phase_stats.timed(
                "scatter_copy",
                serialization.array_nbytes(
                    self._piece_sizes, self._piece_entry.dtype
                ),
            ):
                for t_off, src_view, dst_view in self._scatter:
                    target = self._restore.buffer(t_off)
                    target[dst_view] = piece[src_view]

        nbytes = serialization.array_nbytes(self._piece_sizes, self._piece_entry.dtype)
        if executor is not None and nbytes > 1 << 20:
            await asyncio.get_running_loop().run_in_executor(executor, _work)
        else:
            _work()
        self._restore.piece_done()

    def get_consuming_cost_bytes(self) -> int:
        nbytes = serialization.array_nbytes(
            self._piece_sizes, self._piece_entry.dtype
        )
        if is_framed(self._piece_entry):
            # Frame + decompressed payload coexist during decode.
            return nbytes + (self._piece_entry.compressed_nbytes or nbytes)
        return nbytes
