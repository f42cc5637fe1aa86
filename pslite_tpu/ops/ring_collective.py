"""Fused ring push_pull kernel (Pallas, TPU): reduce-scatter + server
update + all-gather in ONE kernel over the ICI ring.

The XLA path of :class:`~pslite_tpu.parallel.engine.CollectiveEngine`
lowers ``push_pull`` to three ops (``psum_scatter`` → handle →
``all_gather``): the reduced shard and the updated shard each make an HBM
round trip between ops, and the all-gather cannot start until the whole
update finishes.  This kernel is the TPU-native analog of the reference's
steady-state one-sided RDMA pipeline (rdma_transport.h:323-357 — data
WRITE + meta WRITE_WITH_IMM per hop, no intermediate copies): a single
ring program per device where

1. each reduce-scatter hop DMAs a chunk to the neighbor's VMEM and
   accumulates the incoming chunk (compute overlapped with the wire),
2. the server handle (``KVServerDefaultHandle`` semantics,
   kv_app.h:430-452) is applied in VMEM the moment the owned chunk's sum
   completes — no HBM round trip, and
3. the updated chunk immediately re-enters the ring as the all-gather
   payload while later chunks are still reducing.

**Bidirectional mode** (default): each chunk is split in half and the
halves travel the ring in opposite directions simultaneously — both ICI
link directions carry payload every step, doubling the per-hop bandwidth
exactly like XLA's own bidirectional collectives (and like the
reference's multi-rail MultiVan splits traffic across NICs,
multi_van.h:173-197).  The two directions are independent half-rings
whose remote DMAs are started back-to-back and waited together.

Flow control: two communication slots per direction per device with
credit semaphores — a sender may reuse slot ``k`` only after the receiver
signals that it has consumed the previous payload in ``k`` (the ring
neighbors otherwise have no back-pressure and a fast sub-ring could
clobber an unread slot; the reference's AddressPool plays the same role
for RDMA imm slots, van_common.h:72-122).

VMEM: the kernel keeps the whole per-device chunk resident six times
over (store in, store out, send, two receive slots, grads staging), so the
chunk it can serve is bounded by :data:`VMEM_BUDGET_BYTES`; a larger chunk
is refused by name before Mosaic sees it.

``interpret`` is the caller's decision: the engine passes the rule it
derived from its mesh (TPU mesh → Mosaic, anything else → the Pallas TPU
interpreter, which runs the full semaphore/DMA protocol on the virtual CPU
mesh for the unit tests).  Nothing here looks at the process default
backend.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

_LANES = 128
_SUBLANES = 8
_TILE = _LANES * _SUBLANES  # minimum chunk granularity (fp32 elements)

# What the kernel may ask Mosaic for.  A v5e TensorCore has 128 MiB of
# VMEM; the rest is left to the compiler's own scratch and to the XLA ops
# scheduled around the kernel.  Mosaic's default scoped limit is 16 MiB,
# so the kernel states its need (``vmem_limit_bytes``) instead of relying
# on it.  Chips with less VMEM refuse the request in Mosaic's own words.
VMEM_BUDGET_BYTES = 96 << 20
_VMEM_DEFAULT_LIMIT_BYTES = 16 << 20
_VMEM_HEADROOM_BYTES = 2 << 20  # semaphores, DMA descriptors, spills


def derive_collective_id(*key_parts) -> int:
    """Deterministic collective_id in [1, 31] for a ring program.

    Concurrently dispatched collective kernels sharing an id share the
    global barrier semaphore, so distinct programs should get distinct
    ids.  The id must ALSO be identical for the same logical program in
    every process of a multi-process mesh (each process compiles its own
    copy; mismatched ids would pair mismatched barrier semaphores across
    devices) — hence a stable hash of the program key rather than a
    process-local counter.  Collisions degrade to a shared barrier
    semaphore, which stays correct under the engine's consistent
    dispatch ordering — never incorrect, only less isolated."""
    import zlib

    text = "|".join(str(p) for p in key_parts)
    return 1 + (zlib.crc32(text.encode()) % 31)


def ring_chunk_len(total_len: int, num_devices: int, dtype=None,
                   bidir: bool = True, compress: bool = False) -> int:
    """Per-device chunk length (elements) the kernel will use for a
    bucket of ``total_len`` elements: ceil to the VMEM tile — (8, 128)
    for 4-byte dtypes, (16, 128) for 2-byte (bf16) sublane packing,
    (32, 128) for int8-compressed payloads — doubled in bidirectional
    mode so each half-chunk stays tiled."""
    tile = _TILE
    if compress:
        tile = 4 * _TILE  # int8 comm buffers need (32, 128) tiles
    elif dtype is not None and jnp.dtype(dtype).itemsize == 2:
        tile = 2 * _TILE
    if bidir:
        tile = 2 * tile
    chunk = -(-total_len // num_devices)
    return -(-chunk // tile) * tile


def _kernel_body(n: int, axis_name: str, handle: Callable, ndir: int,
                 with_ag: bool = True, compress: bool = False,
                 mesh_axes=None):
    """Build the unrolled kernel for a static ring size ``n`` with
    ``ndir`` directions (1 = clockwise only, 2 = bidirectional halves).
    ``with_ag=False`` builds the push-only variant: reduce-scatter +
    fused update, no all-gather phase and no pulled output ref.
    ``compress=True`` quantizes every hop payload to int8 with a per-hop
    absmax scale riding in a sidecar buffer — 4x fewer wire bytes.

    Refs (per device d; rows = chunk rows, h = rows // ndir):
      grads_ref   ANY  [n*rows, 128] — my worker row, n chunks
      store_ref   VMEM [rows, 128]   — my store shard (chunk d)
      out_store   VMEM [rows, 128]
      out_pulled  ANY  [n*rows, 128] — replicated result
      send_buf    VMEM [ndir, h, 128]     (int8 [ndir, h+32, 128] when
      recv_buf    VMEM [ndir, 2, h, 128]   compressed: payload rows plus
                                           32 int8 rows carrying the f32
                                           absmax scale, bitcast — ONE
                                           DMA per hop, scale embedded)
      gchunk      VMEM [ndir, h, 128] — staging for grads half-chunks
      send_sem/recv_sem  DMA((ndir, 2))
      cap_sem     REGULAR((ndir, 2)) — credits from the downstream peer
      local_sem   DMA(())            — HBM<->VMEM staging copies

    Direction 0 sends to the RIGHT neighbor (receives from left);
    direction 1 sends to the LEFT (receives from right).  Per direction
    ``dir`` the chunk schedule mirrors:
      RS step t   : send chunk (d -+ (1 + t)) % n
      owned chunk : d (both directions — each owns its half)
      AG step s2  : send chunk (d -+ s2) % n
    (``-`` for dir 0, ``+`` for dir 1).

    Compressed semantics: reduce-scatter partial sums are re-quantized
    at every hop (error O(hops), the usual compressed-all-reduce
    trade-off); the all-gather payload is quantized ONCE at the owner
    and forwarded verbatim, and every device — including the owner —
    writes the DEQUANTIZED payload to the pulled output so the
    replicated result is identical everywhere.  The store update itself
    applies to the dequantized sum at full precision.

    ``mesh_axes`` (ordered (name, size) pairs covering the WHOLE mesh)
    generalizes the ring to one axis of a multi-axis torus: remote DMAs
    address devices by LOGICAL id = the row-major flat index over the
    full mesh, so a ring along ``axis_name`` must translate ring
    positions through the device's coordinates on the other axes.  A
    (dp=A, kv=B) mesh then runs B independent size-A rings concurrently
    in ONE kernel launch — per-column sub-rings, the torus analog of
    the reference's per-device multi-rail contexts
    (ucx_van.h:938-1006, multi_van.h:173-197).  None = 1-D mesh
    (identity mapping).
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(grads_ref, store_ref, out_store_ref, *rest):
        if with_ag:
            out_pulled_ref, rest = rest[0], rest[1:]
        (send_buf, recv_buf, gchunk, send_sem, recv_sem, cap_sem,
         local_sem) = rest
        d = lax.axis_index(axis_name)

        def logical_of(ring_pos):
            """Flat mesh index of the device at ``ring_pos`` on my ring
            (my coordinates on every other axis, ring_pos on ours)."""
            if mesh_axes is None:
                return ring_pos
            idx = None
            for name, size in mesh_axes:
                coord = (
                    ring_pos if name == axis_name
                    else lax.axis_index(name)
                )
                idx = coord if idx is None else idx * size + coord
            return idx

        right = logical_of(lax.rem(d + 1, n))
        left = logical_of(lax.rem(d + n - 1, n))
        rows = store_ref.shape[0]
        h = rows // ndir
        dirs = range(ndir)

        def send_peer(dr):
            return right if dr == 0 else left

        def credit_peer(dr):
            # The device whose sends I consume (upstream): I signal it
            # when one of MY slots frees; MY credits arrive from my
            # downstream peer symmetrically.
            return left if dr == 0 else right

        def rs_chunk(dr, t):
            # Chunk sent at RS step t (also the chunk RECEIVED at t-1
            # plus my own contribution); t = n-1 yields the owned chunk d.
            if dr == 0:
                return lax.rem(d + n - 1 - t, n)
            return lax.rem(d + 1 + t, n)

        def ag_chunk(dr, s2):
            # Chunk sent at AG step s2 (s2=0 is my updated chunk d).
            if dr == 0:
                return lax.rem(d - s2 + n, n)
            return lax.rem(d + s2, n)

        # Ring-entry barrier: a fast neighbor must not DMA into our
        # scratch before this invocation owns it.
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(
            barrier, inc=1, device_id=left,
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_signal(
            barrier, inc=1, device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(barrier, 2)

        def stage_grads(dr, chunk_idx):
            """DMA my grads half-chunk (dynamic index) HBM -> gchunk."""
            cp = pltpu.make_async_copy(
                grads_ref.at[pl.ds(chunk_idx * rows + dr * h, h)],
                gchunk.at[dr],
                local_sem,
            )
            cp.start()
            cp.wait()

        def write_pulled(dr, chunk_idx, src_ref):
            cp = pltpu.make_async_copy(
                src_ref,
                out_pulled_ref.at[pl.ds(chunk_idx * rows + dr * h, h)],
                local_sem,
            )
            cp.start()
            cp.wait()

        def start_send(dr, t):
            """Start the remote DMA of send_buf[dr] into the peer's
            recv slot t%2 (compressed payloads carry their scale in the
            trailing rows — still one DMA); returns the handles for a
            later wait."""
            if t >= 2:
                # Credit: my downstream peer freed its slot t%2 (t-2).
                pltpu.semaphore_wait(cap_sem.at[dr, t % 2], 1)
            rdma = pltpu.make_async_remote_copy(
                src_ref=send_buf.at[dr],
                dst_ref=recv_buf.at[dr, t % 2],
                send_sem=send_sem.at[dr, t % 2],
                recv_sem=recv_sem.at[dr, t % 2],
                device_id=send_peer(dr),
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            rdma.start()
            return [rdma]

        def quantize_to_send(dr, vals):
            """Write vals (f32 [h,128]) into send_buf[dr]: int8 payload
            in the leading rows, the f32 absmax scale bitcast into the
            trailing 32 int8 rows."""
            amax = jnp.max(jnp.abs(vals))
            scale = jnp.maximum(amax / 127.0, 1e-30)
            q = jnp.clip(jnp.round(vals / scale), -127, 127)
            send_buf[dr, :h] = q.astype(jnp.int8)
            send_buf[dr, h:] = pltpu.bitcast(
                jnp.full((_SUBLANES, _LANES), scale, jnp.float32),
                jnp.int8,
            )

        def _embedded_scale(buf_rows):
            """f32 scale from a compressed buffer's trailing rows."""
            return pltpu.bitcast(buf_rows, jnp.float32)[0, 0]

        def dequant_recv(dr, slot):
            """f32 view of the compressed payload in recv slot."""
            scale = _embedded_scale(recv_buf[dr, slot, h:])
            return recv_buf[dr, slot, :h].astype(jnp.float32) * scale

        def free_slot(dr, k):
            """Tell my upstream peer its outgoing slot k is consumable."""
            pltpu.semaphore_signal(
                cap_sem.at[dr, k], inc=1, device_id=credit_peer(dr),
                device_id_type=pltpu.DeviceIdType.LOGICAL)

        # ---- phase 1: ring reduce-scatter (steps 0..n-2) ----------------
        for t in range(n - 1):
            rdmas = []
            for dr in dirs:
                stage_grads(dr, rs_chunk(dr, t))
                if t == 0:
                    if compress:
                        quantize_to_send(dr, gchunk[dr])
                    else:
                        send_buf[dr] = gchunk[dr]
                else:
                    if compress:
                        acc = dequant_recv(dr, (t - 1) % 2) + gchunk[dr]
                        quantize_to_send(dr, acc)
                    else:
                        send_buf[dr] = (
                            recv_buf[dr, (t - 1) % 2] + gchunk[dr]
                        )
                    free_slot(dr, (t - 1) % 2)
                rdmas.extend(start_send(dr, t))
            for rdma in rdmas:
                rdma.wait()

        # ---- boundary: own chunk complete -> apply the server handle ----
        updated = []
        for dr in dirs:
            stage_grads(dr, d)
            if n >= 2:
                if compress:
                    summed = dequant_recv(dr, (n - 2) % 2) + gchunk[dr]
                else:
                    summed = recv_buf[dr, (n - 2) % 2] + gchunk[dr]
                free_slot(dr, (n - 2) % 2)
            else:
                summed = gchunk[dr]
            # Elementwise handle: applying per half == applying whole.
            up = handle(store_ref[pl.ds(dr * h, h)], summed)
            updated.append(up)
            out_store_ref[pl.ds(dr * h, h)] = up
            if with_ag and (not compress or n == 1):
                # Compressed owners write their chunk during AG s2==0
                # instead (the dequantized view — every device must see
                # the identical replicated result).
                write_pulled(dr, d, out_store_ref.at[pl.ds(dr * h, h)])

        if not with_ag:
            # Push-only: no all-gather phase.  Drain the un-consumed
            # credits (one per slot that received at least once) so the
            # scratch semaphores exit at zero.
            if n >= 2:
                for dr in dirs:
                    pltpu.semaphore_wait(cap_sem.at[dr, 0], 1)
                    if n >= 3:
                        pltpu.semaphore_wait(cap_sem.at[dr, 1], 1)
            return

        # ---- phase 2: ring all-gather of updated chunks -----------------
        # Compressed: quantize ONCE at the owner (s2==0), forward the
        # int8 payload verbatim afterwards — no per-hop re-quantization
        # error in this phase.
        for s2 in range(n - 1):
            t = n - 1 + s2
            rdmas = []
            for dr in dirs:
                if s2 == 0:
                    if compress:
                        quantize_to_send(dr, updated[dr])
                        gchunk[dr] = (
                            send_buf[dr, :h].astype(jnp.float32)
                            * _embedded_scale(send_buf[dr, h:])
                        )
                        write_pulled(dr, d, gchunk.at[dr])
                    else:
                        send_buf[dr] = updated[dr]
                else:
                    # Forward verbatim (compressed: payload + embedded
                    # scale travel as one buffer — no re-quantization).
                    send_buf[dr] = recv_buf[dr, (t - 1) % 2]
                    if compress:
                        gchunk[dr] = dequant_recv(dr, (t - 1) % 2)
                        write_pulled(dr, ag_chunk(dr, s2), gchunk.at[dr])
                    else:
                        write_pulled(dr, ag_chunk(dr, s2),
                                     send_buf.at[dr])
                    free_slot(dr, (t - 1) % 2)
                rdmas.extend(start_send(dr, t))
            for rdma in rdmas:
                rdma.wait()
        if n >= 2:
            last = 2 * (n - 1) - 1
            for dr in dirs:
                # Final arrival: chunk (d -+ (n-1)) % n.
                if compress:
                    gchunk[dr] = dequant_recv(dr, last % 2)
                    write_pulled(dr, ag_chunk(dr, n - 1), gchunk.at[dr])
                else:
                    send_buf[dr] = recv_buf[dr, last % 2]
                    write_pulled(dr, ag_chunk(dr, n - 1),
                                 send_buf.at[dr])
                free_slot(dr, last % 2)
                # Drain the one un-consumed credit per slot (the credits
                # for the final sends have no matching wait) so the
                # scratch semaphores are zero at kernel exit — leftover
                # counts would poison the next collective kernel.
                pltpu.semaphore_wait(cap_sem.at[dr, 0], 1)
                pltpu.semaphore_wait(cap_sem.at[dr, 1], 1)

    return kernel


def ring_vmem_bytes(chunk: int, dtype, bidir: bool = True,
                    compress: bool = False) -> int:
    """VMEM the kernel holds for a per-device chunk of ``chunk`` elements:
    store in + store out + grads staging at the bucket dtype, and send +
    two receive slots at the wire dtype (int8 plus one scale tile per
    direction when compressed)."""
    ndir = 2 if bidir else 1
    itemsize = jnp.dtype(dtype).itemsize
    if compress:
        comm = chunk + ndir * 4 * _TILE  # int8 payload + scale rows
    else:
        comm = chunk * itemsize
    return 3 * chunk * itemsize + 3 * comm


def _ring_call(grads_chunks, store_chunk, handle: Callable,
               axis_name: str, num_devices: int, collective_id,
               bidir: bool, with_ag: bool, interpret: bool,
               compress: bool = False, mesh_axes=None):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = num_devices
    ndir = 2 if bidir else 1
    chunk = store_chunk.shape[0]
    if compress and store_chunk.dtype != jnp.float32:
        raise ValueError("int8 wire compression requires float32 stores")
    if compress:
        min_tile = 4 * _TILE * ndir
    else:
        min_tile = _TILE * ndir * (
            2 if store_chunk.dtype.itemsize == 2 else 1
        )
    if chunk % min_tile:
        raise ValueError(
            f"chunk {chunk} not a multiple of {min_tile} "
            f"(bidir={bidir}, compress={compress}, "
            f"dtype={store_chunk.dtype})"
        )
    vmem_need = (
        ring_vmem_bytes(chunk, store_chunk.dtype, bidir, compress)
        + _VMEM_HEADROOM_BYTES
    )
    if vmem_need > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"ring kernel: a per-device chunk of {chunk} "
            f"{store_chunk.dtype} elements needs {vmem_need} bytes of "
            f"VMEM (six chunk-sized buffers) but the kernel's budget is "
            f"{VMEM_BUDGET_BYTES} bytes; split the bucket (the ResNet-50 "
            f"trace uses 4 MiB buckets) or run it with impl='xla'"
        )
    if collective_id is None:
        collective_id = derive_collective_id(
            n, chunk, str(store_chunk.dtype), ndir, with_ag, compress
        )
    rows = chunk // _LANES
    h = rows // ndir
    dtype = store_chunk.dtype
    comm_dtype = jnp.int8 if compress else dtype
    g2 = grads_chunks.reshape(n * rows, _LANES)
    s2 = store_chunk.reshape(rows, _LANES)

    out_shape = [jax.ShapeDtypeStruct((rows, _LANES), dtype)]
    out_specs = [pl.BlockSpec(memory_space=pltpu.VMEM)]
    if with_ag:
        out_shape.append(jax.ShapeDtypeStruct((n * rows, _LANES), dtype))
        out_specs.append(pl.BlockSpec(memory_space=pl.ANY))

    # Compressed comm buffers append 32 int8 rows (one bitcast f32
    # (8, 128) tile) carrying the absmax scale — one DMA moves both.
    comm_rows = h + 4 * _SUBLANES if compress else h
    scratch = [
        pltpu.VMEM((ndir, comm_rows, _LANES), comm_dtype),     # send_buf
        pltpu.VMEM((ndir, 2, comm_rows, _LANES), comm_dtype),  # recv_buf
        pltpu.VMEM((ndir, h, _LANES), dtype),                  # gchunk
        pltpu.SemaphoreType.DMA((ndir, 2)),                    # send_sem
        pltpu.SemaphoreType.DMA((ndir, 2)),                    # recv_sem
        pltpu.SemaphoreType.REGULAR((ndir, 2)),                # cap_sem
        pltpu.SemaphoreType.DMA,                               # local_sem
    ]

    kernel = _kernel_body(n, axis_name, handle, ndir, with_ag=with_ag,
                          compress=compress, mesh_axes=mesh_axes)
    outs = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shape),
        in_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        out_specs=tuple(out_specs),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id,
            vmem_limit_bytes=max(_VMEM_DEFAULT_LIMIT_BYTES, vmem_need),
        ),
        interpret=(
            pltpu.InterpretParams(dma_execution_mode="eager")
            if interpret else False
        ),
    )(g2, s2)
    if with_ag:
        return outs[0].reshape(chunk), outs[1].reshape(n * chunk)
    return outs[0].reshape(chunk)


def ring_push_pull(grads_chunks, store_chunk, handle: Callable,
                   axis_name: str, num_devices: int, *, interpret: bool,
                   collective_id: int = None, bidir: bool = True,
                   compress: bool = False, mesh_axes=None):
    """Run the fused RS+update+AG ring inside a shard_map body.

    Args (per-device views inside shard_map):
      grads_chunks: [n, chunk] — my worker row viewed as n ring chunks
                    (``chunk`` must satisfy :func:`ring_chunk_len` for
                    the chosen ``bidir`` mode and dtype).
      store_chunk:  [chunk]    — my store shard.
      handle:       jittable (store_chunk, summed_grads) -> new_store
                    applied blockwise in VMEM (elementwise-safe handles
                    only: padding lanes flow through it, and in
                    bidirectional mode it runs once per half-chunk).
      bidir:        split each chunk across both ring directions (both
                    ICI link directions utilized — the default).
      mesh_axes:    ordered (name, size) pairs of the FULL mesh when the
                    ring runs along one axis of a multi-axis torus (see
                    :func:`_kernel_body`); None for a 1-D mesh.
      interpret:    run under the Pallas TPU interpreter (any mesh that
                    is not a TPU mesh) instead of compiling with Mosaic.
    Returns (new_store_chunk [chunk], pulled [n*chunk]).
    """
    return _ring_call(grads_chunks, store_chunk, handle, axis_name,
                      num_devices, collective_id, bidir, with_ag=True,
                      interpret=interpret, compress=compress,
                      mesh_axes=mesh_axes)


def ring_push(grads_chunks, store_chunk, handle: Callable,
              axis_name: str, num_devices: int, *, interpret: bool,
              collective_id: int = None, bidir: bool = True,
              compress: bool = False, mesh_axes=None):
    """Push-only ring: reduce-scatter + fused server update, no
    all-gather (the ``ZPush`` leg alone).  Same contract as
    :func:`ring_push_pull`; returns just the new store chunk.

    (There is deliberately no pull-only ring: a bare all-gather has no
    update to fuse, so XLA's native all_gather is already optimal.)
    """
    return _ring_call(grads_chunks, store_chunk, handle, axis_name,
                      num_devices, collective_id, bidir, with_ag=False,
                      interpret=interpret, compress=compress,
                      mesh_axes=mesh_axes)
