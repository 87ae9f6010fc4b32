"""Pallas ring allreduce over ICI inter-chip RDMA.

The TPU-native analog of the reference's custom chunked/pipelined allreduce
(SURVEY.md §3 C4, §4.2 — reconstructed, reference mount empty): where the
reference pipelined MPI_Isend/Irecv rings over chunks with CUDA-IPC intra-node
legs, this kernel drives the ICI links directly with async remote DMA and
double-buffered chunk slots.

Algorithm: classic bandwidth-optimal ring — (n-1) reduce-scatter steps then
(n-1) all-gather steps, each device moving one chunk of ``1/n`` of the tensor
per step, so total bytes-on-wire per device = ``2 (n-1)/n * size`` (the same
bound XLA's allreduce targets; the point of this kernel, as of the
reference's, is a *tunable, inspectable* implementation to benchmark against
the stock one, and a scaffold for fusing compute into collective steps).

Two allreduce schedules exist, selected statically per (shape, chunk_bytes):
the VMEM-resident kernels below stage the whole tensor in VMEM (fastest when
it fits); the CHUNKED kernel (``_ring_allreduce_chunked_kernel``) keeps the
tensor in HBM and streams ``config.chunk_bytes``-sized subchunks through
double-buffered VMEM slots with the next subchunk's RDMA already in flight —
the TPU analog of the reference's pipelined chunk loop (SURVEY.md §4.2), and
the only way a full ResNet-50-sized gradient can ride the custom backend.

Flow-control protocol per step (slot = step % 2):

  1. wait ``ack[slot]`` (skipped for the first two steps): the right
     neighbor has consumed this slot from the previous round, so the remote
     buffer is free — prevents the slot-reuse race in the naive pattern.
  2. RDMA my send-chunk into the right neighbor's ``comm[slot]``;
     ``wait()`` covers both my outgoing send and my incoming chunk
     (symmetric SPMD: every device runs the same step).
  3. combine/copy received chunk; signal ``ack[slot]`` to the left neighbor.

Registered with the selector as backend ``"pallas"`` for allreduce.  Tested
in Pallas TPU interpret mode on the CPU mesh (with ``detect_races=True`` —
the race-detection story, SURVEY.md §6.2) and runnable on real ICI unchanged.
The interpreter caps ring iterations (``_INTERPRET_MAX_ITERS``), so the
production-depth slot/ack protocol is additionally executed at FULL depth —
ResNet-50-gradient plans, C >= 50, adversarial interleavings, mutation
tests — by the pure-numpy schedule simulator in :mod:`.ring_sim`
(tests/test_ring_sim.py).
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import selector

# Chunk granularity: one (8, 128) f32 tile row group.  Chunks are laid out
# [rows, 128]; rows must be a multiple of 8 for clean VMEM tiling.
_LANES = 128
_SUBLANES = 8
_TILE = _LANES * _SUBLANES

# Interpret-mode state: None = auto-detect (interpret on CPU meshes, real
# Mosaic lowering on TPU), False = forced off, InterpretParams = forced on.
_INTERPRET = None

# Cap on total ring iterations (2*(n-1)*C) under the INTERPRETER only.
# Above ~45 the interpreter can deadlock on single-core hosts: each device's
# kernel runs on its own Python thread, but buffer-allocation callbacks block
# in np.array() on XLA-computed initial values, and with one XLA CPU
# execution thread a synchronously-blocking semaphore-wait callback starves
# the executor that would materialize them (observed: dev0 completed all 56
# iterations while 7 peers sat in _allocate_buffer).  Real Mosaic
# lowering has no such limit; when the
# plan exceeds the cap under interpret, subchunks are coarsened (C shrinks,
# sub_elems grows) — the simulated schedule stays chunked, just shallower.
_INTERPRET_MAX_ITERS = 28


class RingInterpretCoarseningWarning(UserWarning):
    """Interpret mode rewrote the configured ``chunk_bytes`` pipeline
    depth to stay inside ``_INTERPRET_MAX_ITERS`` — the executed simulated
    schedule is shallower than the one real TPU lowering will run."""


def set_interpret(params) -> None:
    """Control Pallas TPU interpret mode.

    ``InterpretParams(...)`` forces the interpreter (CPU simulation;
    supports ``detect_races``), ``False`` forces real lowering, ``None``
    restores auto-detection.
    """
    global _INTERPRET
    _INTERPRET = params


def local_kernel_params(interpret):
    """Interpret-mode-only compiler params for DEVICE-LOCAL pallas kernels.

    The pallas TPU interpreter runs an N-party global barrier before
    every kernel that lacks a ``collective_id`` ("the kernel doesn't
    specify its own barrier semaphore").  Device-local kernels (flash,
    fused-xent — in the ring/ulysses stacks the rotation happens OUTSIDE
    the kernel via ppermute) touch no remote memory, so that pre-kernel
    barrier is pure interpreter overhead, and on a starved host it is
    where the flaky full-suite abort parks its threads.
    Declaring a collective_id under interpret
    skips it; real TPU lowering is untouched (collective_id there
    allocates a cross-chip barrier semaphore local kernels must not
    claim).  Lives here next to :func:`_interpret_mode`, the shared
    interpret-mode decision point, so the skip logic exists exactly
    once.
    """
    if interpret:
        return pltpu.CompilerParams(collective_id=1)
    return None


def kernel_identity(name: str):
    """``metadata=`` of every ``pallas_call`` in ``ops/``: the kernel's
    identity, ``<family>.<role>`` (``flash.dkv``, ``ring.allreduce.chunked``).

    Pallas carries it into the custom call's
    ``frontend_attributes={kernel_metadata={"tm_kernel":"<name>"}}``, which
    is part of the instruction's HLO text and therefore of the name a TPU
    profile gives the kernel's device events: a trace reader finds a kernel
    by it whatever module or transform the call was traced under.  Not
    ``name=``, and no ``named_scope`` around a kernel: both go through the
    name stack and rename the instruction (``%SPAttention_0.<n>``,
    ``%jvp__.<n>``), which existing readers match on.  Beside
    :func:`local_kernel_params`: the one place the kernels' shared
    ``pallas_call`` arguments are decided.
    """
    return {"tm_kernel": name}


def _interpret_mode():
    """Explicit setting wins; in auto mode, enable the interpreter when the
    devices actually executing (the runtime mesh when initialized, else the
    default backend) are CPU — so `--backend pallas` works on simulated
    meshes even on hosts that also have an accelerator attached."""
    if _INTERPRET is not None:
        return _INTERPRET
    try:
        from .. import runtime

        if runtime.is_initialized():
            platform = list(
                runtime.current_mesh().devices.flat)[0].platform
        else:
            platform = jax.default_backend()
        if platform == "cpu":
            return pltpu.InterpretParams()
    except Exception:
        pass
    return False




def _step_indices(my, n: int, s: int, sign: int):
    """Chunk indices for ring step ``s`` (static) in direction ``sign``
    (+1 clockwise / send-right, -1 counter-clockwise / send-left; the ccw
    schedule is the cw one under my -> -my, chunk -> -chunk).  Covers both
    the reduce-scatter phase (s < n-1) and the all-gather phase."""
    if s < n - 1:
        send = lax.rem(my - sign * s + 4 * n, n)
        recv = lax.rem(my - sign * (s + 1) + 4 * n, n)
    else:
        t = s - (n - 1)
        send = lax.rem(my + sign * (1 - t) + 4 * n, n)
        recv = lax.rem(my - sign * t + 4 * n, n)
    return send, recv


def _pad_and_tile(flat, n: int):
    """Pad a flat vector to a multiple of n*TILE and tile as [n, rows, 128]."""
    pad = (-flat.shape[0]) % (n * _TILE)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(n, flat.shape[0] // n // _LANES, _LANES), pad


def runtime_chunk_bytes() -> int:
    from .. import runtime

    return runtime.effective_config().chunk_bytes


def _neighbor_setup(axis: str, mesh_axes, n: int):
    """Shared kernel preamble: ring neighbors, logical-id mapping, and the
    neighbor barrier (both neighbors inside the kernel before any RDMA).
    The subtlest part of these kernels lives in exactly one place."""
    my = lax.axis_index(axis)
    right = lax.rem(my + 1, n)
    left = lax.rem(my + n - 1, n)

    def coords(idx):
        # Flat logical device id of the ring neighbor: other mesh axes keep
        # our own position, the ring axis takes `idx` (row-major over the
        # mesh axis order, which is how LOGICAL ids are assigned).
        lid = jnp.int32(0)
        for a in mesh_axes:
            pos = idx if a == axis else lax.axis_index(a)
            lid = lid * lax.axis_size(a) + pos
        return lid

    bsem = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(bsem, inc=1, device_id=coords(left),
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_signal(bsem, inc=1, device_id=coords(right),
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(bsem, 2)
    return my, left, right, coords


def _ring_allreduce_bidir_kernel(x1_ref, x2_ref, o1_ref, o2_ref,
                                 comm1_ref, comm2_ref,
                                 send1, recv1, ack1,
                                 send2, recv2, ack2,
                                 *, n: int, axis: str,
                                 mesh_axes: Tuple[str, ...]):
    """Bidirectional ring: half 1 rotates clockwise (send right), half 2
    counter-clockwise (send left) — both directions' DMAs are issued before
    either is waited on, so a full-duplex interconnect carries both halves
    concurrently (2x the unidirectional bandwidth bound).

    The schedule is direction-symmetric: in ring-direction space ("next" =
    right for half 1, left for half 2) both halves run the identical
    allreduce schedule of ``_ring_allreduce_kernel``.
    """
    my, left, right, coords = _neighbor_setup(axis, mesh_axes, n)

    o1_ref[...] = x1_ref[...]
    o2_ref[...] = x2_ref[...]

    total_steps = 2 * (n - 1)
    for s in range(total_steps):
        slot = s % 2
        reduce_phase = s < n - 1
        send_idx, recv_idx = _step_indices(my, n, s, +1)
        send_idx2, recv_idx2 = _step_indices(my, n, s, -1)

        if s >= 2:
            pltpu.semaphore_wait(ack1, 1)
            pltpu.semaphore_wait(ack2, 1)

        rdma1 = pltpu.make_async_remote_copy(
            src_ref=o1_ref.at[send_idx], dst_ref=comm1_ref.at[slot],
            send_sem=send1.at[slot], recv_sem=recv1.at[slot],
            device_id=coords(right),
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        rdma2 = pltpu.make_async_remote_copy(
            src_ref=o2_ref.at[send_idx2], dst_ref=comm2_ref.at[slot],
            send_sem=send2.at[slot], recv_sem=recv2.at[slot],
            device_id=coords(left),
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        rdma1.start()
        rdma2.start()  # both directions in flight before either wait
        rdma1.wait()
        rdma2.wait()

        if reduce_phase:
            o1_ref[recv_idx] = o1_ref[recv_idx] + comm1_ref[slot]
            o2_ref[recv_idx2] = o2_ref[recv_idx2] + comm2_ref[slot]
        else:
            o1_ref[recv_idx] = comm1_ref[slot]
            o2_ref[recv_idx2] = comm2_ref[slot]

        pltpu.semaphore_signal(ack1, inc=1, device_id=coords(left),
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_signal(ack2, inc=1, device_id=coords(right),
                               device_id_type=pltpu.DeviceIdType.LOGICAL)

    pltpu.semaphore_wait(ack1, 2)
    pltpu.semaphore_wait(ack2, 2)


def _ring_allreduce_kernel(x_ref, o_ref, comm_ref, send_sem, recv_sem,
                           ack_sem, *, n: int, axis: str,
                           mesh_axes: Tuple[str, ...]):
    """Per-device kernel.  x/o: [n, rows, 128]; comm: [2, rows, 128]."""
    my, left, right, coords = _neighbor_setup(axis, mesh_axes, n)

    o_ref[...] = x_ref[...]

    total_steps = 2 * (n - 1)
    for s in range(total_steps):  # n is static: fully unrolled
        slot = s % 2
        reduce_phase = s < n - 1
        send_idx, recv_idx = _step_indices(my, n, s, +1)

        if s >= 2:
            # Right neighbor must have freed this slot.
            pltpu.semaphore_wait(ack_sem, 1)

        rdma = pltpu.make_async_remote_copy(
            src_ref=o_ref.at[send_idx],
            dst_ref=comm_ref.at[slot],
            send_sem=send_sem.at[slot],
            recv_sem=recv_sem.at[slot],
            device_id=coords(right),
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()

        if reduce_phase:
            o_ref[recv_idx] = o_ref[recv_idx] + comm_ref[slot]
        else:
            o_ref[recv_idx] = comm_ref[slot]

        # Tell the left neighbor its copy of this slot is consumed.
        pltpu.semaphore_signal(ack_sem, inc=1, device_id=coords(left),
                               device_id_type=pltpu.DeviceIdType.LOGICAL)

    # Drain outstanding acks so the kernel exits with clean semaphore state:
    # our last two sends were acked by nobody yet... they were: every step
    # sent an ack, but the final two acks from the right neighbor target
    # slots we never rewrite.  Consume them to leave the semaphore at zero.
    pltpu.semaphore_wait(ack_sem, 2)


def _ring_reduce_scatter_kernel(x_ref, o_ref, acc_ref, comm_ref, send_sem,
                                recv_sem, ack_sem, *, n: int, axis: str,
                                mesh_axes: Tuple[str, ...]):
    """RS phase only.  x: [n, rows, 128]; o: [rows, 128] — the fully-reduced
    chunk ``my`` (the schedule is the classic ring shifted by one so each
    device finishes owning its own chunk index)."""
    my, left, right, coords = _neighbor_setup(axis, mesh_axes, n)

    acc_ref[...] = x_ref[...]
    steps = n - 1
    for s in range(steps):
        slot = s % 2
        send_idx, recv_idx = _rs_step_indices(my, n, s)
        if s >= 2:
            pltpu.semaphore_wait(ack_sem, 1)
        rdma = pltpu.make_async_remote_copy(
            src_ref=acc_ref.at[send_idx],
            dst_ref=comm_ref.at[slot],
            send_sem=send_sem.at[slot],
            recv_sem=recv_sem.at[slot],
            device_id=coords(right),
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()
        acc_ref[recv_idx] = acc_ref[recv_idx] + comm_ref[slot]
        pltpu.semaphore_signal(ack_sem, inc=1, device_id=coords(left),
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(ack_sem, min(2, steps))
    o_ref[...] = acc_ref[my]


def _ring_all_gather_kernel(x_ref, o_ref, comm_ref, send_sem, recv_sem,
                            ack_sem, *, n: int, axis: str,
                            mesh_axes: Tuple[str, ...]):
    """AG only.  x: [rows, 128] (local chunk); o: [n, rows, 128]."""
    my, left, right, coords = _neighbor_setup(axis, mesh_axes, n)

    o_ref[my] = x_ref[...]
    steps = n - 1
    for t in range(steps):
        slot = t % 2
        send_idx = lax.rem(my + n - t, n)
        recv_idx = lax.rem(my + n - t - 1, n)
        if t >= 2:
            pltpu.semaphore_wait(ack_sem, 1)
        rdma = pltpu.make_async_remote_copy(
            src_ref=o_ref.at[send_idx],
            dst_ref=comm_ref.at[slot],
            send_sem=send_sem.at[slot],
            recv_sem=recv_sem.at[slot],
            device_id=coords(right),
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()
        o_ref[recv_idx] = comm_ref[slot]
        pltpu.semaphore_signal(ack_sem, inc=1, device_id=coords(left),
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(ack_sem, min(2, steps))


def _chunk_plan(nelems: int, n: int, dtype, chunk_bytes: int):
    """Static streaming plan for one device's ring schedule.

    Returns ``(sub_elems, C)``: the tensor pads to ``n * C * sub_elems`` and
    is viewed as ``[n ring chunks, C subchunks, rows, 128]``; each DMA moves
    one ``sub_elems``-element subchunk (~``chunk_bytes`` bytes, TILE-rounded),
    so VMEM residency is 4 double-buffered subchunk slots regardless of
    tensor size.  ``C == 1`` means the whole per-ring-chunk payload fits one
    subchunk and the VMEM-resident kernel is the better schedule.
    """
    ebytes = jnp.dtype(dtype).itemsize
    sub_elems = max(_TILE, (chunk_bytes // ebytes) // _TILE * _TILE)
    per = -(-nelems // n)
    C = max(1, -(-per // sub_elems))
    if C > 1:
        # Rebalance so the last subchunk isn't a sliver of padding.
        sub_elems = -(-per // C)
        sub_elems = -(-sub_elems // _TILE) * _TILE
    return sub_elems, C


def _effective_plan(nelems: int, n: int, dtype, chunk_bytes: int,
                    interpreted: bool, steps: Optional[int] = None):
    """The plan actually executed: under the interpreter the pipeline is
    coarsened so total iterations ``steps * C`` stay within
    ``_INTERPRET_MAX_ITERS`` (see that constant's comment); real Mosaic
    lowering always gets the full plan.  ``steps`` defaults to the
    allreduce schedule's ``2*(n-1)``; the RS/AG-only schedules pass their
    shorter ``n-1`` so their simulated pipelines aren't over-coarsened."""
    if steps is None:
        steps = 2 * (n - 1)
    sub_elems, C = _chunk_plan(nelems, n, dtype, chunk_bytes)
    if interpreted and C > 1:
        # Never coarsen below C=2: a plan that needed chunking must stay
        # chunked (the resident kernel would stage the whole tensor), even
        # on rings wide enough that the iteration cap cannot be honored —
        # the cap is a best-effort wedge guard, the VMEM bound is a
        # guarantee.
        max_c = max(2, _INTERPRET_MAX_ITERS // max(1, steps))
        if C > max_c:
            per = -(-nelems // n)
            configured_c = C
            C = max_c
            per_sub = -(-per // C)
            sub_elems = -(-per_sub // _TILE) * _TILE
            # A knob that silently means something different per platform
            # is dishonest (VERDICT r2 weak #7): say so when the
            # interpreter rewrites the configured schedule.
            warnings.warn(
                f"pallas ring interpret mode coarsened the configured "
                f"chunk_bytes={chunk_bytes} plan from C={configured_c} "
                f"to C={C} subchunks per ring chunk (interpreter "
                f"iteration cap {_INTERPRET_MAX_ITERS} over {steps} "
                f"steps); real TPU lowering executes the full-depth "
                f"plan", RingInterpretCoarseningWarning, stacklevel=3)
    return sub_elems, C


def _rs_step_indices(my, n: int, s: int):
    """Shifted RS schedule (shared by the resident and chunked RS kernels):
    offset by one from the classic ring so each device finishes owning its
    own chunk index."""
    send_idx = lax.rem(my + 2 * n - s - 1, n)
    recv_idx = lax.rem(my + 2 * n - s - 2, n)
    return send_idx, recv_idx


def _chunked_pipeline(work_ref, comm, acc, copy_in, copy_out,
                      send_sem, recv_sem, ack_sem, coords, left, right,
                      *, C: int, steps: int, step_indices, reduce_at):
    """Shared pipelined-subchunk driver for the unidirectional chunked ring
    kernels (allreduce / reduce-scatter / all-gather differ only in step
    count, index schedule, and whether a step reduces or forwards).

    ``work_ref`` is the HBM working buffer ``[n, C, rows, 128]``; comm/acc
    are two-slot VMEM scratch.  Iteration k streams subchunk ``c = k % C``
    of ring step ``s = k // C``:

      - the RDMA for iteration k+1 is issued before iteration k's recv is
        waited on (software pipeline, depth 1), so the next subchunk is on
        the wire while this one is being reduced and written back — the
        HBM->VMEM load of the local addend overlaps the RDMA the same way;
      - subchunks within a step are independent, so the pipeline never
        crosses a true dependency: step s+1 forwards what step s received,
        but subchunk (s+1, c)'s RDMA issues C-1 >= 1 iterations after
        (s, c)'s writeback completed (C > 1 is required; C == 1 plans
        route to the VMEM-resident kernels);
      - slot reuse is flow-controlled by the same neighbor-ack protocol as
        the resident kernels (wait one ack per issue from k >= 2).

    ``step_indices(s) -> (send_idx, recv_idx)``; ``reduce_at(s) -> bool``
    (static Python values — the loop is fully unrolled).
    """
    assert C > 1, "chunked pipeline requires a multi-subchunk plan"
    K = steps * C

    def rdma(k):
        s, c = divmod(k, C)
        send_idx, _ = step_indices(s)
        return pltpu.make_async_remote_copy(
            src_ref=work_ref.at[send_idx, c],
            dst_ref=comm.at[k % 2],
            send_sem=send_sem.at[k % 2],
            recv_sem=recv_sem.at[k % 2],
            device_id=coords(right),
            device_id_type=pltpu.DeviceIdType.LOGICAL)

    def issue(k):
        if k >= 2:
            pltpu.semaphore_wait(ack_sem, 1)
        rdma(k).start()

    issue(0)
    for k in range(K):
        slot = k % 2
        s, c = divmod(k, C)
        _, recv_idx = step_indices(s)
        if k + 1 < K:
            issue(k + 1)
        if reduce_at(s):
            load = pltpu.make_async_copy(work_ref.at[recv_idx, c],
                                         acc.at[slot], copy_in.at[slot])
            load.start()
            rdma(k).wait()
            load.wait()
            acc[slot] = acc[slot] + comm[slot]
            src = acc.at[slot]
        else:
            rdma(k).wait()
            src = comm.at[slot]
        wb = pltpu.make_async_copy(src, work_ref.at[recv_idx, c],
                                   copy_out.at[slot])
        wb.start()
        wb.wait()
        pltpu.semaphore_signal(ack_sem, inc=1, device_id=coords(left),
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(ack_sem, min(2, K))


def _ring_allreduce_chunked_kernel(x_ref, o_ref, comm_ref, acc_ref,
                                   copy_in, copy_out, full_sem,
                                   send_sem, recv_sem, ack_sem,
                                   *, n: int, C: int, axis: str,
                                   mesh_axes: Tuple[str, ...]):
    """Chunked/pipelined ring allreduce: the analog of the reference's
    chunk loop (SURVEY.md §4.2 — the performance-critical code upstream).
    Reduce-scatter phase (steps 0..n-2) then all-gather phase; see
    :func:`_chunked_pipeline` for the streaming/flow-control design."""
    my, left, right, coords = _neighbor_setup(axis, mesh_axes, n)

    stage = pltpu.make_async_copy(x_ref, o_ref, full_sem)
    stage.start()
    stage.wait()

    _chunked_pipeline(
        o_ref, comm_ref, acc_ref, copy_in, copy_out,
        send_sem, recv_sem, ack_sem, coords, left, right,
        C=C, steps=2 * (n - 1),
        step_indices=lambda s: _step_indices(my, n, s, +1),
        reduce_at=lambda s: s < n - 1)


def _ring_allreduce_bidir_chunked_kernel(
        x1_ref, x2_ref, o1_ref, o2_ref, comm1, comm2, acc1, acc2,
        copy_in1, copy_in2, copy_out1, copy_out2, full1, full2,
        send1, recv1, ack1, send2, recv2, ack2,
        *, n: int, C: int, axis: str, mesh_axes: Tuple[str, ...]):
    """Bidirectional chunked ring: half 1 streams clockwise (send right),
    half 2 counter-clockwise — per iteration BOTH directions' next RDMAs
    are in flight before either current receive is waited on, so a
    full-duplex interconnect carries both halves concurrently (2x the
    unidirectional bound) while VMEM stays at ~8 subchunk slots.  Each
    direction runs exactly the ``_ring_allreduce_chunked_kernel`` schedule
    (see its docstring for the pipeline/ack reasoning); direction 2 is the
    same schedule under my -> -my."""
    assert C > 1, "chunked kernel requires a multi-subchunk plan"
    my, left, right, coords = _neighbor_setup(axis, mesh_axes, n)

    s1 = pltpu.make_async_copy(x1_ref, o1_ref, full1)
    s2 = pltpu.make_async_copy(x2_ref, o2_ref, full2)
    s1.start()
    s2.start()
    s1.wait()
    s2.wait()

    K = 2 * (n - 1) * C
    refs = ((o1_ref, comm1, acc1, copy_in1, copy_out1, send1, recv1, ack1,
             +1, right, left),
            (o2_ref, comm2, acc2, copy_in2, copy_out2, send2, recv2, ack2,
             -1, left, right))

    def rdma(k, d):
        o_ref, comm, _acc, _ci, _co, send, recv, _ack, sign, to, _frm = refs[d]
        s, c = divmod(k, C)
        send_idx, _ = _step_indices(my, n, s, sign)
        return pltpu.make_async_remote_copy(
            src_ref=o_ref.at[send_idx, c], dst_ref=comm.at[k % 2],
            send_sem=send.at[k % 2], recv_sem=recv.at[k % 2],
            device_id=coords(to),
            device_id_type=pltpu.DeviceIdType.LOGICAL)

    def issue(k):
        if k >= 2:
            pltpu.semaphore_wait(ack1, 1)
            pltpu.semaphore_wait(ack2, 1)
        r1, r2 = rdma(k, 0), rdma(k, 1)
        r1.start()
        r2.start()

    issue(0)
    for k in range(K):
        slot = k % 2
        s, c = divmod(k, C)
        reduce_phase = s < n - 1
        if k + 1 < K:
            issue(k + 1)
        loads = []
        for d in (0, 1):
            o_ref, comm, acc, ci, _co, _s, _r, _a, sign, _to, _frm = refs[d]
            _, recv_idx = _step_indices(my, n, s, sign)
            if reduce_phase:
                load = pltpu.make_async_copy(o_ref.at[recv_idx, c],
                                             acc.at[slot], ci.at[slot])
                load.start()
                loads.append(load)
        rdma(k, 0).wait()
        rdma(k, 1).wait()
        for load in loads:
            load.wait()
        wbs = []
        for d in (0, 1):
            o_ref, comm, acc, _ci, co, _s, _r, _a, sign, _to, _frm = refs[d]
            _, recv_idx = _step_indices(my, n, s, sign)
            if reduce_phase:
                acc[slot] = acc[slot] + comm[slot]
                src = acc.at[slot]
            else:
                src = comm.at[slot]
            wb = pltpu.make_async_copy(src, o_ref.at[recv_idx, c],
                                       co.at[slot])
            wb.start()
            wbs.append(wb)
        for wb in wbs:
            wb.wait()
        pltpu.semaphore_signal(ack1, inc=1, device_id=coords(left),
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_signal(ack2, inc=1, device_id=coords(right),
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(ack1, min(2, K))
    pltpu.semaphore_wait(ack2, min(2, K))


def _ring_allreduce_bidir_chunked(flat, n: int, axis: str,
                                  mesh_axes: Tuple[str, ...],
                                  sub_elems: int, C: int):
    """flat split in two halves, each padded to [n, C, rows, 128]; both
    stream in opposite directions concurrently."""
    half = flat.shape[0] // 2
    h1, h2 = flat[:half], flat[half:]
    padded = n * C * sub_elems
    L1, L2 = h1.shape[0], h2.shape[0]
    if padded > L1:
        h1 = jnp.concatenate([h1, jnp.zeros((padded - L1,), flat.dtype)])
    if padded > L2:
        h2 = jnp.concatenate([h2, jnp.zeros((padded - L2,), flat.dtype)])
    rows = sub_elems // _LANES
    x1 = h1.reshape(n, C, rows, _LANES)
    x2 = h2.reshape(n, C, rows, _LANES)
    kernel = functools.partial(_ring_allreduce_bidir_chunked_kernel, n=n,
                               C=C, axis=axis, mesh_axes=mesh_axes)
    o1, o2 = pl.pallas_call(
        kernel,
        out_shape=(_out_sds(x1.shape, x1), _out_sds(x2.shape, x2)),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)),
        scratch_shapes=[
            pltpu.VMEM((2, rows, _LANES), x1.dtype),   # comm1
            pltpu.VMEM((2, rows, _LANES), x2.dtype),   # comm2
            pltpu.VMEM((2, rows, _LANES), x1.dtype),   # acc1
            pltpu.VMEM((2, rows, _LANES), x2.dtype),   # acc2
            pltpu.SemaphoreType.DMA((2,)),             # copy_in1
            pltpu.SemaphoreType.DMA((2,)),             # copy_in2
            pltpu.SemaphoreType.DMA((2,)),             # copy_out1
            pltpu.SemaphoreType.DMA((2,)),             # copy_out2
            pltpu.SemaphoreType.DMA(()),               # full1
            pltpu.SemaphoreType.DMA(()),               # full2
            pltpu.SemaphoreType.DMA((2,)),             # send1
            pltpu.SemaphoreType.DMA((2,)),             # recv1
            pltpu.SemaphoreType.REGULAR,               # ack1
            pltpu.SemaphoreType.DMA((2,)),             # send2
            pltpu.SemaphoreType.DMA((2,)),             # recv2
            pltpu.SemaphoreType.REGULAR,               # ack2
        ],
        compiler_params=pltpu.CompilerParams(collective_id=12),
        interpret=_interpret_mode(),
        metadata=kernel_identity("ring.allreduce.bidir_chunked"),
    )(x1, x2)
    f1 = o1.reshape(-1)[:L1]
    f2 = o2.reshape(-1)[:L2]
    return jnp.concatenate([f1, f2])


def _ring_allreduce_chunked(flat, n: int, axis: str,
                            mesh_axes: Tuple[str, ...],
                            sub_elems: int, C: int):
    """flat: 1-D; pads to [n, C, rows, 128] HBM-resident views."""
    L = flat.shape[0]
    padded = n * C * sub_elems
    if padded > L:
        flat = jnp.concatenate([flat, jnp.zeros((padded - L,), flat.dtype)])
    rows = sub_elems // _LANES
    x = flat.reshape(n, C, rows, _LANES)
    kernel = functools.partial(_ring_allreduce_chunked_kernel, n=n, C=C,
                               axis=axis, mesh_axes=mesh_axes)
    out = pl.pallas_call(
        kernel,
        out_shape=_out_sds(x.shape, x),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, rows, _LANES), x.dtype),   # comm slots
            pltpu.VMEM((2, rows, _LANES), x.dtype),   # accumulate slots
            pltpu.SemaphoreType.DMA((2,)),            # copy_in
            pltpu.SemaphoreType.DMA((2,)),            # copy_out
            pltpu.SemaphoreType.DMA(()),              # full staging copy
            pltpu.SemaphoreType.DMA((2,)),            # send
            pltpu.SemaphoreType.DMA((2,)),            # recv
            pltpu.SemaphoreType.REGULAR,              # ack
        ],
        compiler_params=pltpu.CompilerParams(collective_id=11),
        interpret=_interpret_mode(),
        metadata=kernel_identity("ring.allreduce.chunked"),
    )(x)
    return out.reshape(-1)[:L]


def _ring_reduce_scatter_chunked_kernel(x_ref, o_ref, work_ref, comm, acc,
                                        copy_in, copy_out, full_sem,
                                        send_sem, recv_sem, ack_sem,
                                        *, n: int, C: int, axis: str,
                                        mesh_axes: Tuple[str, ...]):
    """Chunked RS phase only: x/work ``[n, C, rows, 128]`` in HBM (work
    is the call's second output), o ``[C, rows, 128]`` (the
    fully-reduced chunk ``my``).  The shared
    :func:`_chunked_pipeline` with the shifted RS schedule."""
    my, left, right, coords = _neighbor_setup(axis, mesh_axes, n)

    stage = pltpu.make_async_copy(x_ref, work_ref, full_sem)
    stage.start()
    stage.wait()

    _chunked_pipeline(
        work_ref, comm, acc, copy_in, copy_out,
        send_sem, recv_sem, ack_sem, coords, left, right,
        C=C, steps=n - 1,
        step_indices=lambda s: _rs_step_indices(my, n, s),
        reduce_at=lambda s: True)

    out = pltpu.make_async_copy(work_ref.at[my], o_ref, full_sem)
    out.start()
    out.wait()


def _ring_all_gather_chunked_kernel(x_ref, o_ref, comm, copy_out, full_sem,
                                    send_sem, recv_sem, ack_sem,
                                    *, n: int, C: int, axis: str,
                                    mesh_axes: Tuple[str, ...]):
    """Chunked AG phase only: x ``[C, rows, 128]`` (local chunk), o
    ``[n, C, rows, 128]`` in HBM.  The shared :func:`_chunked_pipeline`
    with the classic forward schedule and no reduce (received subchunks
    DMA straight from the comm slot to their HBM home; the acc/copy_in
    scratch is never touched, so the resident AG kernel's comm scratch is
    reused in both roles)."""
    my, left, right, coords = _neighbor_setup(axis, mesh_axes, n)

    stage = pltpu.make_async_copy(x_ref, o_ref.at[my], full_sem)
    stage.start()
    stage.wait()

    # AG steps t = 0..n-2 use the classic schedule: send my - t, receive
    # my - t - 1 — exactly _step_indices' reduce-phase formula.
    _chunked_pipeline(
        o_ref, comm, None, None, copy_out,
        send_sem, recv_sem, ack_sem, coords, left, right,
        C=C, steps=n - 1,
        step_indices=lambda t: _step_indices(my, n, t, +1),
        reduce_at=lambda t: False)


def _ring_reduce_scatter_chunked(xin, n: int, axis: str,
                                 mesh_axes: Tuple[str, ...],
                                 sub_elems: int, C: int):
    """xin: [n, per] per-chunk rows; pads per to C*sub_elems."""
    per = xin.shape[1]
    padded = C * sub_elems
    if padded > per:
        xin = jnp.concatenate(
            [xin, jnp.zeros((n, padded - per), xin.dtype)], axis=1)
    rows = sub_elems // _LANES
    x = xin.reshape(n, C, rows, _LANES)
    kernel = functools.partial(_ring_reduce_scatter_chunked_kernel, n=n, C=C,
                               axis=axis, mesh_axes=mesh_axes)
    # The HBM work buffer is a second, discarded OUTPUT: the chip's
    # compiler allocates scratch only in VMEM/SMEM/semaphore memory.
    out, _work = pl.pallas_call(
        kernel,
        out_shape=(_out_sds((C, rows, _LANES), x),
                   _out_sds((n, C, rows, _LANES), x)),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)),
        scratch_shapes=[
            pltpu.VMEM((2, rows, _LANES), x.dtype),                # comm
            pltpu.VMEM((2, rows, _LANES), x.dtype),                # acc
            pltpu.SemaphoreType.DMA((2,)),                         # copy_in
            pltpu.SemaphoreType.DMA((2,)),                         # copy_out
            pltpu.SemaphoreType.DMA(()),                           # full
            pltpu.SemaphoreType.DMA((2,)),                         # send
            pltpu.SemaphoreType.DMA((2,)),                         # recv
            pltpu.SemaphoreType.REGULAR,                           # ack
        ],
        compiler_params=pltpu.CompilerParams(collective_id=13),
        interpret=_interpret_mode(),
        metadata=kernel_identity("ring.reduce_scatter.chunked"),
    )(x)
    return out.reshape(-1)[:per]


def _ring_all_gather_chunked(xin, n: int, axis: str,
                             mesh_axes: Tuple[str, ...],
                             sub_elems: int, C: int):
    """xin: [L] local flat chunk; pads to C*sub_elems; returns [n, padded]."""
    L = xin.shape[0]
    padded = C * sub_elems
    if padded > L:
        xin = jnp.concatenate([xin, jnp.zeros((padded - L,), xin.dtype)])
    rows = sub_elems // _LANES
    x = xin.reshape(C, rows, _LANES)
    kernel = functools.partial(_ring_all_gather_chunked_kernel, n=n, C=C,
                               axis=axis, mesh_axes=mesh_axes)
    out = pl.pallas_call(
        kernel,
        out_shape=_out_sds((n, C, rows, _LANES), x),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, rows, _LANES), x.dtype),   # comm
            pltpu.SemaphoreType.DMA((2,)),            # copy_out
            pltpu.SemaphoreType.DMA(()),              # full
            pltpu.SemaphoreType.DMA((2,)),            # send
            pltpu.SemaphoreType.DMA((2,)),            # recv
            pltpu.SemaphoreType.REGULAR,              # ack
        ],
        compiler_params=pltpu.CompilerParams(collective_id=14),
        interpret=_interpret_mode(),
        metadata=kernel_identity("ring.all_gather.chunked"),
    )(x)
    return out.reshape(n, -1)[:, :L]


def _ring_allreduce_padded(x, n: int, axis: str,
                           mesh_axes: Tuple[str, ...]):
    """x: [n, rows, 128] tiled per device (see _pad_and_tile)."""
    rows = x.shape[1]
    kernel = functools.partial(_ring_allreduce_kernel, n=n, axis=axis,
                               mesh_axes=mesh_axes)
    out = pl.pallas_call(
        kernel,
        out_shape=_out_sds(x.shape, x),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, rows, _LANES), x.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ],
        compiler_params=pltpu.CompilerParams(collective_id=7),
        interpret=_interpret_mode(),
        metadata=kernel_identity("ring.allreduce.padded"),
    )(x)
    return out.reshape(-1)


def _ring_allreduce_bidir_padded(flat, n: int, axis: str,
                                 mesh_axes: Tuple[str, ...]):
    """flat split in two halves, each padded to n*TILE; both ring in
    opposite directions concurrently."""
    half = flat.shape[0] // 2
    h1, h2 = flat[:half], flat[half:]

    x1, pad1 = _pad_and_tile(h1, n)
    x2, pad2 = _pad_and_tile(h2, n)
    kernel = functools.partial(_ring_allreduce_bidir_kernel, n=n, axis=axis,
                               mesh_axes=mesh_axes)
    o1, o2 = pl.pallas_call(
        kernel,
        out_shape=(_out_sds(x1.shape, x1), _out_sds(x2.shape, x2)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 2,
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM)),
        scratch_shapes=[
            pltpu.VMEM((2,) + x1.shape[1:], x1.dtype),
            pltpu.VMEM((2,) + x2.shape[1:], x2.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ],
        compiler_params=pltpu.CompilerParams(collective_id=10),
        interpret=_interpret_mode(),
        metadata=kernel_identity("ring.allreduce.bidir_padded"),
    )(x1, x2)
    f1 = o1.reshape(-1)
    f2 = o2.reshape(-1)
    if pad1:
        f1 = f1[:f1.shape[0] - pad1]
    if pad2:
        f2 = f2[:f2.shape[0] - pad2]
    return jnp.concatenate([f1, f2])


_SUPPORTED_DTYPES = (jnp.float32, jnp.bfloat16, jnp.int32)


def ring_allreduce(x, axis_names, *, op: str = "sum"):
    """Selector-registered entry: allreduce over the *last* axis in
    ``axis_names`` with the ring kernel; any leading axes (e.g. ``dcn``) are
    reduced with a stock psum afterwards (hierarchical composition).

    Schedule selection (all static, so ``set_config(chunk_bytes=...)``
    recompiles and genuinely changes the schedule):

    - per-ring-chunk payload > ``config.chunk_bytes``: the chunked/pipelined
      kernel streams subchunks HBM->VMEM with the next RDMA in flight —
      VMEM use is bounded by ~4x chunk_bytes however large the tensor;
    - otherwise ``config.pallas_bidirectional`` and size permitting: the
      VMEM-resident bidirectional kernel (halves ring in opposite
      directions, 2x bandwidth bound on full-duplex ICI links);
    - otherwise: the VMEM-resident unidirectional kernel.

    Supported dtypes: f32, bf16, i32; anything else raises (no silent
    downcast — a backend swap must never change numerics).
    """
    if op not in ("sum", "mean"):
        raise KeyError(f"pallas ring allreduce does not support op {op!r}")
    axes = (axis_names,) if isinstance(axis_names, str) else tuple(axis_names)
    ring_axis = axes[-1]
    outer_axes = axes[:-1]
    n = lax.axis_size(ring_axis)

    # Logical device ids need the coordinates over ALL mesh axes of the
    # enclosing shard_map, not just the ring axis; see _mesh_axes_for.
    mesh_axes = _mesh_axes_for(axes)

    from .. import runtime

    cfg = runtime.effective_config()
    bidir = cfg.pallas_bidirectional
    chunk_bytes = cfg.chunk_bytes

    if n == 1:
        out = x
    else:
        shape, dtype = x.shape, x.dtype
        if dtype not in _SUPPORTED_DTYPES:
            raise TypeError(
                f"pallas ring allreduce supports f32/bf16/i32, got {dtype} "
                f"(use the xla backend for other dtypes)")
        flat = x.reshape(-1)
        interp = bool(_interpret_mode())
        sub_elems, C = _effective_plan(flat.shape[0], n, dtype, chunk_bytes,
                                       interp)
        if C > 1:
            half_plan = _effective_plan(-(-flat.shape[0] // 2), n, dtype,
                                        chunk_bytes, interp)
            if bidir and half_plan[1] > 1:
                reduced = _ring_allreduce_bidir_chunked(
                    flat, n, ring_axis, mesh_axes, *half_plan)
            else:
                reduced = _ring_allreduce_chunked(flat, n, ring_axis,
                                                  mesh_axes, sub_elems, C)
        elif bidir and flat.shape[0] >= 2 * n * _TILE:
            reduced = _ring_allreduce_bidir_padded(flat, n, ring_axis,
                                                   mesh_axes)
        else:
            tiled, pad = _pad_and_tile(flat, n)
            reduced = _ring_allreduce_padded(tiled, n, ring_axis, mesh_axes)
            if pad:
                reduced = reduced[:reduced.shape[0] - pad]
        out = reduced.reshape(shape).astype(dtype)
    for a in outer_axes:
        out = lax.psum(out, a)
    if op == "mean":
        total = n
        for a in outer_axes:
            total *= lax.axis_size(a)
        out = out / total
    return out


selector.register("allreduce", "pallas", ring_allreduce)


def _mesh_axes_for(axes: Tuple[str, ...]) -> Tuple[str, ...]:
    """All mesh axis names of the enclosing shard_map, in mesh order —
    logical device ids are row-major over the FULL mesh, so the neighbor
    computation needs every axis, not just the ring axes.  Uses the public
    abstract-mesh accessor; falls back to the ring axes when tracing
    outside any mesh (e.g. direct kernel unit tests)."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
        mesh_axes = tuple(mesh.axis_names) if mesh is not None else axes
    except Exception:
        mesh_axes = axes
    if not all(a in mesh_axes for a in axes):
        mesh_axes = axes
    return mesh_axes


def _out_sds(shape, x):
    try:
        vma = jax.typeof(x).vma
    except Exception:
        vma = None
    return (jax.ShapeDtypeStruct(shape, x.dtype, vma=vma)
            if vma else jax.ShapeDtypeStruct(shape, x.dtype))


def ring_reduce_scatter(x, axis_names, *, op: str = "sum"):
    """Ring reduce-scatter over the last axis of ``axis_names``, with the
    same tiled semantics as the stock backend (``lax.psum_scatter`` with
    ``scatter_dimension=0, tiled=True``): input ``[k, ...]`` with ``k``
    divisible by the group size yields output ``[k/group, ...]`` — whole
    leading-dim rows, so selector fallback between backends never changes
    the output shape.

    Composition order for multi-axis groups: the outer (dcn) axes are
    psum_scatter'd with the stock path FIRST, then the remaining slice is
    ring-scattered over ICI — combined-rank order is outer-major, so device
    (d, i) ends with global slice ``d*n + i``."""
    if op != "sum":
        raise KeyError(f"pallas ring reduce_scatter supports sum, not {op!r}")
    axes = (axis_names,) if isinstance(axis_names, str) else tuple(axis_names)
    ring_axis = axes[-1]
    outer_axes = axes[:-1]
    n = lax.axis_size(ring_axis)
    mesh_axes = _mesh_axes_for(axes)
    total = n
    for a in outer_axes:
        total *= lax.axis_size(a)
    if x.shape[0] % total != 0:
        raise ValueError(
            f"reduce_scatter needs leading dim divisible by group size: "
            f"{x.shape[0]} % {total}")
    out_shape = (x.shape[0] // total,) + x.shape[1:]
    for a in outer_axes:
        x = x.reshape((-1,) + x.shape[1:])
        x = lax.psum_scatter(x, a, scatter_dimension=0, tiled=True)
    flat = x.reshape(-1)
    L = flat.shape[0]
    per = L // n
    chunks = flat.reshape(n, per)
    if n == 1:
        return chunks[0].reshape(out_shape)
    sub_elems, C = _effective_plan(L, n, flat.dtype,
                                   runtime_chunk_bytes(),
                                   bool(_interpret_mode()), steps=n - 1)
    if C > 1:
        out = _ring_reduce_scatter_chunked(chunks, n, ring_axis, mesh_axes,
                                           sub_elems, C)
        return out.reshape(out_shape)
    pad = (-per) % _TILE
    if pad:
        chunks = jnp.concatenate(
            [chunks, jnp.zeros((n, pad), flat.dtype)], axis=1)
    rows = (per + pad) // _LANES
    xin = chunks.reshape(n, rows, _LANES)
    kernel = functools.partial(_ring_reduce_scatter_kernel, n=n,
                               axis=ring_axis, mesh_axes=mesh_axes)
    out = pl.pallas_call(
        kernel,
        out_shape=_out_sds((rows, _LANES), xin),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((n, rows, _LANES), xin.dtype),
            pltpu.VMEM((2, rows, _LANES), xin.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ],
        compiler_params=pltpu.CompilerParams(collective_id=8),
        interpret=_interpret_mode(),
        metadata=kernel_identity("ring.reduce_scatter"),
    )(xin)
    return out.reshape(-1)[:per].reshape(out_shape)


def ring_all_gather(x, axis_names):
    """Ring all-gather over the last axis; output stacks ring members on a
    new leading axis (matching ``lax.all_gather(axis=0, tiled=False)``),
    then outer axes are gathered with the stock path and flattened so the
    leading axis is the full (row-major) rank order."""
    axes = (axis_names,) if isinstance(axis_names, str) else tuple(axis_names)
    ring_axis = axes[-1]
    outer_axes = axes[:-1]
    n = lax.axis_size(ring_axis)
    mesh_axes = _mesh_axes_for(axes)
    shape = x.shape
    flat = x.reshape(-1)
    L = flat.shape[0]
    sub_elems, C = _effective_plan(L * n, n, flat.dtype,
                                   runtime_chunk_bytes(),
                                   bool(_interpret_mode()), steps=n - 1)
    if n > 1 and C > 1:
        gathered = _ring_all_gather_chunked(flat, n, ring_axis, mesh_axes,
                                            sub_elems, C)
        out = gathered.reshape((n,) + shape)
        for a in reversed(outer_axes):
            out = lax.all_gather(out, a, axis=0, tiled=False)
            out = out.reshape((-1,) + shape)
        return out
    pad = (-L) % _TILE
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    rows = flat.shape[0] // _LANES
    xin = flat.reshape(rows, _LANES)
    if n == 1:
        gathered = xin[None]
    else:
        kernel = functools.partial(_ring_all_gather_kernel, n=n,
                                   axis=ring_axis, mesh_axes=mesh_axes)
        gathered = pl.pallas_call(
            kernel,
            out_shape=_out_sds((n, rows, _LANES), xin),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, rows, _LANES), xin.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.REGULAR,
            ],
            compiler_params=pltpu.CompilerParams(collective_id=9),
            interpret=_interpret_mode(),
            metadata=kernel_identity("ring.all_gather"),
        )(xin)
    out = gathered.reshape(n, -1)[:, :L].reshape((n,) + shape)
    for a in reversed(outer_axes):
        out = lax.all_gather(out, a, axis=0, tiled=False)
        out = out.reshape((-1,) + shape)
    return out


selector.register("reduce_scatter", "pallas", ring_reduce_scatter)
selector.register("allgather", "pallas", ring_all_gather)
