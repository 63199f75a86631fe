"""Flat-function shim backing the embedded-Python C API (native/c_api.cpp).

The reference exposes its C++ core to C via opaque handles (src/c_bind.cpp) and to
Python via ctypes over that C layer (include/mlsl/mlsl.py). This framework inverts the
stack — the core is Python/JAX — so the C API embeds the interpreter and calls these
flat functions. Handles are integers into a registry; buffers cross the boundary as
raw pointer addresses wrapped with ctypes (single-controller: a C caller provides the
whole world's buffer, shape (world, count), and receives results the same way).
"""

from __future__ import annotations

import ctypes
import threading

import numpy as np

from mlsl_tpu.core.environment import Environment
from mlsl_tpu.types import CompressionType, DataType, GroupType, OpType, ReductionType, jnp_dtype

_registry: dict = {}
_next_id = 1
_lock = threading.Lock()


def _put(obj) -> int:
    global _next_id
    with _lock:
        hid = _next_id
        _next_id += 1
        _registry[hid] = obj
    return hid


def _get(hid: int):
    return _registry[int(hid)]


def _release(hid: int) -> int:
    _registry.pop(int(hid), None)
    return 0


# ---- environment ----

def env_init() -> int:
    Environment.get_env().init()
    return 0


def env_finalize() -> int:
    Environment.get_env().finalize()
    return 0


def env_process_count() -> int:
    return Environment.get_env().get_process_count()


def env_create_distribution(data_parts: int, model_parts: int, seq_parts: int) -> int:
    env = Environment.get_env()
    return _put(env.create_distribution(data_parts, model_parts, seq_parts=seq_parts))


def env_create_distribution_with_colors(
    data_addr: int, model_addr: int, n: int
) -> int:
    """Color-defined process groups (reference CreateDistributionWithColors,
    include/mlsl.hpp:864): int64[n] per-rank color vectors at the given
    addresses; ranks sharing a data/model color form that group (unequal
    partitions ride the padded ragged-group contract)."""
    data = tuple(int(c) for c in _read_i64_array(data_addr, int(n)))
    model = tuple(int(c) for c in _read_i64_array(model_addr, int(n)))
    env = Environment.get_env()
    return _put(env.create_distribution_with_colors(data, model))


def env_create_session() -> int:
    return _put(Environment.get_env().create_session())


def env_set_quantization_params(
    lib_path, quant_name, dequant_name, reduce_name,
    block_size: int, elem_in_block: int,
) -> int:
    """Register codec parameters (reference src/mlsl.cpp:798). A lib_path is
    honored via the dlopen/ctypes trampoline (comm/codec.py); load failures
    raise and surface as MLSL_TPU_FAILURE with the message in
    mlsl_get_last_error()."""
    from mlsl_tpu.types import QuantParams

    Environment.get_env().set_quantization_params(QuantParams(
        block_size=int(block_size) if block_size else 256,
        elem_in_block=int(elem_in_block) if elem_in_block else 256,
        lib_path=lib_path or None,
        quant_buffer_func_name=quant_name or None,
        dequant_buffer_func_name=dequant_name or None,
        reduce_sum_func_name=reduce_name or None,
    ))
    return 0


# ---- buffers: address <-> numpy ----

def _read_world_buffer(dist, addr: int, count: int, data_type: int):
    """C buffer at `addr`, logical shape (world, count), -> distributed buffer."""
    dt = jnp_dtype(DataType(data_type))
    world = dist.get_process_count_global()
    flat = np.ctypeslib.as_array(
        ctypes.cast(int(addr), ctypes.POINTER(ctypes.c_char)),
        shape=(world * count * np.dtype(dt).itemsize,),
    ).view(dt).reshape(world, count)
    return dist.make_buffer(lambda p: flat[p], count, DataType(data_type))


def _write_world_buffer(dist, result, addr: int, count: int, data_type: int) -> int:
    dt = np.dtype(jnp_dtype(DataType(data_type)))
    world = dist.get_process_count_global()
    out = np.ctypeslib.as_array(
        ctypes.cast(int(addr), ctypes.POINTER(ctypes.c_char)),
        shape=(world * count * dt.itemsize,),
    ).view(dt).reshape(world, count)
    host = np.asarray(result).reshape(world, -1)
    out[:, : host.shape[1]] = host[:, :count]
    return 0


# ---- distribution collectives (sync + async) ----

def dist_collective_start(
    dist_h: int, kind: str, addr: int, count: int, data_type: int,
    op: int, root: int, group: int,
) -> int:
    dist = _get(dist_h)
    buf = _read_world_buffer(dist, addr, count, data_type)
    gt = GroupType(group)
    if kind == "allreduce":
        req = dist.all_reduce(buf, count, data_type, ReductionType(op), gt)
    elif kind == "bcast":
        req = dist.bcast(buf, count, data_type, root, gt)
    elif kind == "reduce":
        req = dist.reduce(buf, count, data_type, ReductionType(op), root, gt)
    elif kind == "allgather":
        req = dist.all_gather(buf, count, data_type, gt)
    elif kind == "gather":
        req = dist.gather(buf, count, data_type, root, gt)
    elif kind in ("scatter", "reduce_scatter", "alltoall"):
        from mlsl_tpu.log import mlsl_assert

        g = dist._group(gt)
        gsize = 1 if g.is_self else g.size
        mlsl_assert(
            count % gsize == 0,
            "%s send count %d must be divisible by group size %d",
            kind, count, gsize,
        )
        per = count // gsize
        if kind == "scatter":
            req = dist.scatter(buf, per, data_type, root, gt)
        elif kind == "reduce_scatter":
            req = dist.reduce_scatter(buf, per, data_type, ReductionType(op), gt)
        else:
            req = dist.all_to_all(buf, per, data_type, gt)
    else:
        raise ValueError(f"unknown collective {kind}")
    return _put((dist, req))


def request_wait(req_h: int, out_addr: int, out_count: int, data_type: int) -> int:
    dist, req = _get(req_h)
    result = Environment.get_env().wait(req)
    _write_world_buffer(dist, result, out_addr, out_count, data_type)
    _release(req_h)
    return 0


def request_test(req_h: int) -> int:
    """1 if complete, 0 otherwise. Non-consuming: a later request_wait still
    delivers the result (the request caches it on test completion)."""
    dist, req = _get(req_h)
    done, _ = req.test()
    return 1 if done else 0


def dist_send_recv_list(
    dist_h: int, addr: int, count: int, data_type: int,
    pairs_addr: int, n_pairs: int, group: int,
) -> int:
    """pairs_addr: int64 array [src0, dst0, src1, dst1, ...] of length 2*n_pairs."""
    dist = _get(dist_h)
    flat = np.ctypeslib.as_array(
        ctypes.cast(int(pairs_addr), ctypes.POINTER(ctypes.c_int64)),
        shape=(2 * n_pairs,),
    )
    pairs = [(int(flat[2 * i]), int(flat[2 * i + 1])) for i in range(n_pairs)]
    buf = _read_world_buffer(dist, addr, count, data_type)
    req = dist.send_recv_list(buf, count, data_type, pairs, GroupType(group))
    return _put((dist, req))


def dist_barrier(dist_h: int, group: int) -> int:
    _get(dist_h).barrier(GroupType(group))
    return 0


def dist_process_count(dist_h: int, group: int) -> int:
    return _get(dist_h).get_process_count(GroupType(group))


def dist_process_idx(dist_h: int, group: int, global_idx: int) -> int:
    """Member index of world rank `global_idx` within the group — the per-rank
    GetProcessIdx (reference include/mlsl.hpp:361) with the rank explicit."""
    return _get(dist_h).get_process_idx(GroupType(group), global_idx)


# ---- session graph ----

def session_set_minibatch(sess_h: int, size: int) -> int:
    _get(sess_h).set_global_minibatch_size(size)
    return 0


def session_create_reginfo(sess_h: int, op_type: int) -> int:
    return _put(_get(sess_h).create_operation_reg_info(OpType(op_type)))


def reginfo_add_input(reg_h: int, count: int, size: int, data_type: int) -> int:
    return _get(reg_h).add_input(count, size, DataType(data_type))


def reginfo_add_output(reg_h: int, count: int, size: int, data_type: int) -> int:
    return _get(reg_h).add_output(count, size, DataType(data_type))


def reginfo_add_parameter_set(
    reg_h: int, count: int, size: int, data_type: int, dist_update: int, compression: int
) -> int:
    return _get(reg_h).add_parameter_set(
        count, size, DataType(data_type),
        distributed_update=bool(dist_update),
        compression_type=CompressionType(compression),
    )


def session_add_operation(sess_h: int, reg_h: int, dist_h: int) -> int:
    sess = _get(sess_h)
    idx = sess.add_operation(_get(reg_h), _get(dist_h))
    return _put(sess.get_operation(idx))


def session_commit(sess_h: int) -> int:
    _get(sess_h).commit()
    return 0


def operation_set_next(op_h: int, next_h: int, out_idx: int, in_idx: int) -> int:
    _get(op_h).set_next(_get(next_h), out_idx, in_idx)
    return 0


def operation_set_prev(op_h: int, prev_h: int, in_idx: int, prev_out_idx: int) -> int:
    _get(op_h).set_prev(_get(prev_h), in_idx, prev_out_idx)
    return 0


def operation_local_minibatch(op_h: int) -> int:
    return _get(op_h).get_local_minibatch_size()


def operation_global_minibatch(op_h: int) -> int:
    return _get(op_h).get_global_minibatch_size()


def operation_param_local_count(op_h: int, ps_idx: int) -> int:
    ps = _get(op_h).get_parameter_set(ps_idx)
    return ps.get_local_kernel_count() * ps.get_kernel_size()


def operation_param_owned_count(op_h: int, ps_idx: int) -> int:
    ps = _get(op_h).get_parameter_set(ps_idx)
    return ps.get_owned_kernel_count() * ps.get_kernel_size()


# ---- activations (reference c_bind.cpp activation wrappers over
# include/mlsl.hpp:210-268) ----

def operation_get_input(op_h: int, idx: int) -> int:
    return _put(_get(op_h).get_input(idx))


def operation_get_output(op_h: int, idx: int) -> int:
    return _put(_get(op_h).get_output(idx))


def operation_input_count(op_h: int) -> int:
    return _get(op_h).get_input_count()


def operation_output_count(op_h: int) -> int:
    return _get(op_h).get_output_count()


def activation_query(act_h: int, what: int) -> int:
    """what: 0=global_fm_count 1=local_fm_count 2=fm_size 3=pack_block_count
    4=unpack_block_count 5=comm_buf_size 6=need_comm 7=send_count
    8=recv_count."""
    act = _get(act_h)
    if what == 0:
        return act.get_global_fm_count()
    if what == 1:
        return act.get_local_fm_count()
    if what == 2:
        return act.get_fm_size()
    if what == 3:
        return act.get_pack_block_count()
    if what == 4:
        return act.get_unpack_block_count()
    if what == 5:
        return act.get_comm_buf_size()
    if what == 6:
        return int(act.need_comm)
    if what == 7:
        return _act_wire_count(act)
    if what == 8:
        return _act_recv_count(act)
    raise ValueError(f"unknown activation query {what}")


def _act_wire_count(act) -> int:
    """Per-rank wire-buffer element count for this activation's request (an
    AlltoAll request's desc.count is the per-member block; the buffer holds one
    block per group member)."""
    req = act.comm_req
    if req is None:
        return 0
    if req.desc.kind == "alltoall":
        g = req.desc.group
        return req.desc.count * (1 if g.is_self else g.size)
    return req.desc.count


def _act_recv_count(act) -> int:
    """Per-rank element count of this activation's request RESULT (what a
    peer's wait_comm delivers) — sizes the C caller's recv buffer."""
    req = act.comm_req
    if req is None:
        return 0
    g = req.desc.group
    gsize = 1 if g.is_self else g.size
    kind = req.desc.kind
    if kind in ("allgather", "alltoall"):
        return req.desc.count * gsize
    if kind == "reduce_scatter":
        return req.desc.recv_count
    return req.desc.count  # allreduce


def activation_fm_offset(act_h: int, model_idx: int) -> int:
    """Per-rank GetGlobalFmOffset (reference include/mlsl.hpp:219) with the
    rank's model-group index explicit."""
    return _get(act_h).get_global_fm_offset(model_idx)


def activation_block_query(act_h: int, is_unpack: int, idx: int, field: int) -> int:
    """field: 0=mb_offset 1=mb_count 2=fm_offset 3=fm_count 4=fm_size
    5=buf_offset (reference CommBlockInfo include/mlsl.hpp:177-204)."""
    act = _get(act_h)
    b = (act.unpack_blocks if is_unpack else act.pack_blocks)[idx]
    return (b.mb_offset, b.mb_count, b.fm_offset, b.fm_count,
            b.fm_size, b.buf_offset)[field]


def activation_start_comm(act_h: int, addr: int, data_type: int) -> int:
    act = _get(act_h)
    n = _act_wire_count(act)
    if n == 0:
        return 0  # no comm on this edge (reference: no-op start)
    buf = _read_world_buffer(act.dist, addr, n, data_type)
    act.start_comm(buf)
    return 0


def activation_wait_comm(act_h: int, out_addr: int, data_type: int) -> int:
    """Waits the PEER's transfer (reference invariant) and writes (world, n);
    returns per-rank n (0 = no comm on this edge)."""
    act = _get(act_h)
    out = act.wait_comm()
    if out is None:
        return 0
    n = int(np.asarray(out).shape[-1])
    peer = act.peer_act
    dist = peer.dist if peer is not None else act.dist
    _write_world_buffer(dist, out, out_addr, n, data_type)
    return n


# ---- v-collectives (reference mlsl.hpp:418-471 AllGatherv/AlltoAllv) ----

def _read_i64_array(addr: int, n: int):
    return np.ctypeslib.as_array(
        ctypes.cast(int(addr), ctypes.POINTER(ctypes.c_int64)), shape=(n,)
    ).copy()


def dist_all_gatherv(dist_h: int, addr: int, send_count: int,
                     recv_counts_addr: int, data_type: int, group: int) -> int:
    """recv_counts: int64[group_size], identical on every rank (MPI semantics);
    the send buffer is (world, max(recv_counts)) with rank p's first
    recv_counts[member_idx(p)] elements valid."""
    dist = _get(dist_h)
    gt = GroupType(group)
    g = dist._group(gt)
    gsize = 1 if g.is_self else g.size
    counts = tuple(int(c) for c in _read_i64_array(recv_counts_addr, gsize))
    buf = _read_world_buffer(dist, addr, send_count, data_type)
    req = dist.all_gatherv(buf, send_count, counts, data_type, gt)
    return _put((dist, req))


def dist_all_to_allv(dist_h: int, addr: int, send_len: int,
                     send_counts_addr: int, send_offsets_addr: int,
                     recv_offsets_addr: int, data_type: int, group: int) -> int:
    """MPI AlltoAllv with rank-uniform int64[group_size] count/displacement
    arrays (the 1-D 'same on every rank' mode; see comm.request._normalize_alltoallv).
    Pass 0 for an offsets addr to use the packed default."""
    dist = _get(dist_h)
    gt = GroupType(group)
    g = dist._group(gt)
    gsize = 1 if g.is_self else g.size
    counts = _read_i64_array(send_counts_addr, gsize)
    soff = _read_i64_array(send_offsets_addr, gsize) if send_offsets_addr else None
    roff = _read_i64_array(recv_offsets_addr, gsize) if recv_offsets_addr else None
    buf = _read_world_buffer(dist, addr, send_len, data_type)
    req = dist.all_to_allv(buf, counts, soff, None, roff, data_type, gt)
    return _put((dist, req))


def dist_all_to_allv_full(dist_h: int, addr: int, send_len: int,
                          send_counts_addr: int, send_offsets_addr: int,
                          recv_counts_addr: int, recv_offsets_addr: int,
                          data_type: int, group: int) -> int:
    """General per-rank AlltoAllv: int64[world * group] row-major tables, row w
    = world rank w's own count/displacement vectors (full MPI generality; see
    comm.request._normalize_alltoallv_per_rank). 0 addr = packed default
    offsets / derived recv counts."""
    dist = _get(dist_h)
    gt = GroupType(group)
    g = dist._group(gt)
    gsize = 1 if g.is_self else g.size
    w = dist.topology.world_size
    rd = lambda a: _read_i64_array(a, w * gsize).reshape(w, gsize) if a else None
    buf = _read_world_buffer(dist, addr, send_len, data_type)
    req = dist.all_to_allv(
        buf, rd(send_counts_addr), rd(send_offsets_addr),
        rd(recv_counts_addr), rd(recv_offsets_addr), data_type, gt,
    )
    return _put((dist, req))


# ---- statistics (reference mlsl.hpp:651-726, c_bind stats wrappers) ----

def session_get_stats(sess_h: int) -> int:
    return _put(_get(sess_h).get_stats())


def stats_control(stats_h: int, what: int) -> int:
    """what: 0=start 1=stop 2=reset 3=is_enabled 4=is_started."""
    st = _get(stats_h)
    if what == 0:
        st.start()
    elif what == 1:
        st.stop()
    elif what == 2:
        st.reset()
    elif what == 3:
        return int(st.is_enabled())
    elif what == 4:
        return int(st.is_started())
    else:
        raise ValueError(f"unknown stats control {what}")
    return 0


def stats_query(stats_h: int, what: int, op_idx: int) -> int:
    """what: 0=comm_size 1=comm_cycles 2=compute_cycles 3=isolation_comm_cycles
    4=overlap_permille (hidden/isolation x 1000; -1 until isolation stats and
    accounted steps exist). Per-op with op_idx >= 0, totals with op_idx < 0.
    Cycles are nanoseconds (the TPU analog of the reference's rdtsc cycles)."""
    st = _get(stats_h)
    if what == 4:
        # index-keyed (robust to duplicate op names); out-of-range op_idx has
        # no slots and yields the no-data sentinel like the sibling queries
        f = st.get_overlap_fraction(None if op_idx < 0 else int(op_idx))
        return -1 if f is None else int(round(f * 1000))
    if op_idx < 0:
        return (st.get_total_comm_size(), st.get_total_comm_cycles(),
                st.get_total_compute_cycles(),
                st.get_total_isolation_comm_cycles())[what]
    return (st.get_comm_size(op_idx), st.get_comm_cycles(op_idx),
            st.get_compute_cycles(op_idx),
            st.get_isolation_comm_cycles(op_idx))[what]


def stats_print(stats_h: int) -> int:
    _get(stats_h).print_()
    return 0


# ---- parameter sets (cont.) ----

def param_query(op_h: int, ps_idx: int, what: int) -> int:
    """what: 0=global_kernel_count 1=local_kernel_count 2=owned_kernel_count
    3=kernel_size 4=is_distributed_update."""
    ps = _get(op_h).get_parameter_set(ps_idx)
    return (ps.get_global_kernel_count(), ps.get_local_kernel_count(),
            ps.get_owned_kernel_count(), ps.get_kernel_size(),
            int(ps.is_distributed_update()))[what]


def param_owned_offset(op_h: int, ps_idx: int, data_idx: int) -> int:
    """Per-rank GetOwnedKernelOffset (reference include/mlsl.hpp:298) with the
    rank's data-group index explicit."""
    return _get(op_h).get_parameter_set(ps_idx).get_owned_kernel_offset(data_idx)


def param_test_gradient_comm(op_h: int, ps_idx: int) -> int:
    done, _ = _get(op_h).get_parameter_set(ps_idx).test_gradient_comm()
    return 1 if done else 0


def param_start_increment_comm(op_h: int, ps_idx: int, addr: int, data_type: int) -> int:
    op = _get(op_h)
    ps = op.get_parameter_set(ps_idx)
    count = ps.get_owned_kernel_count() * ps.get_kernel_size()
    buf = _read_world_buffer(op.distribution, addr, count, data_type)
    ps.start_increment_comm(buf)
    return 0


def param_wait_increment_comm(op_h: int, ps_idx: int, out_addr: int, data_type: int) -> int:
    """Returns the per-rank element count written (0 if no comm was needed)."""
    op = _get(op_h)
    ps = op.get_parameter_set(ps_idx)
    out = ps.wait_increment_comm()
    if out is None:
        return 0
    n = int(np.asarray(out).shape[-1])
    _write_world_buffer(op.distribution, out, out_addr, n, data_type)
    return n


def param_start_gradient_comm(op_h: int, ps_idx: int, addr: int, data_type: int) -> int:
    op = _get(op_h)
    ps = op.get_parameter_set(ps_idx)
    count = ps.get_local_kernel_count() * ps.get_kernel_size()
    buf = _read_world_buffer(op.distribution, addr, count, data_type)
    ps.start_gradient_comm(buf)
    return 0


def param_wait_gradient_comm(op_h: int, ps_idx: int, out_addr: int, data_type: int) -> int:
    """Returns the per-rank element count written (0 if no comm was needed)."""
    op = _get(op_h)
    ps = op.get_parameter_set(ps_idx)
    out = ps.wait_gradient_comm()
    if out is None:
        return 0
    n = int(np.asarray(out).shape[-1])
    _write_world_buffer(op.distribution, out, out_addr, n, data_type)
    return n


def handle_release(hid: int) -> int:
    return _release(hid)
