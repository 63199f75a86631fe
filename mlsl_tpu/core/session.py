"""Session, OperationRegInfo, Operation: the graph registration and commit path.

Mirrors the reference (include/mlsl.hpp:510-798, src/mlsl_impl.cpp:540-600,
src/mlsl_impl.hpp:941-1097): a Session collects Operations sharing a global minibatch
size; each Operation is registered from an OperationRegInfo (activation shapes +
parameter sets) against a Distribution; SetPrev/SetNext wire graph edges; Commit
finalizes every edge (picks the peer-connection case, builds the collectives) and runs
the isolation benchmark when statistics are enabled.

The TPU "Commit = compile" analog: all CommRequests are built over cached jitted
shard_map programs at commit time, so the training loop only re-dispatches compiled
executables (the reference likewise builds all CommRequests once and reuses them,
src/mlsl_impl.hpp:1024-1071).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

from mlsl_tpu.core.activation import Activation
from mlsl_tpu.core.parameter_set import ParameterSet
from mlsl_tpu.core.stats import Statistics
from mlsl_tpu.log import log_debug, mlsl_assert
from mlsl_tpu.types import CompressionType, DataType, OpType, PhaseType


@dataclasses.dataclass
class _RegEntry:
    count: int
    size: int
    data_type: DataType
    distributed_update: bool = False
    compression: CompressionType = CompressionType.NONE


class OperationRegInfo:
    """Shape registration for one Operation (reference include/mlsl.hpp:510-556)."""

    def __init__(self, op_type: OpType):
        self.op_type = OpType(op_type)
        self.name = ""
        self.inputs: List[_RegEntry] = []
        self.outputs: List[_RegEntry] = []
        self.parameter_sets: List[_RegEntry] = []

    def set_name(self, name: str) -> None:
        self.name = name

    def add_input(self, count: int, size: int, data_type=DataType.FLOAT) -> int:
        self.inputs.append(_RegEntry(int(count), int(size), DataType(data_type)))
        return len(self.inputs) - 1

    def add_output(self, count: int, size: int, data_type=DataType.FLOAT) -> int:
        self.outputs.append(_RegEntry(int(count), int(size), DataType(data_type)))
        return len(self.outputs) - 1

    def add_parameter_set(
        self,
        kernel_count: int,
        kernel_size: int,
        data_type=DataType.FLOAT,
        distributed_update: bool = False,
        compression_type=CompressionType.NONE,
    ) -> int:
        self.parameter_sets.append(
            _RegEntry(
                int(kernel_count),
                int(kernel_size),
                DataType(data_type),
                bool(distributed_update),
                CompressionType(compression_type),
            )
        )
        return len(self.parameter_sets) - 1

    def validate(self) -> None:
        if self.op_type == OpType.DATA:
            mlsl_assert(not self.inputs, "DATA op cannot have inputs")
        if self.op_type == OpType.EVAL:
            mlsl_assert(not self.outputs, "EVAL op cannot have outputs")

    # PascalCase parity aliases
    SetName = set_name
    AddInput = add_input
    AddOutput = add_output
    AddParameterSet = add_parameter_set


class Operation:
    """One graph node (reference include/mlsl.hpp:564-645, OperationImpl
    src/mlsl_impl.hpp:941-1097)."""

    def __init__(self, reg: OperationRegInfo, session: "Session", distribution, op_idx: int):
        reg.validate()
        self.session = session
        self.distribution = None
        self._reg = reg
        self.op_type = reg.op_type
        self.name = reg.name or f"op{op_idx}"
        self.op_idx = op_idx
        self.inputs: List[Activation] = []
        self.outputs: List[Activation] = []
        self.parameter_sets: List[ParameterSet] = []
        if distribution is not None:
            self.set_distribution(distribution)

    def set_distribution(self, distribution) -> None:
        """Bind (or late-bind) the parallelism layout. The reference allows
        AddOperation(regInfo, NULL) followed by Operation::SetDistribution
        (include/mlsl.hpp:765-767, :574); activations and parameter sets are
        derived here because their partitioning depends on the grid."""
        mlsl_assert(
            self.distribution is None, "distribution can be set only once"
        )
        mlsl_assert(
            not getattr(distribution, "is_ragged", False),
            "operations require equal-sized color groups: the minibatch/kernel "
            "partitioning assumes a uniform group size (ragged partitions "
            "support Distribution collectives only)",
        )
        self.distribution = distribution
        reg = self._reg

        data_size = distribution.get_process_count_data()
        global_mb = self.session.global_minibatch_size
        mlsl_assert(
            global_mb % data_size == 0,
            "global minibatch %d not divisible by data parts %d",
            global_mb,
            data_size,
        )
        self.global_minibatch_size = global_mb
        self.local_minibatch_size = global_mb // data_size

        self.inputs = [Activation(self, r, True, i) for i, r in enumerate(reg.inputs)]
        self.outputs = [Activation(self, r, False, i) for i, r in enumerate(reg.outputs)]
        self.parameter_sets = [
            ParameterSet(self, r, i) for i, r in enumerate(reg.parameter_sets)
        ]

    # -- introspection -----------------------------------------------------

    def get_op_type(self) -> OpType:
        return self.op_type

    def get_name(self) -> str:
        return self.name

    def get_distribution(self):
        return self.distribution

    def get_session(self):
        return self.session

    def get_global_minibatch_size(self) -> int:
        return self.global_minibatch_size

    def get_local_minibatch_size(self) -> int:
        return self.local_minibatch_size

    def get_global_minibatch_offset(self, data_idx: int = 0) -> int:
        return self.local_minibatch_size * data_idx

    def get_input_count(self) -> int:
        return len(self.inputs)

    def get_input(self, idx: int) -> Activation:
        return self.inputs[idx]

    def get_output_count(self) -> int:
        return len(self.outputs)

    def get_output(self, idx: int) -> Activation:
        return self.outputs[idx]

    def get_parameter_set_count(self) -> int:
        return len(self.parameter_sets)

    def has_parameter_sets(self) -> bool:
        return bool(self.parameter_sets)

    def get_parameter_set(self, idx: int) -> ParameterSet:
        return self.parameter_sets[idx]

    # -- graph wiring (reference src/mlsl_impl.cpp:68-113) -----------------

    def set_prev(self, prev: Optional["Operation"], input_idx: int, prev_out_idx: int) -> None:
        act = self.inputs[input_idx]
        if prev is None:
            act.set_peer(None)
            return
        mlsl_assert(prev.session is self.session, "different sessions")
        prev.outputs[prev_out_idx].set_peer(act)

    def set_next(self, nxt: Optional["Operation"], output_idx: int, next_in_idx: int) -> None:
        act = self.outputs[output_idx]
        if nxt is None:
            act.set_peer(None)
            return
        mlsl_assert(nxt.session is self.session, "different sessions")
        act.set_peer(nxt.inputs[next_in_idx])

    # PascalCase parity aliases
    GetOpType = get_op_type
    GetName = get_name
    GetDistribution = get_distribution
    GetSession = get_session
    GetGlobalMinibatchSize = get_global_minibatch_size
    GetLocalMinibatchSize = get_local_minibatch_size
    GetGlobalMinibatchOffset = get_global_minibatch_offset
    GetInputCount = get_input_count
    GetInput = get_input
    GetOutputCount = get_output_count
    GetOutput = get_output
    GetParameterSetCount = get_parameter_set_count
    GetParameterSet = get_parameter_set
    HasParameterSets = has_parameter_sets
    SetDistribution = set_distribution
    SetPrev = set_prev
    SetNext = set_next


class Session:
    """A collection of Operations with one global minibatch size
    (reference include/mlsl.hpp:731-797)."""

    def __init__(self, env, phase_type: PhaseType = PhaseType.TRAIN):
        self.env = env
        self.phase_type = PhaseType(phase_type)
        self.global_minibatch_size = 0
        self.operations: List[Operation] = []
        self.stats = Statistics(self)
        self._committed = False
        self._valid = True

    def _invalidate(self):
        self._valid = False

    def set_global_minibatch_size(self, size: int) -> None:
        mlsl_assert(size > 0, "global minibatch size must be positive")
        self.global_minibatch_size = int(size)

    def get_global_minibatch_size(self) -> int:
        return self.global_minibatch_size

    def get_phase_type(self) -> PhaseType:
        return self.phase_type

    def create_operation_reg_info(self, op_type: OpType) -> OperationRegInfo:
        return OperationRegInfo(op_type)

    def delete_operation_reg_info(self, reg: OperationRegInfo) -> None:
        return None

    def add_operation(self, reg: OperationRegInfo, distribution=None) -> int:
        """Register an operation. distribution may be None (reference
        AddOperation(regInfo, NULL)) and bound later with
        Operation.set_distribution — it must be bound before Commit."""
        mlsl_assert(self.global_minibatch_size > 0, "set global minibatch size first")
        mlsl_assert(
            distribution is None or not getattr(distribution, "is_ragged", False),
            "operations require equal-sized color groups: the minibatch/kernel "
            "partitioning assumes a uniform group size (ragged partitions "
            "support Distribution collectives only)",
        )
        op = Operation(reg, self, distribution, len(self.operations))
        self.operations.append(op)
        return len(self.operations) - 1

    # reference mlsl.py exposes both spellings
    add_operation_with_distribution = add_operation

    def remove_operations(self) -> None:
        self.operations.clear()
        self._committed = False

    def get_operation_count(self) -> int:
        return len(self.operations)

    def get_operation(self, idx: int) -> Operation:
        return self.operations[idx]

    def get_stats(self) -> Statistics:
        return self.stats

    def commit(self) -> None:
        """Finalize all graph edges and build the collectives
        (reference SessionImpl::Commit src/mlsl_impl.cpp:567-578)."""
        for op in self.operations:
            mlsl_assert(
                op.distribution is not None,
                "operation %s has no distribution bound at Commit", op.name,
            )
        for op in self.operations:
            for act in op.outputs:
                act.init_peer_connection()
            for act in op.inputs:
                act.init_peer_connection()
        self._committed = True
        cfg = self.env.config
        if cfg is not None and getattr(cfg, "tune_codec", False):
            # MLSL_TUNE_CODEC=1: measure per-set gradient sensitivity and
            # assign codec x block against the convergence (NSR) budget —
            # BEFORE buckets form, so they partition on the calibrated
            # codecs (tuner/calibrate.py; docs/TUNING.md §22)
            from mlsl_tpu.tuner.calibrate import calibrate_session

            calibrate_session(self)
        if cfg is not None and cfg.grad_bucket_mb > 0:
            from mlsl_tpu.core.bucketing import build_buckets

            build_buckets(self, cfg.grad_bucket_mb)
        if cfg is not None and getattr(cfg, "verify", False):
            # MLSL_VERIFY=1: statically verify the collective plan NOW —
            # after buckets formed (their geometry is checked) and before
            # the precompile warm spends compile time on a plan the
            # verifier may reject (mlsl_tpu/analysis/plan.py; severity
            # behavior under MLSL_VERIFY_SEVERITY)
            from mlsl_tpu.analysis.plan import run_commit_verify
            from mlsl_tpu.analysis.protocol import run_commit_protocol_check

            run_commit_verify(self)
            # same gate, second pass: exhaustively explore the control-plane
            # membership/drain and elastic shrink/grow protocol models
            # (deadlock-freedom, no dual coordinator, no lost drain-ack) —
            # memoized process-wide, so repeated commits pay once
            # (mlsl_tpu/analysis/protocol.py, A15x)
            run_commit_protocol_check(self)
        if cfg is not None and cfg.precompile:
            self.precompile_collectives()
        self.stats.initialize()
        if cfg is not None and cfg.enable_stats:
            self.stats.collect_isolation_stats()

    def precompile_collectives(self) -> int:
        """AOT-warm every collective program this session's committed graph
        can dispatch — activation edges, per-layer gradient/increment
        requests (plain, chunked, quant-ring), and the coalesced GradBucket
        programs (pack, collective, unpack) — by executing each once on zero
        buffers, so step 0 of the training loop contains no collective
        compilation (run automatically at Commit under MLSL_PRECOMPILE=1).

        Idempotent across sessions: programs already warmed under the same
        plan key (the collectives-cache identity: kind, group, dtype, count,
        compression) are skipped via collectives._plan_cache, which
        collectives.clear_cache() clears together with the program cache.
        Returns the number of programs run."""
        from mlsl_tpu.comm.collectives import _group_key, _plan_cache

        n = 0

        from mlsl_tpu.types import CompressionType

        cfg = self.env.config

        def warm_req(req):
            nonlocal n
            if req is None or not req.is_setup:
                return
            d = req.desc
            # compressed programs are parameterized by codec geometry the
            # desc does not carry (quant_ring/sparse cache by it): a plan
            # entry recorded under one block size / ratio / custom codec must
            # not suppress warming a program built under another
            codec_key = ()
            if d.compression != CompressionType.NONE:
                codec_key = (cfg.quant_block_elems, cfg.topk_ratio,
                             id(cfg.custom_codec))
            # pallas-ring variant identity: a slot-geometry or direction
            # change compiles a DIFFERENT kernel, and a plan entry recorded
            # under the old geometry must not skip re-warming it
            pallas_key = ()
            if req.algo in ("pallas_ring", "pallas_ring2d"):
                pallas_key = (
                    int(getattr(cfg, "pallas_ring_slots", 2)),
                    bool(getattr(cfg, "pallas_ring_bidir", False)),
                )
            elif req.algo == "pallas_rhd":
                # the rhd kernel's only compile-time knob is slot depth
                pallas_key = (int(getattr(cfg, "pallas_ring_slots", 2)),)
            elif req.algo == "pallas_a2a":
                # wire-codec identity: toggling the int8 codec (or its block
                # grid) compiles a DIFFERENT kernel
                from mlsl_tpu.ops import a2a_kernels

                pallas_key = (
                    int(getattr(cfg, "pallas_ring_slots", 2)),
                    int(getattr(cfg, "quant_block_elems", 256)),
                    bool(a2a_kernels.quant_enabled(cfg)),
                )
            elif req.algo == "hier":
                # two-tier variant identity: a DCN-codec or tier-shape
                # change compiles a DIFFERENT program (comm/algos/hier.py),
                # and a stale plan entry must not skip re-warming it
                import os

                pallas_key = (
                    str(getattr(cfg, "hier_dcn_codec", "int8")),
                    os.environ.get("MLSL_MESH_TIERS", ""),
                )
            # the algorithm identity is part of the plan key: a profile (or
            # MLSL_ALGO) switching a request from 'lax' to 'rhd' between
            # sessions compiles a DIFFERENT program, and a stale plan entry
            # recorded under the old algorithm must not skip warming it
            key = (
                "req", d.kind, _group_key(d.group), int(d.data_type), d.count,
                int(d.compression), d.recv_count,
                None if d.op is None else int(d.op), d.root,
                len(req._chunk_slices), codec_key, pallas_key, req.algo,
            )
            if key in _plan_cache:
                return
            n += req.precompile()
            _plan_cache[key] = True

        buckets: dict = {}
        for op in self.operations:
            for act in op.inputs + op.outputs:
                warm_req(act.comm_req)
            for ps in op.parameter_sets:
                warm_req(ps.grad_req)
                warm_req(ps.inc_req)
                for b in (ps.bucket, ps.inc_bucket):
                    if b is not None:
                        buckets[id(b)] = b
        # buckets warm per INSTANCE (GradBucket.precompile is idempotent on
        # itself): their pack/unpack are per-instance jit closures, so a
        # shape-identity plan entry would skip a same-shaped sibling whose
        # caches are cold. Only the bucket's underlying collective comes from
        # the shared module caches — re-warming it costs one cheap execution.
        for b in buckets.values():
            n += b.precompile()
        if n:
            log_debug("precompile: %d collective program(s) warmed at commit", n)
        return n

    # -- statistics plumbing ----------------------------------------------

    def _stat_event(self, entity, action: str, is_param: bool = False, is_increment: bool = False):
        # Gate on started, not the env flag: MLSL_STATS drives the default via
        # initialize(), but Statistics.start() must also work programmatically
        # (reference Statistics::Start, include/mlsl.hpp:662) — a caller may
        # turn accounting on for a few steps only.
        if self.stats.is_started():
            self.stats.update(entity, action, is_param, is_increment)

    # PascalCase parity aliases
    SetGlobalMinibatchSize = set_global_minibatch_size
    GetGlobalMinibatchSize = get_global_minibatch_size
    GetPhaseType = get_phase_type
    CreateOperationRegInfo = create_operation_reg_info
    DeleteOperationRegInfo = delete_operation_reg_info
    AddOperation = add_operation
    RemoveOperations = remove_operations
    GetOperationCount = get_operation_count
    GetOperation = get_operation
    GetStats = get_stats
    Commit = commit
    PrecompileCollectives = precompile_collectives
