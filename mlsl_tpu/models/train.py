"""MLSL-driven data-parallel training: the Session/Operation graph in the loop.

This is the BASELINE config-5 workload shape (Caffe ResNet-50 per-layer grad sync,
reference canonical loop tests/examples/mlsl_test/mlsl_test.cpp:660-698) done the TPU
way:

- one jitted shard_map computes *local* (unsynced) gradients per device — the analog of
  each MPI rank's backprop producing local gradients;
- each model layer is an MLSL Operation whose ParameterSet carries the gradient
  collective; StartGradientComm is issued per layer in reverse (backprop) order so the
  newest-first priority scheduler sees the same stream the reference's eplib does;
- WaitGradientComm + a jitted update apply SGD, with the distributed-update
  (ReduceScatter / local update / AllGather-increment) path supported per layer.

Gradients cross the framework boundary as distributed buffers (R, D, S, M, count): the
device-local flattened layer gradient is the shard — no host round-trips in the loop.
"""

from __future__ import annotations

import functools
import time
from typing import Callable, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from mlsl_tpu import chaos
from mlsl_tpu.comm.collectives import _BUF_SPEC
from mlsl_tpu.comm.mesh import (
    DATA_AXIS,
    GRID_AXES,
    NUM_GRID_AXES,
    SEQ_AXIS,
)
from mlsl_tpu.log import mlsl_assert
from mlsl_tpu.obs import metrics as obs_metrics
from mlsl_tpu.obs import tracer as obs_trace
from mlsl_tpu.types import CompressionType, DataType, OpType


from mlsl_tpu.comm.collectives import smap  # noqa: F401  (canonical home)


def _flatten_layer(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])


def build_owned_increment_fn(mesh, lr: float, norm: float, with_scale: bool = False):
    """Jitted fn: owned-shard gradient buffer -> owned-shard SGD increment
    (-lr * g / norm), shared by every distributed-update trainer. With
    with_scale the fn takes an extra replicated scalar multiplied into the
    gradient (global-norm clipping)."""

    def body(g, s):
        return (-lr * s * g.reshape(g.shape[NUM_GRID_AXES:]) / norm)[
            None, None, None, None
        ]

    if with_scale:
        def inc_s(g, s):
            return smap(
                body, mesh, in_specs=(_BUF_SPEC, P()), out_specs=_BUF_SPEC
            )(g, s)

        return jax.jit(inc_s)

    def inc(g):
        return smap(
            lambda g: body(g, 1.0), mesh, in_specs=_BUF_SPEC, out_specs=_BUF_SPEC
        )(g)

    return jax.jit(inc)


def build_owned_norm_fn(mesh, norm: float, grad_axes=(DATA_AXIS, SEQ_AXIS)):
    """Jitted fn: dict of owned-shard gradient buffers -> global L2 norm of the
    mean gradient (replicated scalar). Owned shards partition the parameters
    across the gradient group, so sq-sum locally + psum = the full norm — the
    cross-shard reduction ZeRO-1 global-norm clipping needs."""

    def gnorm(owned):
        names = sorted(owned)

        def body(*gs):
            local = sum(jnp.sum((g / norm) ** 2) for g in gs)
            # mlsl-lint: disable=A201 -- the global-norm reduction is part
            # of the clip math inside the compiled step, not a request
            return jnp.sqrt(jax.lax.psum(local, grad_axes))

        sm = smap(
            body, mesh,
            in_specs=tuple(_BUF_SPEC for _ in names),
            out_specs=P(),
            check=False,
        )
        return sm(*[owned[n] for n in names])

    return jax.jit(gnorm)


def _leaf_buf_spec(leaf) -> P:
    """PartitionSpec for a distributed buffer with arbitrary payload rank."""
    return P(*GRID_AXES, *([None] * (leaf.ndim - NUM_GRID_AXES)))


def _clip_scale(sq_norm, clip: float):
    """Scale factor applying an L2 gradient clip: min(1, clip / norm)."""
    return jnp.minimum(1.0, clip / jnp.maximum(jnp.sqrt(sq_norm), 1e-12))


def init_shard_opt_state(topo, optimizer, count: int):
    """Optimizer state over a flat (count,) per-rank shard, as distributed
    buffers (scalar leaves ride as payload shape (1,))."""
    state = optimizer.init(jnp.zeros((count,), jnp.float32))
    grid = topo.grid_shape

    def bufferize(leaf):
        arr = np.asarray(leaf)
        if arr.ndim == 0:
            arr = arr.reshape(1)
        return topo.shard_buffer(
            np.ascontiguousarray(np.broadcast_to(arr, grid + arr.shape))
        )

    return jax.tree.map(bufferize, state)


def build_owned_opt_increment_fn(mesh, optimizer, norm: float,
                                 with_scale: bool = False):
    """Jitted (owned-shard grad buffer, state buffers[, scale]) -> (increment
    buffer, new state buffers): the optax analog of build_owned_increment_fn.
    The transform sees each rank's flat (owned,) shard, so only elementwise/
    shard-local transforms are correct here (see DataParallelTrainer)."""

    def body(g, state, s):
        gl = s * g.reshape(g.shape[NUM_GRID_AXES:]) / norm
        local = jax.tree.map(
            lambda l: l.reshape(l.shape[NUM_GRID_AXES:]), state
        )
        updates, new_state = optimizer.update(gl, local)
        grid1 = (1,) * NUM_GRID_AXES
        return (
            updates.reshape(grid1 + updates.shape),
            jax.tree.map(lambda l: l.reshape(grid1 + l.shape), new_state),
        )

    if with_scale:
        def inc_s(g, state, s):
            state_specs = jax.tree.map(_leaf_buf_spec, state)
            sm = smap(
                body, mesh,
                in_specs=(_BUF_SPEC, state_specs, P()),
                out_specs=(_BUF_SPEC, state_specs),
                check=False,
            )
            return sm(g, state, s)

        return jax.jit(inc_s)

    def inc(g, state):
        state_specs = jax.tree.map(_leaf_buf_spec, state)
        sm = smap(
            lambda g, st: body(g, st, 1.0), mesh,
            in_specs=(_BUF_SPEC, state_specs),
            out_specs=(_BUF_SPEC, state_specs),
            check=False,
        )
        return sm(g, state)

    return jax.jit(inc)


def build_local_grads(loss_fn, layers, get_layer, padded):
    """The local-gradient core shared by the host ``_grad_fn`` and the
    compiled overlap engine's fused program: ``(params, x, y) -> (scalar
    loss, {layer: padded flat grad})`` on already-squeezed local shards.
    ONE implementation on purpose — the flatten/pad policy is what the
    compiled-vs-host lockstep parity pins, so it must never diverge."""

    def local_grads(params, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, (x, y))
        flat = {}
        for name in layers:
            g = _flatten_layer(get_layer(grads, name))
            flat[name] = jnp.pad(g, (0, padded[name] - g.shape[0]))
        return loss, flat

    return local_grads


def _unflatten_like(tree, flat: jax.Array):
    leaves, treedef = jax.tree.flatten(tree)
    out, off = [], 0
    for l in leaves:
        n = int(np.prod(l.shape))
        out.append(flat[off : off + n].reshape(l.shape).astype(l.dtype))
        off += n
    return jax.tree.unflatten(treedef, out)


class DataParallelTrainer:
    """Trains a model with per-layer MLSL gradient sync.

    model contract:
      params: pytree; loss_fn(params, batch) -> scalar;
      layers: ordered list of names; get_layer(params, name) -> subtree (its flattened
      size is the Operation's kernel count).

    Attribute contract: ``trainer.params`` is replaced every step, and the
    previous value's buffers are DONATED to XLA (in-place HBM update) on the
    fused, per-layer, and distributed-update paths — a reference held across a
    step() becomes unreadable. Snapshot with ``jax.device_get(trainer.params)``
    or construct with donate_params=False (the overlap_updates path never
    donates). Optimizer state follows the same donation contract.
    """

    def __init__(
        self,
        env,
        dist,
        session,
        params,
        loss_fn: Callable,
        layers: List[str],
        get_layer: Callable,
        distributed_update: bool = False,
        compression: CompressionType = CompressionType.NONE,
        lr: float = 0.05,
        donate_params: bool = True,
        overlap_updates: bool = False,
        overlap_compiled: Optional[bool] = None,
        force_graph_path: bool = False,
        optimizer=None,
        clip_global_norm: Optional[float] = None,
    ):
        """optimizer: an optax.GradientTransformation (e.g. optax.adam(lr)).
        None keeps the built-in SGD (p - lr * mean_grad). With
        distributed_update=True the optimizer state lives ONLY on each rank's
        owned gradient shard (ZeRO-1 proper: Adam moments sharded over the data
        group, reference owned-kernel math src/mlsl_impl.cpp:401-435). The
        sharded path runs the transform on each rank's flat (owned,) shard, so
        a black-box optax transform is correct only if it is elementwise/
        shard-local (adam, sgd with momentum, rmsprop, ...); params-consuming
        (weight decay) or shape-dependent black-box transforms would silently
        see per-shard views. The shape-dependent cases this framework supports
        cross-shard have dedicated implementations: pass
        mlsl_tpu.optim.ShardedAdafactor for factored-stats Adafactor under
        ZeRO-1, and clip_global_norm= (below) for global-norm clipping.

        overlap_compiled: arm the compiled overlap engine (comm/overlap.py;
        None = the MLSL_OVERLAP_COMPILED config default): ONE single-dispatch
        donation-enabled step program with every layer's gradient collective
        emitted in-graph, newest-first, staged over MLSL_OVERLAP_STAGES unit
        starts — XLA's latency-hiding scheduler overlaps the comm instead of
        the host Start/Wait loop. SGD only (an optax optimizer, ZeRO-1, or
        overlap_updates impose their own schedules — asserted when requested
        explicitly); TOPK/custom-codec/color-group graphs fall back to the
        host path, which stays the default and the parity oracle
        (tests/test_overlap_compiled.py). With the sentinel quality gate
        armed the engine runs the two-program split (grad program + one
        compiled comm/update program) so the gate keeps its host-side
        gradient boundary.

        clip_global_norm: clip the (mean) gradient to this global L2 norm
        BEFORE the optimizer — on every path, including ZeRO-1, where the norm
        is assembled from per-rank owned-shard partials via a psum over the
        gradient group (the cross-shard reduction a black-box optax
        clip_by_global_norm cannot perform there)."""
        from mlsl_tpu.optim import ShardedAdafactor

        self.env = env
        self.dist = dist
        self.session = session
        self.loss_fn = loss_fn
        self.layers = layers
        self.get_layer = get_layer
        self.lr = lr
        self.optimizer = optimizer
        # ShardedAdafactor is a config marker: the plain/fused paths run its
        # optax equivalent; distributed update runs the cross-shard factored
        # implementation (mlsl_tpu/optim.py) with identical numerics.
        self._af_cfg = optimizer if isinstance(optimizer, ShardedAdafactor) else None
        self._optax_opt = (
            optimizer.as_optax() if self._af_cfg is not None else optimizer
        )
        self.clip_global_norm = clip_global_norm
        self.mesh = dist.topology.mesh
        mlsl_assert(
            not (optimizer is not None and overlap_updates),
            "overlap_updates is not supported with an optax optimizer "
            "(per-layer state slicing would impose its own schedule)",
        )
        # Normalizer must match the reduction group (grad_group = data x seq); this
        # trainer only shards the batch, so it requires seq_parts == 1 and the two
        # coincide (HybridTrainer handles sequence-parallel grids).
        mlsl_assert(
            dist.get_process_count_model() == 1
            and dist.replica_count == 1
            and dist.get_seq_parts() == 1,
            "DataParallelTrainer requires model=seq=1 and replica_count == 1 "
            "(got model=%d, seq=%d, replicas=%d)",
            dist.get_process_count_model(),
            dist.get_seq_parts(),
            dist.replica_count,
        )
        self.data_size = dist.get_process_count_data()

        # Register one Operation per layer (reference per-layer Caffe graph).
        self.ops = {}
        self.layer_counts = {}
        for name in layers:
            count = int(
                sum(np.prod(l.shape) for l in jax.tree.leaves(get_layer(params, name)))
            )
            self.layer_counts[name] = count
            reg = session.create_operation_reg_info(OpType.CC)
            reg.set_name(name)
            reg.add_input(1, 1)
            reg.add_output(1, 1)
            reg.add_parameter_set(
                count, 1, DataType.FLOAT,
                distributed_update=distributed_update,
                compression_type=compression,
            )
            self.ops[name] = session.get_operation(session.add_operation(reg, dist))
        session.commit()
        # distributed update pads the local kernel count so every data rank owns an
        # equal shard (reference src/mlsl_impl.cpp:403-405); grads buffers must match.
        self.padded_counts = {
            name: self.ops[name].get_parameter_set(0).get_local_kernel_count()
            for name in layers
        }

        # When Commit shows no parameter set needs communication (single data rank),
        # the per-layer Start/Wait structure buys nothing — fuse the entire step into
        # one XLA program (with donated, in-place-updated params) so the framework
        # beats a monolithic jit rather than matching it.
        needs_comm = any(
            self.ops[n].get_parameter_set(0).need_comm for n in layers
        )
        # Integrity sentinel (mlsl_tpu.sentinel): the step quality gate and
        # the cross-replica consistency audit, armed from Config
        # (MLSL_SENTINEL_*). Public: FaultTolerantLoop drives the audit
        # cadence and verified-checkpoint fingerprints through it.
        self.sentinel = None
        cfg = env.config
        if cfg is not None:
            from mlsl_tpu import sentinel as sentinel_mod

            if sentinel_mod.armed(cfg):
                self.sentinel = sentinel_mod.Sentinel.from_config(
                    cfg, self.mesh
                )
        # Straggler sentinel (obs/straggler.py): per-replica step-time skew
        # watch, armed from Config (MLSL_STRAGGLER_*). This process feeds
        # its own replica id; FaultTolerantLoop polls shed_candidate()
        # between steps and hands a confirmed straggler to the elastic
        # coordinator.
        self.straggler = None
        if cfg is not None:
            from mlsl_tpu.obs import straggler as straggler_mod

            if straggler_mod.armed(cfg):
                self.straggler = straggler_mod.StragglerSentinel(
                    skew=cfg.straggler_skew,
                    every=cfg.straggler_every,
                    sustain=cfg.straggler_sustain,
                    shed=cfg.straggler_shed,
                )
        # straggler attribution: the pod rank when a control plane is armed
        # (pod-wide peer medians need pod-unique replica ids — remote ranks'
        # samples arrive over heartbeat frames under THEIR rank), else
        # jax.process_index() as before
        from mlsl_tpu import control as control_mod

        self._replica_id = control_mod.replica_id(jax.process_index())
        self._gnorm_fn = None       # lazy telemetry grad-norm program
        self._stall_ms_seen = 0.0   # FEED stall total at the last sample
        # force_graph_path bypasses the fused shortcut so the per-layer
        # Start/Wait machinery can be measured even when no comm is needed
        # (chip_smoke.py P1 trains both and compares them on one chip). An
        # armed quality gate does the same: the gate screens at the
        # gradient boundary, which the fused program never exposes.
        use_fused = (
            not needs_comm and not force_graph_path
            and not (self.sentinel is not None and self.sentinel.gate_armed)
        )
        self.donate_params = bool(donate_params)
        sharding = NamedSharding(self.mesh, P())
        # Donation happens on the fused and barrier-update paths; the
        # overlap_updates per-layer path never donates (but the fused shortcut
        # can still engage under overlap_updates on a no-comm grid). Make the
        # owning copy exactly when some donating program will consume
        # self.params — device_put alone can alias the caller's on-device
        # arrays, and donating an aliased buffer deletes the caller's tree.
        will_donate = donate_params and (use_fused or not overlap_updates)
        if not will_donate:
            self.params = jax.device_put(params, sharding)
        else:
            self.params = jax.tree.map(
                lambda x: jax.device_put(jnp.array(x, copy=True), sharding), params
            )
        # Optimizer state: replicated alongside the params on the plain path;
        # per-layer buffers over each rank's OWNED gradient shard under
        # distributed update (ZeRO-1: moments sharded over the data group).
        self._opt_state = None
        self._du_opt_state = None
        self._af_layouts = {}
        self._du_inc_fns = None
        self._needs_comm = needs_comm
        self._accum_fns = None
        self._du_norm_fn = None
        if optimizer is not None:
            if distributed_update and needs_comm:
                self._du_opt_state = {
                    n: self._init_owned_opt_state(n) for n in layers
                }
            else:
                # No gradient comm (single data rank, fused or forced graph
                # path): owned == full, replicated state drives the plain
                # update.
                self._opt_state = jax.device_put(
                    self._optax_opt.init(self.params), sharding
                )
        self._grad_fn = self._build_grad_fn()
        self._update_fn = self._build_update_fn()
        self._du_inc_fn = self._build_du_inc_fn() if distributed_update else None
        self._du_apply_fn = self._build_du_apply_fn() if distributed_update else None
        self.distributed_update = distributed_update
        self._fused_fn = self._build_fused_fn() if use_fused else None
        # Test-driven overlap (the reference's canonical loop polls
        # TestGradientComm and updates each layer as its collective lands,
        # tests/examples/mlsl_test/mlsl_test.cpp:660-698): per-layer jitted
        # updates dispatched on completion instead of one barrier-then-update.
        mlsl_assert(
            not (overlap_updates and distributed_update),
            "overlap_updates is not supported together with distributed_update "
            "(the increment all-gather imposes its own schedule)",
        )
        self.overlap_updates = overlap_updates
        self._layer_update_fns = (
            {n: self._build_layer_update_fn(n) for n in layers}
            if self.overlap_updates
            else None
        )
        # Compiled overlap engine (comm/overlap.py): the in-graph per-layer
        # comm schedule. Explicitly requesting it alongside a mode that
        # imposes its own schedule is a usage error; the env-armed default
        # (MLSL_OVERLAP_COMPILED=1) silently skips those graphs instead, so
        # one exported knob doesn't break unrelated trainers.
        if overlap_compiled:
            mlsl_assert(
                optimizer is None,
                "overlap_compiled is not supported with an optax optimizer "
                "(per-layer fused updates would impose their own state "
                "slicing)",
            )
            mlsl_assert(
                not distributed_update,
                "overlap_compiled is not supported with distributed_update "
                "(the increment all-gather imposes its own schedule)",
            )
            mlsl_assert(
                not overlap_updates,
                "overlap_compiled replaces overlap_updates (the schedule "
                "lives in the compiled program, not the host poll loop)",
            )
        want_overlap = (
            overlap_compiled if overlap_compiled is not None
            else bool(cfg is not None and cfg.overlap_compiled)
        )
        self._overlap = None
        if (
            want_overlap
            and optimizer is None
            and not distributed_update
            and not overlap_updates
            and self._fused_fn is None
        ):
            from mlsl_tpu.comm import overlap as overlap_mod

            # may return None (TOPK / custom codec / color groups ride the
            # host path)
            self._overlap = overlap_mod.engine_for_trainer(self, cfg)
        # monotonically increasing step() counter — trace spans
        # (mlsl_tpu.obs) carry it so a timeline row maps back to a step
        self._step_no = 0

    # -- compiled pieces ---------------------------------------------------

    def _init_owned_opt_state(self, name: str):
        """Optimizer state over this layer's owned shard (ZeRO-1)."""
        from mlsl_tpu import optim

        ps = self.ops[name].get_parameter_set(0)
        if self._af_cfg is not None:
            layout = optim.build_adafactor_layout(
                [tuple(l.shape)
                 for l in jax.tree.leaves(self.get_layer(self.params, name))],
                self.padded_counts[name],
                self.data_size,
                self._af_cfg.min_dim_size_to_factor,
            )
            self._af_layouts[name] = layout
            return optim.init_adafactor_state(
                self.dist.topology, layout, self._af_cfg, self.data_size
            )
        return init_shard_opt_state(
            self.dist.topology, self.optimizer, ps.owned_kernel_count
        )

    def _build_grad_fn(self):
        layers = self.layers
        core = build_local_grads(
            self.loss_fn, layers, self.get_layer, self.padded_counts
        )

        def local_grads(params, batch):
            # per-device: local-batch loss -> local grads (NO cross-device sync here;
            # the MLSL requests own the reduction)
            x, y = batch
            x = x.reshape(x.shape[NUM_GRID_AXES:])  # strip grid block dims
            y = y.reshape(y.shape[NUM_GRID_AXES:])
            loss, flat = core(params, x, y)
            return (
                loss[None, None, None, None, None],
                {n: g[None, None, None, None] for n, g in flat.items()},
            )

        sm = smap(
            local_grads,
            self.mesh,
            in_specs=(P(), (_BUF_SPEC, _BUF_SPEC)),
            out_specs=(_BUF_SPEC, {n: _BUF_SPEC for n in layers}),
            check=False,
        )
        return jax.jit(sm)

    def _build_update_fn(self):
        if self.optimizer is not None:
            return self._build_opt_update_fn()
        layers, get_layer = self.layers, self.get_layer
        data_size, lr = self.data_size, self.lr
        counts = self.layer_counts
        clip = self.clip_global_norm

        def update(params, reduced: Dict[str, jax.Array]):
            def body(params, *flat_grads):
                cscale = (
                    _clip_scale(
                        sum(
                            jnp.sum((g.reshape(-1)[: counts[n]] / data_size) ** 2)
                            for n, g in zip(layers, flat_grads)
                        ),
                        clip,
                    )
                    if clip is not None
                    else 1.0
                )
                new = params
                for name, g in zip(layers, flat_grads):
                    g = g.reshape(-1)[: counts[name]] / data_size * cscale
                    sub = get_layer(new, name)
                    new_sub = jax.tree.map(
                        lambda p, gg: p - lr * gg,
                        sub,
                        _unflatten_like(sub, g),
                    )
                    new = _set_layer(new, name, new_sub)
                return new

            sm = smap(
                body,
                self.mesh,
                in_specs=(P(),) + tuple(_BUF_SPEC for _ in layers),
                out_specs=P(),
                check=False,
            )
            return sm(params, *[reduced[n] for n in layers])

        # donated params: the update is in-place in HBM (same contract as the
        # fused path — see the class docstring)
        return jax.jit(
            update, donate_argnums=(0,) if self.donate_params else ()
        )

    def _build_opt_update_fn(self):
        """optax path: reduced per-layer gradient buffers -> (params, opt_state)."""
        import optax

        layers, get_layer = self.layers, self.get_layer
        data_size, counts = self.data_size, self.layer_counts
        optimizer = self._optax_opt
        clip = self.clip_global_norm

        def update(params, opt_state, reduced: Dict[str, jax.Array]):
            def body(params, opt_state, *flat_grads):
                grads = jax.tree.map(jnp.zeros_like, params)
                for name, g in zip(layers, flat_grads):
                    g = g.reshape(-1)[: counts[name]] / data_size
                    sub = get_layer(params, name)
                    grads = _set_layer(grads, name, _unflatten_like(sub, g))
                if clip is not None:
                    cscale = _clip_scale(
                        sum(jnp.sum(g ** 2) for g in jax.tree.leaves(grads)), clip
                    )
                    grads = jax.tree.map(lambda g: g * cscale, grads)
                updates, new_state = optimizer.update(grads, opt_state, params)
                # Apply only to registered layers: leaves outside `layers`
                # (frozen params) must stay untouched even under
                # params-consuming transforms like weight decay, matching the
                # SGD path's semantics.
                new_params = params
                for name in layers:
                    new_params = _set_layer(
                        new_params, name,
                        optax.apply_updates(
                            get_layer(params, name), get_layer(updates, name)
                        ),
                    )
                return new_params, new_state

            sm = smap(
                body,
                self.mesh,
                in_specs=(P(), P()) + tuple(_BUF_SPEC for _ in layers),
                out_specs=(P(), P()),
                check=False,
            )
            return sm(params, opt_state, *[reduced[n] for n in layers])

        return jax.jit(
            update, donate_argnums=(0, 1) if self.donate_params else ()
        )

    def _build_du_inc_fn(self):
        """distributed-update: owned-shard gradient -> owned-shard increment."""
        from mlsl_tpu import optim

        with_scale = self.clip_global_norm is not None
        if self.optimizer is None:
            return build_owned_increment_fn(
                self.mesh, self.lr, self.data_size, with_scale=with_scale
            )
        if self._af_cfg is not None:
            self._du_inc_fns = {
                name: optim.build_adafactor_inc_fn(
                    self.mesh,
                    self.dist.topology,
                    self._af_cfg,
                    self._af_layouts[name],
                    self.data_size,
                    with_scale=with_scale,
                )
                for name in self._af_layouts
            }
            return None
        return build_owned_opt_increment_fn(
            self.mesh, self.optimizer, self.data_size, with_scale=with_scale
        )

    def _build_du_apply_fn(self):
        layers, get_layer = self.layers, self.get_layer

        def apply(params, incs: Dict[str, jax.Array]):
            def body(params, *flat_incs):
                new = params
                for name, inc in zip(layers, flat_incs):
                    inc = inc.reshape(-1)[: self.layer_counts[name]]
                    sub = get_layer(new, name)
                    new_sub = jax.tree.map(
                        lambda p, dd: p + dd, sub, _unflatten_like(sub, inc)
                    )
                    new = _set_layer(new, name, new_sub)
                return new

            sm = smap(
                body,
                self.mesh,
                in_specs=(P(),) + tuple(_BUF_SPEC for _ in layers),
                out_specs=P(),
                check=False,
            )
            return sm(params, *[incs[n] for n in layers])

        return jax.jit(
            apply, donate_argnums=(0,) if self.donate_params else ()
        )

    def _build_layer_update_fn(self, name: str):
        data_size, lr = self.data_size, self.lr
        count = self.layer_counts[name]

        def update_layer(sub, g):
            def body(sub, g):
                g = g.reshape(-1)[:count] / data_size
                return jax.tree.map(
                    lambda p, gg: p - lr * gg, sub, _unflatten_like(sub, g)
                )

            sm = smap(
                body, self.mesh, in_specs=(P(), _BUF_SPEC), out_specs=P(),
                check=False,
            )
            return sm(sub, g)

        return jax.jit(update_layer)

    def _build_fused_fn(self):
        loss_fn, lr = self.loss_fn, self.lr
        optimizer = self._optax_opt
        clip = self.clip_global_norm

        def _clipped(grads):
            if clip is None:
                return grads
            cscale = _clip_scale(
                sum(jnp.sum(g.astype(jnp.float32) ** 2)
                    for g in jax.tree.leaves(grads)), clip
            )
            return jax.tree.map(lambda g: g * cscale, grads)

        # Donating the params lets XLA update weights in place (the trainer owns
        # self.params and always replaces it) — halves parameter HBM traffic in the
        # optimizer tail, something a caller-owned raw-JAX step cannot safely do.
        if optimizer is None:
            @functools.partial(jax.jit, donate_argnums=(0,) if self.donate_params else ())
            def fused(params, batch):
                x, y = batch
                x = x.reshape(x.shape[NUM_GRID_AXES:])
                y = y.reshape(y.shape[NUM_GRID_AXES:])
                loss, grads = jax.value_and_grad(loss_fn)(params, (x, y))
                grads = _clipped(grads)
                return loss, jax.tree.map(lambda p, g: p - lr * g, params, grads)

            return fused

        import optax

        @functools.partial(jax.jit, donate_argnums=(0, 1) if self.donate_params else ())
        def fused_opt(params, opt_state, batch):
            x, y = batch
            x = x.reshape(x.shape[NUM_GRID_AXES:])
            y = y.reshape(y.shape[NUM_GRID_AXES:])
            loss, grads = jax.value_and_grad(loss_fn)(params, (x, y))
            updates, new_state = optimizer.update(_clipped(grads), opt_state, params)
            return loss, optax.apply_updates(params, updates), new_state

        return fused_opt

    # -- AOT warm-up (MLSL_PRECOMPILE) --------------------------------------

    def precompile(self, batch) -> None:
        """Warm every compiled program one step() dispatches, so step 0 of the
        timed loop contains no compilation: the session's collective plans
        (Session.precompile_collectives — also run automatically at Commit
        under MLSL_PRECOMPILE=1, and idempotent here) plus this trainer's
        model-side programs. Donating programs are exercised on copies — a
        donated warm argument must never consume the live params/opt state.
        ``batch`` is a shard_batch() result; its values are read, not trained
        on (params are unchanged afterwards)."""
        self.session.precompile_collectives()
        copy = lambda tree: jax.tree.map(jnp.copy, tree)
        if self._fused_fn is not None:
            # the fused step never dispatches _grad_fn — warming it here would
            # ADD a full-model compile to startup, the exact stall this exists
            # to remove
            if self.optimizer is None:
                out = self._fused_fn(copy(self.params), batch)
            else:
                out = self._fused_fn(copy(self.params), copy(self._opt_state), batch)
            jax.block_until_ready(out)
            return
        if self._overlap is not None:
            # The engine warms the program step() dispatches on donation-safe
            # copies: the fused single program, or (gate armed) _grad_fn +
            # the split sync program. A gate-unarmed step_accum still pays
            # its first-use sync-program compile — the same contract as the
            # host path, whose accum add/scale jits are likewise not warmed.
            self._overlap.precompile(batch)
            return
        loss, grads = self._grad_fn(self.params, batch)
        if self.overlap_updates:
            for name in self.layers:  # per-layer update fns never donate
                self._layer_update_fns[name](
                    self.get_layer(self.params, name), grads[name]
                )
        elif not (self.distributed_update and self._needs_comm):
            if self.optimizer is None:
                self._update_fn(copy(self.params), grads)
            else:
                self._update_fn(copy(self.params), copy(self._opt_state), grads)
        else:
            topo = self.dist.topology
            grid = topo.grid_shape
            owned = {
                name: topo.shard_buffer(np.zeros(
                    (*grid,
                     self.ops[name].get_parameter_set(0).owned_kernel_count
                     * self.ops[name].get_parameter_set(0).kernel_size),
                    np.float32,
                ))
                for name in self.layers
            }
            scale_args = ()
            if self.clip_global_norm is not None:
                if self._du_norm_fn is None:
                    self._du_norm_fn = build_owned_norm_fn(
                        self.mesh, self.data_size
                    )
                scale_args = (_clip_scale(
                    self._du_norm_fn(owned) ** 2, self.clip_global_norm
                ),)
            incs = {}
            for name in self.layers:
                if self.optimizer is None:
                    self._du_inc_fn(owned[name], *scale_args)
                elif self._du_inc_fns is not None:
                    self._du_inc_fns[name](
                        owned[name], copy(self._du_opt_state[name]),
                        self.get_layer(self.params, name), *scale_args
                    )
                else:
                    self._du_inc_fn(
                        owned[name], copy(self._du_opt_state[name]), *scale_args
                    )
                incs[name] = topo.shard_buffer(np.zeros(
                    (*grid, self.padded_counts[name]), np.float32
                ))
            self._du_apply_fn(copy(self.params), incs)
        jax.block_until_ready(loss)

    # -- data placement ----------------------------------------------------

    def shard_batch(self, x: np.ndarray, y: np.ndarray):
        """Global batch (B, ...) -> distributed buffers (R, D, S, M, localB, ...)."""
        topo = self.dist.topology
        r, d, s, m = topo.grid_shape
        local_b = x.shape[0] // (r * d)
        xs = np.broadcast_to(
            x.reshape(r, d, 1, 1, local_b, *x.shape[1:]),
            (r, d, s, m, local_b, *x.shape[1:]),
        )
        ys = np.broadcast_to(
            y.reshape(r, d, 1, 1, local_b, *y.shape[1:]),
            (r, d, s, m, local_b, *y.shape[1:]),
        )
        return topo.shard_buffer(xs), topo.shard_buffer(ys)

    def shard_batch_local(self, x: np.ndarray, y: np.ndarray):
        """Multi-host batch placement: x/y are THIS process's contiguous rows of
        the global batch (global_batch / process_count rows each); no host
        materializes the full batch. Requires the data-rank count (r*d) to be
        divisible by the process count with replica-major contiguity (r == 1 or
        process_count dividing r)."""
        topo = self.dist.topology
        r, d, s, m = topo.grid_shape
        nproc = jax.process_count()
        mlsl_assert(
            (r * d) % nproc == 0 and (r == 1 or r % nproc == 0),
            "data ranks (r=%d x d=%d) must split contiguously over %d processes",
            r, d, nproc,
        )
        rd_local = (r * d) // nproc
        local_b = x.shape[0] // rd_local
        r_loc = max(1, r // nproc)
        d_loc = rd_local // r_loc
        xs = np.broadcast_to(
            x.reshape(r_loc, d_loc, 1, 1, local_b, *x.shape[1:]),
            (r_loc, d_loc, s, m, local_b, *x.shape[1:]),
        )
        ys = np.broadcast_to(
            y.reshape(r_loc, d_loc, 1, 1, local_b, *y.shape[1:]),
            (r_loc, d_loc, s, m, local_b, *y.shape[1:]),
        )
        gx = (r, d, s, m, local_b, *x.shape[1:])
        gy = (r, d, s, m, local_b, *y.shape[1:])
        return topo.shard_buffer_local(xs, gx), topo.shard_buffer_local(ys, gy)

    def feed(self, source, *, depth: Optional[int] = None, **kw):
        """Build the wire-compressed prefetching device feed for this
        trainer's topology: an :class:`mlsl_tpu.data.AsyncLoader` over a
        :class:`mlsl_tpu.data.DeviceFeed` whose decoded batches are the SAME
        distributed buffers :meth:`shard_batch` produces — ``step`` consumes
        them unchanged, but batches cross the h2d link in the configured
        wire dtype and epoch replays can serve straight from the HBM cache.

        Defaults come from the environment's Config (``MLSL_FEED_*``,
        docs/TUNING.md §12); any DeviceFeed kwarg (wire, cache_mb, epochs,
        shuffle_seed, normalize, augment, ...) can be overridden here.
        Remember to ``close()`` the returned loader."""
        from mlsl_tpu.data import AsyncLoader, DeviceFeed

        cfg = self.env.config
        kw.setdefault("wire", cfg.feed_wire_dtype if cfg else None)
        kw.setdefault("cache_mb", cfg.feed_cache_mb if cfg else None)
        kw.setdefault("retries", cfg.feed_retries if cfg else None)
        kw.setdefault("quant_block", cfg.quant_block_elems if cfg else None)
        if depth is None:
            depth = cfg.feed_depth if cfg else None
        dev_feed = DeviceFeed(source, self.dist.topology, **kw)
        return AsyncLoader(dev_feed, depth=depth)

    # -- silent-corruption chaos sites + the sentinel quality gate ---------

    def _chaos_state_sites(self) -> None:
        """``train.params`` / ``train.opt_state`` silent-corruption sites:
        a fired ``silent`` plan flips/perturbs ONE replica's copy of live
        state without raising (sentinel.corrupt_silent) — the SDC class only
        the consistency audit can catch. Called at step entry."""
        from mlsl_tpu import sentinel as sentinel_mod

        p = chaos.inject("train.params", step=self._step_no)
        if p is not None and p.kind == "silent":
            self.params = sentinel_mod.corrupt_silent(self.params, p)
        if self._opt_state is not None or self._du_opt_state:
            # only consult the site when there IS state to corrupt: firing
            # (and burning a plan's xN budget) against a stateless SGD
            # trainer would make a soak's "every fire was detected"
            # accounting vacuous
            p = chaos.inject("train.opt_state", step=self._step_no)
            if p is not None and p.kind == "silent":
                if self._opt_state is not None:
                    self._opt_state = sentinel_mod.corrupt_silent(
                        self._opt_state, p
                    )
                else:
                    name = sorted(self._du_opt_state)[
                        chaos._rng.randrange(len(self._du_opt_state))
                    ]
                    self._du_opt_state[name] = sentinel_mod.corrupt_silent(
                        self._du_opt_state[name], p
                    )

    def _screen(self, loss, grads):
        """``train.grads`` silent site + the step quality gate, between the
        gradient program and any gradient comm. -> (grads, proceed): proceed
        False means the gate chose ``skip_step`` — the caller returns the
        loss without syncing or updating, so no comm starts, error-feedback
        residuals never advance, and the step behaves exactly as if it had
        not run (lockstep-twin parity, tests/test_sentinel.py)."""
        if chaos._plans:
            p = chaos.inject("train.grads", step=self._step_no)
            if p is not None and p.kind == "silent":
                from mlsl_tpu import sentinel as sentinel_mod

                grads = sentinel_mod.corrupt_silent(grads, p)
        if self.sentinel is not None and self.sentinel.gate_armed:
            if not self.sentinel.gate(loss, grads, self.params,
                                      self._step_no):
                return grads, False
        m = obs_metrics._registry
        if m is not None and self._step_no % m.every == 0:
            # telemetry cadence: the (local) gradient norm, recorded here
            # because only the host grad paths expose a gradient boundary
            self._record_grad_norm(m, grads)
        return grads, True

    # -- the training step (reference loop mlsl_test.cpp:660-698) ----------

    def step(self, batch) -> jax.Array:
        """One training step. With the telemetry plane disarmed this is a
        zero-overhead passthrough (two module/attr None-checks); armed, the
        step wall time feeds the ``mlsl_step_ms`` histogram and the
        straggler sentinel, and every ``MLSL_METRICS_EVERY`` steps the
        cadence tick samples loss/grad-norm/input-stall plus every counter
        family (``_sample_telemetry``)."""
        m = obs_metrics._registry
        if m is None and self.straggler is None:
            return self._step_impl(batch)
        t0 = time.perf_counter()
        loss = self._step_impl(batch)
        self._post_step_telemetry(m, loss, t0)
        return loss

    def step_accum(self, batches) -> jax.Array:
        m = obs_metrics._registry
        if m is None and self.straggler is None:
            return self._step_accum_impl(batches)
        t0 = time.perf_counter()
        loss = self._step_accum_impl(batches)
        self._post_step_telemetry(m, loss, t0)
        return loss

    def _post_step_telemetry(self, m, loss, t0: float) -> None:
        """Armed-path epilogue: step wall time into the histogram + the
        straggler feed, cadence tick every ``m.every`` steps."""
        step_ms = (time.perf_counter() - t0) * 1e3
        if m is not None:
            m.observe("mlsl_step_ms", step_ms)
            if self._step_no % m.every == 0:
                self._sample_telemetry(m, loss)
        strag = self.straggler
        if strag is not None:
            strag.observe(self._replica_id, step_ms)
            strag.maybe_audit(self._step_no)

    def _sample_telemetry(self, m, loss) -> None:
        """One cadence tick (``MLSL_METRICS_EVERY``): the scalars that cost
        a device sync or IO live here, NOT per step — loss readback (one
        host sync), the input-stall delta since the last tick, a gauge
        snapshot of every core/stats counter family, one timestamped sample
        per series, and the JSONL append."""
        try:
            # per-device loss buffers (the step's native shape) read back as
            # the device mean — the same scalar the examples log
            m.set("mlsl_loss", float(np.asarray(loss).mean()))
        except (TypeError, ValueError):  # non-numeric custom loss: skip
            pass
        from mlsl_tpu.core import stats as stats_mod

        stall = float(stats_mod.FEED_COUNTERS["stall_ms"])
        m.set("mlsl_input_stall_ms", max(0.0, stall - self._stall_ms_seen))
        self._stall_ms_seen = stall
        m.sample_families()
        m.write_jsonl(records=m.sample())

    def _record_grad_norm(self, m, grads) -> None:
        """Telemetry grad-norm at the cadence tick (host grad paths only —
        the fused/unsplit-overlap programs expose no gradient boundary).
        One small jitted program, built lazily on first use."""
        if self._gnorm_fn is None:
            def sq(tree):
                leaves = jax.tree.leaves(tree)
                return sum(jnp.sum(jnp.square(g)) for g in leaves)

            self._gnorm_fn = jax.jit(sq)
        try:
            m.set("mlsl_grad_norm",
                  float(jnp.sqrt(self._gnorm_fn(grads))))
        except (TypeError, ValueError):  # pragma: no cover - odd dtypes
            pass

    def _step_accum_impl(self, batches) -> jax.Array:
        """Gradient accumulation (the Caffe iter_size pattern the reference's
        per-layer sync was built around): k local fwd/bwd passes, ONE gradient
        sync + update. Each entry of ``batches`` is a shard_batch() result with
        the same local minibatch size; the effective loss is the mean over all
        k micro-batches. Returns the mean loss."""
        mlsl_assert(len(batches) >= 1, "step_accum needs at least one batch")
        self._step_no += 1
        if chaos._plans:
            self._chaos_state_sites()
        if self._accum_fns is None:
            def add(a, b):
                return jax.tree.map(jnp.add, a, b)

            def scale(tree, k):
                return jax.tree.map(lambda g: g / k, tree)

            self._accum_fns = (jax.jit(add), jax.jit(scale, static_argnums=1))
        add_fn, scale_fn = self._accum_fns
        tr = obs_trace._tracer
        t0 = tr.now() if tr is not None else 0
        total, loss_sum = None, None
        for b in batches:
            loss, grads = self._grad_fn(self.params, b)
            total = grads if total is None else add_fn(total, grads)
            loss_sum = loss if loss_sum is None else loss_sum + loss
        k = len(batches)
        if tr is not None:
            tr.complete("step.grad", "step", t0, step=self._step_no,
                        micro_batches=k)
        loss = loss_sum / k
        grads, proceed = self._screen(loss, scale_fn(total, k))
        if not proceed:
            return loss
        if self._overlap is not None:
            # accumulated grads ride the engine's split comm/update program
            # (one compiled dispatch for the whole sync, residuals threaded)
            self._overlap.step(None, grads=grads, loss=loss)
            return loss
        return self._sync_and_update(grads, loss)

    def _step_impl(self, batch) -> jax.Array:
        self._step_no += 1
        if chaos._plans:
            self._chaos_state_sites()
        tr = obs_trace._tracer
        t0 = tr.now() if tr is not None else 0
        if self._fused_fn is not None:
            if self.optimizer is None:
                loss, self.params = self._fused_fn(self.params, batch)
            else:
                loss, self.params, self._opt_state = self._fused_fn(
                    self.params, self._opt_state, batch
                )
            if tr is not None:
                tr.complete("step.fused", "step", t0, step=self._step_no)
            return loss
        if self._overlap is not None:
            return self._overlap_step(batch)
        loss, grads = self._grad_fn(self.params, batch)
        if tr is not None:
            # host-side dispatch of the local-gradient program (async: device
            # compute overlaps the comm Starts that follow)
            tr.complete("step.grad", "step", t0, step=self._step_no)
        grads, proceed = self._screen(loss, grads)
        if not proceed:
            return loss
        return self._sync_and_update(grads, loss)

    def _overlap_step(self, batch) -> jax.Array:
        """One compiled-overlap step (comm/overlap.py). With the sentinel
        quality gate armed the two-program split runs — the gate screens at
        the host gradient boundary and a ``skip_step`` verdict never
        dispatches the comm program, so EF residuals and data order stay
        lockstep with the host path; unarmed, the fused single-dispatch
        program carries the whole step (like the no-comm fused shortcut, it
        exposes no gradient boundary)."""
        if self.sentinel is not None and self.sentinel.gate_armed:
            tr = obs_trace._tracer
            t0 = tr.now() if tr is not None else 0
            loss, grads = self._grad_fn(self.params, batch)
            if tr is not None:
                tr.complete("step.grad", "step", t0, step=self._step_no)
            grads, proceed = self._screen(loss, grads)
            if not proceed:
                return loss
            self._overlap.step(batch, grads=grads, loss=loss)
            return loss
        return self._overlap.step(batch)

    def _sync_and_update(self, grads, loss) -> jax.Array:
        # Start gradient comms newest-gradient-first (reverse layer order), the
        # stream shape eplib's priority allreduce was built for.
        tr = obs_trace._tracer
        t0 = tr.now() if tr is not None else 0
        for name in reversed(self.layers):
            self.ops[name].get_parameter_set(0).start_gradient_comm(grads[name])
        if tr is not None:
            tr.complete("step.sync_start", "step", t0, step=self._step_no,
                        layers=len(self.layers))
            t0 = tr.now()

        if self.overlap_updates:
            # poll Test and update each layer the moment its collective lands
            new_params = self.params

            def apply(name, g):
                nonlocal new_params
                sub = self._layer_update_fns[name](
                    self.get_layer(new_params, name), g
                )
                new_params = _set_layer(new_params, name, sub)

            pending = list(self.layers)
            while pending:
                still = []
                for name in pending:
                    ps = self.ops[name].get_parameter_set(0)
                    done, out = ps.test_gradient_comm()
                    if done:
                        apply(name, out if out is not None else grads[name])
                    else:
                        still.append(name)
                if still and len(still) == len(pending):
                    # nothing landed this pass: block on one to avoid spinning
                    name = still.pop()
                    ps = self.ops[name].get_parameter_set(0)
                    out = ps.wait_gradient_comm()
                    apply(name, out if out is not None else grads[name])
                pending = still
            self.params = new_params
        elif not (self.distributed_update and self._needs_comm):
            reduced = {}
            for name in self.layers:
                ps = self.ops[name].get_parameter_set(0)
                out = ps.wait_gradient_comm()
                reduced[name] = out if out is not None else grads[name]
            if self.optimizer is None:
                self.params = self._update_fn(self.params, reduced)
            else:
                self.params, self._opt_state = self._update_fn(
                    self.params, self._opt_state, reduced
                )
        else:
            incs = {}
            owned_all, scale_args = {}, ()
            if self.clip_global_norm is not None:
                # Global-norm clipping needs every owned shard before any
                # increment: wait all, psum the shard norms, then scale.
                for name in self.layers:
                    ps = self.ops[name].get_parameter_set(0)
                    owned_all[name] = ps.wait_gradient_comm()
                    mlsl_assert(
                        owned_all[name] is not None,
                        "distributed update requires dataParts>1",
                    )
                if self._du_norm_fn is None:
                    self._du_norm_fn = build_owned_norm_fn(
                        self.mesh, self.data_size
                    )
                cscale = _clip_scale(
                    self._du_norm_fn(owned_all) ** 2, self.clip_global_norm
                )
                scale_args = (cscale,)
            for name in self.layers:
                ps = self.ops[name].get_parameter_set(0)
                if name in owned_all:
                    owned = owned_all[name]
                else:
                    owned = ps.wait_gradient_comm()
                    mlsl_assert(
                        owned is not None, "distributed update requires dataParts>1"
                    )
                if self.optimizer is None:
                    inc_local = self._du_inc_fn(owned, *scale_args)
                elif self._du_inc_fns is not None:
                    # sharded adafactor: factored stats need the replicated
                    # layer subtree (per-leaf shapes / parameter scale)
                    inc_local, self._du_opt_state[name] = self._du_inc_fns[name](
                        owned, self._du_opt_state[name],
                        self.get_layer(self.params, name), *scale_args
                    )
                else:
                    inc_local, self._du_opt_state[name] = self._du_inc_fn(
                        owned, self._du_opt_state[name], *scale_args
                    )
                ps.start_increment_comm(inc_local)
            for name in self.layers:
                ps = self.ops[name].get_parameter_set(0)
                incs[name] = ps.wait_increment_comm()
            self.params = self._du_apply_fn(self.params, incs)
        if tr is not None:
            # wait-all + parameter update phase (whatever path ran above)
            tr.complete("step.update", "step", t0, step=self._step_no)
        return loss


def _set_layer(params, name: str, subtree):
    """Functional update of a layer subtree addressed by resnet-style names."""
    if isinstance(params, dict) and name in params:
        new = dict(params)
        new[name] = subtree
        return new
    stage, block = name.split(".")
    new = dict(params)
    lst = list(new[stage])
    lst[int(block)] = subtree
    new[stage] = lst
    return new
