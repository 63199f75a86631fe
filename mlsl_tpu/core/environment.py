"""The Environment singleton — framework bootstrap and global services.

Mirrors the reference Environment (include/mlsl.hpp:799-915, src/mlsl.cpp:684-812):
Init/Finalize, Distribution and Session factories, Alloc/Free, Wait/Test on generic
requests, quantization-params registration, and color-based global-group configuration.
The TPU-native difference: Init builds no MPI world — it captures the JAX device set;
"process count" is the device count and "process idx" is only meaningful per-device
(SPMD), so the single-controller API exposes rank math as pure functions instead.
"""

from __future__ import annotations

import os
import threading
from typing import Optional, Sequence

import numpy as np
import jax

from mlsl_tpu import sysinfo
from mlsl_tpu.config import Config
from mlsl_tpu.comm.request import CommRequest, Dispatcher, RequestStorage
from mlsl_tpu.log import mlsl_assert, set_log_level
from mlsl_tpu.types import DataType, QuantParams, jnp_dtype


class Environment:
    """Process-wide singleton (reference include/mlsl.hpp:799)."""

    _instance: Optional["Environment"] = None
    _lock = threading.Lock()
    _jax_distributed_up = False  # process-wide: jax.distributed inits at most once

    def __init__(self):
        self._initialized = False
        self._init_pid: Optional[int] = None
        self.config: Optional[Config] = None
        self.dispatcher: Optional[Dispatcher] = None
        self.request_storage = RequestStorage()
        self.devices: Sequence[jax.Device] = ()
        self.quant_params: Optional[QuantParams] = None
        self.compile_cache_dir = ""  # set by init (sysinfo.resolve_compile_cache)
        self._distributions: list = []
        self._sessions: list = []
        self._global_colors: Optional[tuple] = None

    # -- singleton --------------------------------------------------------

    @classmethod
    def get_env(cls) -> "Environment":
        with cls._lock:
            if cls._instance is None:
                cls._instance = Environment()
            return cls._instance

    @classmethod
    def is_initialized(cls) -> bool:
        return cls._instance is not None and cls._instance._initialized

    # -- lifecycle (reference src/mlsl.cpp:684-746) -----------------------

    def init(
        self,
        devices: Optional[Sequence[jax.Device]] = None,
        coordinator_address: Optional[str] = None,
        num_processes: Optional[int] = None,
        process_id: Optional[int] = None,
    ) -> "Environment":
        """Bootstrap. For multi-host slices/pods pass the jax.distributed
        coordination parameters (the DCN analog of the reference's multi-node MPI
        launch); single-host/single-controller needs none."""
        if self._initialized:
            return self
        if coordinator_address is not None and not Environment._jax_distributed_up:
            # jax.distributed.initialize may only run once per process; init/finalize
            # cycles of the Environment must not re-run it.
            self._distributed_init_with_retry(
                coordinator_address, num_processes, process_id
            )
            Environment._jax_distributed_up = True
        # a TPU runtime that failed to start must not become a CPU run
        sysinfo.require_chosen_backend()
        self.config = Config.from_env()
        set_log_level(self.config.log_level)
        sysinfo.auto_config(self.config)
        # fail-fast validation (MLSLError): contradictory settings — an
        # MLSL_ALGO name outside the registry, nonsensical knob ranges — are
        # init-time errors, not latent dispatch failures
        self.config.validate()
        # (re)apply the recovery-ladder breaker knobs: breakers are
        # process-wide and keep their STATE across an Environment rebuild
        # (subsystem health must survive recovery cycles), but adopt the
        # freshly validated thresholds
        from mlsl_tpu import supervisor

        supervisor.configure(self.config)
        if devices is not None:
            self.devices = tuple(devices)
        else:
            # elastic-mesh registry (mlsl_tpu.elastic): after a shrink, a
            # recovery/factory rebuild with no explicit device list must
            # adopt the survivor world, not silently re-inflate to the full
            # one — the registry outlives Environment teardown by design
            from mlsl_tpu import elastic as elastic_mod

            self.devices = (
                elastic_mod.active_devices() or tuple(jax.devices())
            )
        # the persistent XLA cache must be armed BEFORE the tuner sweep: the
        # sweep compiles every eligible algorithm x size x shape program, and
        # on real chips those compiles are the tens-of-seconds cost the cache
        # exists to amortize across restarts
        self.compile_cache_dir = sysinfo.resolve_compile_cache()
        # autotuner hook: MLSL_TUNE=1 sweeps and persists a profile on the
        # live mesh; MLSL_TUNE_PROFILE loads one (stale fingerprints rejected
        # with a warning, missing/corrupt files raise). Sets
        # config.tuned_profile, which comm/algos.select consults, and applies
        # tuned chunk/bucket/priority knobs (explicit env always wins).
        from mlsl_tpu import tuner

        tuner.init_profile(self.config, self.devices)
        # telemetry plane (obs/metrics.py + obs/serve.py): arm the registry
        # when MLSL_METRICS or a scrape port asks for it, and start the
        # /metrics + /healthz + /statusz daemon thread on MLSL_METRICS_PORT.
        # Both are process-wide and idempotent (the tracer contract): a
        # recovery teardown/rebuild cycle keeps the series history and the
        # scrape surface alive mid-incident.
        if self.config.metrics or self.config.metrics_port:
            from mlsl_tpu.obs import metrics as obs_metrics

            obs_metrics.enable(every=self.config.metrics_every,
                               retention=self.config.metrics_retention)
        if self.config.metrics_port:
            from mlsl_tpu.obs import serve as obs_serve

            obs_serve.start_server(self.config.metrics_port)
        # pod control plane (mlsl_tpu.control): when the config names a
        # control world, join it — membership/heartbeat over a stdlib TCP
        # channel separate from the JAX collective fabric. Process-wide and
        # idempotent like the telemetry plane: pod membership must survive
        # an Environment rebuild mid-recovery.
        if self.config.control_addrs or (
            self.config.control_port and self.config.control_world
        ):
            from mlsl_tpu import control as control_mod

            control_mod.ensure_started(self.config)
        self.dispatcher = Dispatcher(self.config)
        self._initialized = True
        self._init_pid = os.getpid()
        if self.quant_params is not None:
            try:
                # a pre-init SetQuantizationParams is applied now that config
                # exists; if the deferred codec can no longer load, unwind so a
                # retried init() re-attempts it instead of silently proceeding
                # with the built-in codec
                self.set_quantization_params(self.quant_params)
            except Exception:
                self._initialized = False
                self._init_pid = None
                self.dispatcher.shutdown()
                self.dispatcher = None
                raise
        self._dump_config()
        return self

    @staticmethod
    def _distributed_init_with_retry(
        coordinator_address: str,
        num_processes: Optional[int],
        process_id: Optional[int],
    ) -> None:
        """jax.distributed.initialize with MLSL_DIST_INIT_RETRIES backoff.

        The known gloo TCP preamble race (KNOWN_FAILURES.md) and plain
        coordinator-not-up-yet races surface here as RuntimeError/OSError
        during the coordination-service handshake. Retrying INSIDE init —
        with a best-effort shutdown between attempts so the client can
        rebind — is the library-side fix that let tests/test_multiprocess.py
        drop its test-side retry-on-SIGABRT wrapper. Only the handshake is
        retryable; a failure after the world is up propagates (that is the
        control plane's job, not init's)."""
        import time as _time

        from mlsl_tpu.config import _env_float, _env_int
        from mlsl_tpu.log import log_warning

        retries = max(0, _env_int("MLSL_DIST_INIT_RETRIES", 3))
        backoff_s = max(0.0, _env_float("MLSL_DIST_INIT_BACKOFF_S", 0.5))
        for attempt in range(retries + 1):
            if attempt:
                _time.sleep(backoff_s * (2 ** (attempt - 1)))
            try:
                jax.distributed.initialize(
                    coordinator_address=coordinator_address,
                    num_processes=num_processes,
                    process_id=process_id,
                )
                return
            except (RuntimeError, OSError) as e:
                if attempt >= retries:
                    raise
                log_warning(
                    "jax.distributed.initialize failed (attempt %d/%d, "
                    "retrying in %.2gs): %s: %s",
                    attempt + 1, retries + 1,
                    backoff_s * (2 ** attempt), type(e).__name__, e,
                )
                try:
                    jax.distributed.shutdown()
                except Exception:  # mlsl-lint: disable=A205 -- half-
                    pass  # initialized client: nothing to unwind

    def _dump_config(self) -> None:
        """One-time config/world dump at init (the reference's rank-0 env-var dump,
        src/comm_ep.cpp:1701-1739), at INFO level."""
        from mlsl_tpu.log import log_info

        if jax.process_index() != 0:  # rank-0 only, like the reference
            return
        si = sysinfo.probe()
        log_info(
            "mlsl_tpu init: platform=%s kind=%s devices=%d hosts=%d",
            si.platform, si.device_kind, len(self.devices), si.num_hosts,
        )
        for field, value in sorted(vars(self.config).items()):
            log_info("  config %s = %r", field, value)

    def finalize(self) -> None:
        # Fork-safety: a forked child must not tear down the parent's state
        # (reference initPid guard, src/mlsl.cpp:720-724).
        if not self._initialized or os.getpid() != self._init_pid:
            return
        if self.dispatcher is not None:
            self.dispatcher.shutdown()
        for s in self._sessions:
            s._invalidate()
        self._sessions.clear()
        self._distributions.clear()
        self._initialized = False
        Environment._instance = None

    # -- world introspection ---------------------------------------------

    def get_process_count(self) -> int:
        mlsl_assert(self._initialized, "Environment not initialized")
        return len(self.devices)

    def get_process_idx(self) -> int:
        """Single-controller SPMD: the controller is logical rank 0. Per-device rank
        math lives on Distribution (process_idx_of)."""
        return 0

    # -- configuration (reference src/mlsl.cpp:620-682) -------------------

    def configure(self, conf_str: str) -> None:
        """Color-based restriction of the world (reference Configure("color=N"),
        src/mlsl.cpp:620-647: MPI ranks with the same color form the new global group).

        Single-controller translation: 'color=N' (one value) keeps the full device set
        (every device shares the controller's color — identical to the reference when
        all ranks pass the same color). 'color=c0,c1,...' (one value per device)
        restricts subsequently created Distributions to the devices whose color equals
        the first listed color.
        """
        conf_str = conf_str.strip()
        mlsl_assert(
            conf_str.startswith("color="),
            "unsupported configuration string: %s",
            conf_str,
        )
        values = [int(v) for v in conf_str.split("=", 1)[1].split(",")]
        if len(values) == 1:
            self._global_colors = tuple(values * len(self.devices))
            return
        mlsl_assert(
            len(values) == len(self.devices),
            "color list length %d != device count %d",
            len(values),
            len(self.devices),
        )
        self._global_colors = tuple(values)
        self.devices = tuple(
            d for d, c in zip(self.devices, values) if c == values[0]
        )

    # -- factories --------------------------------------------------------

    def create_distribution(
        self,
        data_parts: int,
        model_parts: int,
        devices: Optional[Sequence[jax.Device]] = None,
        seq_parts: int = 1,
    ):
        from mlsl_tpu.core.distribution import Distribution

        mlsl_assert(self._initialized, "Environment not initialized")
        d = Distribution(
            self,
            data_parts,
            model_parts,
            devices=devices or self.devices,
            seq_parts=seq_parts,
        )
        self._distributions.append(d)
        return d

    def create_distribution_with_colors(self, data_color_per_rank, model_color_per_rank):
        from mlsl_tpu.core.distribution import Distribution

        mlsl_assert(self._initialized, "Environment not initialized")
        d = Distribution(
            self,
            None,
            None,
            devices=self.devices,
            data_colors=tuple(data_color_per_rank),
            model_colors=tuple(model_color_per_rank),
        )
        self._distributions.append(d)
        return d

    def delete_distribution(self, dist) -> None:
        if dist in self._distributions:
            self._distributions.remove(dist)

    def create_session(self, phase_type=None):
        from mlsl_tpu.core.session import Session
        from mlsl_tpu.types import PhaseType

        mlsl_assert(self._initialized, "Environment not initialized")
        s = Session(self, phase_type if phase_type is not None else PhaseType.TRAIN)
        self._sessions.append(s)
        return s

    def delete_session(self, session) -> None:
        if session in self._sessions:
            session._invalidate()
            self._sessions.remove(session)

    # -- memory (reference Alloc/Free -> EPLIB_memalign shm; here device arrays) --

    def alloc(self, count: int, data_type: DataType = DataType.FLOAT):
        """Allocate a zeroed host-side buffer; collectives accept device arrays
        directly, so this exists for API parity and test convenience."""
        return np.zeros((count,), dtype=jnp_dtype(data_type))

    def free(self, buf) -> None:  # noqa: ARG002 - parity no-op (GC owns memory)
        return None

    # -- generic request completion (reference src/mlsl.cpp:784-796) ------

    def wait(self, req: CommRequest):
        out = req.wait()
        self.request_storage.remove(req)
        return out

    def test(self, req: CommRequest):
        done, out = req.test()
        if done:
            self.request_storage.remove(req)
        return done, out

    # -- quantization (reference src/mlsl.cpp:798) ------------------------

    def set_quantization_params(self, params: QuantParams) -> None:
        """Select the codec for CT_QUANTIZATION collectives (reference
        src/mlsl.cpp:798 -> quant_load, quant/quant.c:96-133). Callable fields
        register a jittable user codec; lib_path dlopens the reference's library
        contract (failing loudly if it cannot be honored); otherwise the
        built-in Pallas int8 kernels are used with the given block geometry.

        Before init() the request is recorded and applied at init time (the
        reference likewise defers: quant params submitted pre-Init reach the
        servers on EPLIB_init). State mutates only after a codec loads, so a
        failed lib_path leaves the previous registration fully active."""
        from mlsl_tpu.comm import codec as codec_mod
        from mlsl_tpu.log import mlsl_assert

        codec = None
        if getattr(params, "compress_fn", None) is not None:
            mlsl_assert(
                getattr(params, "decompress_fn", None) is not None,
                "compress_fn requires decompress_fn",
            )
            codec = codec_mod.CustomCodec(
                compress=params.compress_fn,
                decompress=params.decompress_fn,
                reduce=getattr(params, "reduce_sum_fn", None),
            )
        elif params.lib_path:
            # raises MLSLError on open/resolve failure — never silently ignored
            codec = codec_mod.load_library_codec(params)

        self.quant_params = params
        if self.config is not None:
            if params.elem_in_block:
                self.config.quant_block_elems = int(params.elem_in_block)
            self.config.custom_codec = codec

    def get_quantization_params(self) -> Optional[QuantParams]:
        return self.quant_params

    def get_version(self) -> str:
        from mlsl_tpu import __version__

        return __version__

    # PascalCase parity aliases (reference include/mlsl.hpp:799-915)
    GetVersion = get_version
    GetEnv = get_env
    Init = init
    Finalize = finalize
    GetProcessCount = get_process_count
    GetProcessIdx = get_process_idx
    Configure = configure
    CreateDistribution = create_distribution
    CreateDistributionWithColors = create_distribution_with_colors
    DeleteDistribution = delete_distribution
    CreateSession = create_session
    DeleteSession = delete_session
    Alloc = alloc
    Free = free
    Wait = wait
    Test = test
    SetQuantizationParams = set_quantization_params
    GetQuantizationParams = get_quantization_params
