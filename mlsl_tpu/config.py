"""Typed configuration with MLSL_* environment-variable overrides.

The reference scatters ~25 env knobs across three tiers (src/env.cpp:26-40,
src/comm_ep.cpp:43-92,1543-1699, eplib/env.c). Here a single dataclass holds the typed
config; every field can be overridden by the same ``MLSL_*`` names the reference honors
(where a knob still makes sense on TPU). Knobs tied to MPI endpoint servers are accepted
and mapped to their TPU analog or recorded as no-ops, so existing launch scripts keep
working.
"""

from __future__ import annotations

import dataclasses
import os

from mlsl_tpu.obs.tracer import DEFAULT_CAPACITY as _TRACE_DEFAULT_CAPACITY


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    return int(v) if v not in (None, "") else default


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    return float(v) if v not in (None, "") else default


def _env_bool(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v in (None, ""):
        return default
    return v.strip().lower() not in ("0", "false", "no", "off")


# Registry codec names (mlsl_tpu.codecs), mirrored statically: validate()
# must stay importable without jax, and the registry re-asserts membership
# at every get() so the mirror cannot drift silently past dispatch.
_CODEC_NAMES = ("f32", "int8", "prune", "topk", "vq")

# env var -> Config field, for the explicit-override bookkeeping in from_env
# (auto_config must never clobber a knob the user exported)
_ENV_FIELDS = {
    "MLSL_LARGE_MSG_SIZE_MB": "large_msg_size_mb",
    "MLSL_LARGE_MSG_CHUNKS": "large_msg_chunks",
    "MLSL_MSG_PRIORITY_THRESHOLD": "msg_priority_threshold",
    "MLSL_MSG_PRIORITY_FLUSH_MS": "msg_priority_flush_ms",
    "MLSL_GATHER_DEVICE_LIMIT_MB": "gather_device_limit_mb",
    "MLSL_GRAD_BUCKET_MB": "grad_bucket_mb",
    "MLSL_NUM_SERVERS": "num_servers",
    "MLSL_QUANT_BLOCK_ELEMS": "quant_block_elems",
    "MLSL_HIER_DCN_CODEC": "hier_dcn_codec",
    "MLSL_CODEC": "codec",
    "MLSL_CODEC_NSR_BUDGET": "codec_nsr_budget",
    "MLSL_CODEC_GUARD_BREACHES": "codec_guard_breaches",
    "MLSL_VQ_DIM": "vq_dim",
    "MLSL_VQ_CODEBOOK": "vq_codebook",
    "MLSL_PRUNE_RATIO": "prune_ratio",
    "MLSL_PALLAS_RING_SLOTS": "pallas_ring_slots",
    "MLSL_PALLAS_RHD_MAX_BYTES": "pallas_rhd_max_bytes",
    "MLSL_OVERLAP_STAGES": "overlap_stages",
    "MLSL_FEED_DEPTH": "feed_depth",
    "MLSL_FEED_CACHE_MB": "feed_cache_mb",
    "MLSL_FEED_WIRE_DTYPE": "feed_wire_dtype",
    "MLSL_SENTINEL_EVERY": "sentinel_every",
    "MLSL_METRICS_EVERY": "metrics_every",
    "MLSL_STRAGGLER_EVERY": "straggler_every",
    "MLSL_HEARTBEAT_MISSES": "heartbeat_misses",
    "MLSL_SERVE_MAX_BATCH": "serve_max_batch",
    "MLSL_SERVE_KV_PAGE_ELEMS": "serve_kv_page_elems",
    "MLSL_SERVE_KV_CACHE_MB": "serve_kv_cache_mb",
    "MLSL_SERVE_QUEUE_DEPTH": "serve_queue_depth",
}


@dataclasses.dataclass
class Config:
    # --- core tier (reference src/env.cpp:26-40) ---
    log_level: int = 0              # MLSL_LOG_LEVEL
    dup_group: bool = False         # MLSL_DUP_GROUP: force a dedicated data group even
                                    # when dataParts == world size
    enable_stats: bool = False      # MLSL_STATS
    auto_config_type: int = 0       # MLSL_AUTO_CONFIG_TYPE

    # --- dispatch/backend tier (reference src/comm_ep.cpp:43-92) ---
    # Number of parallel dispatch lanes. TPU analog of MLSL_NUM_SERVERS (endpoint
    # count): how many independent collective launches may be in flight.
    num_servers: int = 4            # MLSL_NUM_SERVERS
    # Chunking for very large messages (reference splits >128 MiB into chunks,
    # src/comm_ep.cpp:95-97). XLA handles ICI channelization; the knob survives as the
    # size at which a collective is split into independently dispatched chunks so Wait
    # can complete (and overlap) incrementally.
    large_msg_size_mb: int = 128    # MLSL_LARGE_MSG_SIZE_MB
    large_msg_chunks: int = 4       # MLSL_LARGE_MSG_CHUNKS
    max_short_msg_size: int = 0     # MLSL_MAX_SHORT_MSG_SIZE
    # Gradient bucketing (core/bucketing.py): coalesce per-layer gradient
    # allreduces below this bucket size into one concatenated allreduce
    # (fewer host dispatches, bandwidth-sized wire messages). 0 = off.
    grad_bucket_mb: int = 0         # MLSL_GRAD_BUCKET_MB
    # Per-device output cap (MiB) for the device-side rooted gather, whose
    # rank-uniform SPMD result replicates the concatenation on every member
    # (docs/DESIGN.md 'Rooted gather'); larger gathers must use
    # gather_to_host (host delivery, no device footprint). 0 = unlimited.
    gather_device_limit_mb: int = 1024  # MLSL_GATHER_DEVICE_LIMIT_MB

    # --- priority scheduling (reference eplib/env.c:135-165, allreduce_pr.c) ---
    msg_priority: bool = False        # MLSL_MSG_PRIORITY: newest-first dispatch
    msg_priority_threshold: int = 10000  # MLSL_MSG_PRIORITY_THRESHOLD (bytes)
    msg_priority_mode: bool = True    # MLSL_MSG_PRIORITY_MODE: 1 = LIFO
    # Coalescing window before the progress thread launches deferred requests on
    # its own (reference: endpoint servers progress without app polls,
    # eplib/allreduce_pr.c:69-278). Requests deferred within the window are
    # launched together, newest first.
    msg_priority_flush_ms: float = 2.0  # MLSL_MSG_PRIORITY_FLUSH_MS

    # --- collective algorithm engine (comm/algos) + autotuner (tuner/) ---
    # Forced algorithm selection: '' = auto (tuned profile, else the 'lax'
    # baseline). Either one registry name ('rhd') applied to every engine
    # kind, or a comma list of kind=name entries
    # ('allreduce=rhd,reduce_scatter=ring2d'). Validated against the
    # registry at init (validate()) — an unknown name is an MLSLError there,
    # not a failure deep in dispatch.
    collective_algo: str = ""       # MLSL_ALGO
    # Run the topology autotuner at Environment.init: sweep candidate
    # algorithms x chunk/bucket/priority knobs on the live mesh and persist
    # the winning table to ``tune_profile`` (tuner/).
    tune: bool = False              # MLSL_TUNE
    # Profile path: read at init when set (MLSL_TUNE=0), written when the
    # sweep runs (MLSL_TUNE=1). '' = the default mlsl_tune_profile.json in
    # MLSL_STATS_DIR (or CWD). A profile whose topology fingerprint does not
    # match the probed hardware is rejected with a warning; a missing or
    # corrupt file is an MLSLError at init.
    tune_profile: str = ""          # MLSL_TUNE_PROFILE
    # Loaded tuner.TunedProfile (or None): consulted by comm/algos.select
    # for every engine collective. Set by Environment.init, never from env.
    tuned_profile: object = None

    # --- hierarchical (two-tier) collectives (comm/algos/hier.py;
    # docs/TUNING.md §17) ---
    # Synthetic tier override 'TxL' (T DCN slices x L devices/slice): how
    # the CPU proof mesh and tier-1 exercise a two-tier world. On real TPU
    # multislice the tier map comes from device.slice_index and this stays
    # ''. Recorded for discoverability like pallas_interpret: the mesh/hier
    # modules read the SAME env var per build, so a monkeypatched env is
    # honored without a Config handle. Validated at init.
    mesh_tiers: str = ""            # MLSL_MESH_TIERS
    # DCN-tier codec for the 'hier' compressed wire: 'int8' (blockwise
    # shared-scale integer sum — the THC shape, default), 'topk', or 'f32'
    # (no compression on the slow hop). The ICI tier is always f32.
    # Tunable via a tuner profile (tuner.KNOB_CHOICES); exported env wins.
    hier_dcn_codec: str = "int8"    # MLSL_HIER_DCN_CODEC

    # --- pallas ring kernels (ops/ring_kernels.py; docs/TUNING.md §15) ---
    # Comm slots per ring direction for the 'pallas_ring' lowering: how many
    # in-flight recv slots the double-buffered RDMA cycles through (>= 2; a
    # remote-capacity semaphore handshake guards reuse). More slots = more
    # hop-pipelining headroom at (slots x chunk) VMEM cost. Tunable via a
    # tuner profile (tuner.KNOB_RANGES); an exported env var always wins.
    pallas_ring_slots: int = 2      # MLSL_PALLAS_RING_SLOTS
    # Bidirectional variant: split the payload's block-rows in half and run
    # opposite-rotation rings concurrently (both directions of each full-
    # duplex ICI link). Changes quantization grouping order, so the
    # quantized EF-parity oracle covers the unidirectional form only.
    pallas_ring_bidir: bool = False  # MLSL_PALLAS_RING_BIDIR
    # Arm the latency-class fused allreduce heuristic rung: with this set,
    # dense SUM allreduces whose payload fits the small-message band lower
    # to 'pallas_rhd' (ops/rhd_kernels.py — log2(G) halving/doubling rounds
    # in one kernel) WITHOUT a tuned profile or MLSL_ALGO. Off by default:
    # untuned selection stays bit-for-bit the baseline. A forced or tuned
    # 'pallas_rhd' works regardless of this knob, like any algorithm.
    pallas_rhd: bool = False         # MLSL_PALLAS_RHD
    # Upper edge (bytes) of the heuristic band above. 0 = derive from the
    # reference's small-message boundary: 4 x msg_priority_threshold
    # elements' worth of f32 payload (rhd_kernels.env_max_bytes). Tunable
    # via a tuner profile (tuner.KNOB_RANGES); an exported env always wins.
    pallas_rhd_max_bytes: int = 0    # MLSL_PALLAS_RHD_MAX_BYTES
    # Fuse the int8 blockwise codec into the 'pallas_a2a' alltoall wire
    # (quantize on send-slot write, dequantize on receive — wire bytes
    # <= 1/3 of f32). Off = the same kernel exchanges dense f32. The codec
    # block size rides MLSL_QUANT_BLOCK_ELEMS like every quantized wire.
    pallas_a2a_quant: bool = True    # MLSL_PALLAS_A2A_QUANT
    # Interpreter gate, recorded for discoverability like chaos_spec: the
    # kernels read the SAME env var per build ('1' force-interpret, '0'
    # force-compiled, '' = compiled on TPU / interpreter elsewhere — but
    # selection only admits pallas_ring off-TPU when explicitly '1').
    pallas_interpret: str = ""       # MLSL_PALLAS_INTERPRET

    # --- compiled overlap engine (comm/overlap.py; docs/TUNING.md §14) ---
    # Arm the single-dispatch compiled step: the backward pass decomposed
    # per layer with every gradient collective emitted IN-GRAPH,
    # newest-first, so XLA's latency-hiding scheduler overlaps ICI DMA with
    # compute instead of the host per-layer poll loop. The host path stays
    # the default and the parity oracle.
    overlap_compiled: bool = False   # MLSL_OVERLAP_COMPILED
    # Staging depth: a layer's reduce phases are spread over the next this-
    # many unit starts (stage boundaries pinned with optimization_barrier).
    # Tunable via a tuner profile (tuner.KNOB_RANGES); exported env wins.
    overlap_stages: int = 2          # MLSL_OVERLAP_STAGES

    # --- device feed pipeline (mlsl_tpu.data; docs/TUNING.md §12) ---
    # Wire dtype for host->device batch transfer: '' = full width (off),
    # 'uint8' (images: 4x vs f32), 'bf16' (2x), 'int8' (block codec shared
    # with the quantized collectives). Per-leaf overrides ride in the same
    # string ('uint8,y=none'); parsed/validated by data.wire.parse_wire_spec
    # at validate(). The data package reads the SAME env var per feed, so
    # standalone DeviceFeed construction honors it without a Config handle.
    feed_wire_dtype: str = ""       # MLSL_FEED_WIRE_DTYPE
    # HBM budget (MiB) for the feed cache: wire batches pin on device after
    # first touch and epoch replays skip h2d entirely. 0 = off.
    feed_cache_mb: int = 0          # MLSL_FEED_CACHE_MB
    # Prefetch depth: batches in flight device-side (2 = double buffering).
    # Tunable via a tuner profile (tuner.KNOB_RANGES) — an exported env var
    # always wins (the Config._explicit contract).
    feed_depth: int = 2             # MLSL_FEED_DEPTH
    # TRANSIENT source-read retries per batch (supervisor taxonomy, rung 2).
    feed_retries: int = 2           # MLSL_FEED_RETRIES

    # --- serving engine (mlsl_tpu.serve; docs/TUNING.md §21) ---
    # Decode-slot ceiling for the in-flight continuous batch. New sequences
    # join at decode-step granularity up to this many slots; the SLA ladder
    # sheds below it under pressure. Tunable via a tuner profile — an
    # exported env var always wins (the Config._explicit contract).
    serve_max_batch: int = 8        # MLSL_SERVE_MAX_BATCH
    # Tokens per KV page: the paged-cache allocation granularity. Small
    # pages waste less HBM on short tails but grow the page tables; sized
    # by the tuner, an exported env always wins.
    serve_kv_page_elems: int = 16   # MLSL_SERVE_KV_PAGE_ELEMS
    # HBM budget (MiB) for the paged KV cache (global logical bytes, the
    # FeedCache accounting contract). Caps total pages; admissions that
    # cannot get pages are refused or trigger eviction of finished tails.
    serve_kv_cache_mb: int = 64     # MLSL_SERVE_KV_CACHE_MB
    # Admission queue depth: requests waiting beyond the in-flight batch.
    # Over it, submit() rejects 429-style with a retry-after hint instead
    # of queueing unboundedly (the AsyncLoader backpressure contract).
    serve_queue_depth: int = 32     # MLSL_SERVE_QUEUE_DEPTH
    # Store KV pages int8-blockwise (ops/quant_kernels codec) instead of
    # full width: ~4x more tokens per HBM byte at a bounded dequantize
    # error; also what SLA ladder rung 2 switches on under pressure.
    serve_kv_quant: bool = False    # MLSL_SERVE_KV_QUANT

    # --- compression ---
    quant_block_elems: int = 256
    topk_ratio: float = 0.01       # MLSL_TOPK_RATIO: fraction of elements kept
    # user-pluggable codec (comm/codec.py CustomCodec), registered through
    # Environment.set_quantization_params; None = built-in Pallas int8 kernels
    custom_codec: object = None

    # --- codec lab (mlsl_tpu.codecs; docs/TUNING.md §22) ---
    # Registry codec for every QUANTIZATION-compressed gradient wire:
    # '' = the seed int8 path; any mlsl_tpu.codecs name ('vq', 'prune',
    # 'topk', 'f32') routes through the registry transport. An EXPORTED
    # MLSL_CODEC beats a calibrated per-set assignment (the _explicit
    # contract); a programmatic value is the default the calibration
    # overrides per set.
    codec: str = ""                 # MLSL_CODEC
    # Run the codec calibration pass at Session.commit (tuner/calibrate.py):
    # measure per-set norm spectra + quantization noise-to-signal, solve
    # codec x block per ParameterSet against codec_nsr_budget, persist the
    # assignment into the topology-keyed tuned profile, and re-route the
    # live gradient requests to the solved codecs.
    tune_codec: bool = False        # MLSL_TUNE_CODEC
    # Per-set codec assignment (request name -> calibration cell dict):
    # written by the calibration pass or loaded from a tuned profile at
    # init. Never set from env.
    codec_assignment: dict = dataclasses.field(default_factory=dict)
    # Calibration convergence budget: max per-set quantization-noise-to-
    # signal power ratio a solved codec may incur; sets where no cheaper
    # codec fits the budget stay int8.
    codec_nsr_budget: float = 0.02  # MLSL_CODEC_NSR_BUDGET
    # Consecutive sentinel loss z-score breaches (while a calibrated codec
    # is live) before the guardrail demotes every calibrated set to int8.
    codec_guard_breaches: int = 3   # MLSL_CODEC_GUARD_BREACHES
    # VQ codec shape: elements per vector and codebook rows (<= 256: one
    # index byte per vector on the wire). Tunable via a tuner profile.
    vq_dim: int = 4                 # MLSL_VQ_DIM
    vq_codebook: int = 16           # MLSL_VQ_CODEBOOK
    # Pruning codec keep ratio (importance-weighted masks); the calibrated
    # per-set ratio overrides this uniform default.
    prune_ratio: float = 0.05       # MLSL_PRUNE_RATIO

    # --- robustness tier (chaos layer + watchdog + checkpoint retry) ---
    # Request watchdog: wait() on an async request raises MLSLTimeoutError
    # (recoverable) once the request has been in flight longer than this,
    # instead of blocking forever on a hung collective. 0 = off.
    watchdog_timeout_s: float = 0.0   # MLSL_WATCHDOG_TIMEOUT (seconds)
    # Checkpoint save retry on transient IO errors (OSError): attempts beyond
    # the first, with exponential backoff starting at the base below. Recorded
    # here for discoverability/printing only (like chaos_spec): CheckpointManager
    # has no Config handle and reads the SAME env vars at construction —
    # override programmatically via its save_retries/retry_backoff_s ctor args,
    # not by mutating these fields.
    ckpt_save_retries: int = 3          # MLSL_CKPT_SAVE_RETRIES
    ckpt_retry_backoff_s: float = 0.05  # MLSL_CKPT_RETRY_BACKOFF_S
    # Recovery ladder (mlsl_tpu.supervisor). Rung 2: transient collective
    # dispatch/wait failures retry in place with exponential backoff +
    # jitter before anything escalates. 0 = no retries (fail straight to
    # the breaker/restart rungs).
    comm_retries: int = 2               # MLSL_COMM_RETRIES
    comm_retry_backoff_s: float = 0.05  # MLSL_COMM_RETRY_BACKOFF_S
    # Rung 3: per-subsystem circuit breakers (quant codec, grad buckets,
    # algo engine, tracer). After `threshold` classified failures inside the
    # sliding window the subsystem degrades to its always-correct fallback;
    # after the cooldown a half-open probe re-engages the fast path.
    # Breakers are process-wide (state survives Environment rebuilds —
    # deliberately, so recovery cycles can escalate); these knobs are
    # (re)applied to them at Environment.init via supervisor.configure.
    breaker_threshold: int = 3          # MLSL_BREAKER_THRESHOLD
    breaker_window_s: float = 30.0      # MLSL_BREAKER_WINDOW_S
    breaker_cooldown_s: float = 10.0    # MLSL_BREAKER_COOLDOWN_S
    # Rung 4: total checkpoint recoveries FaultTolerantLoop performs across
    # a run before aborting with a flight record. Read by the loop itself
    # (like the checkpoint retry knobs: recorded here for discoverability —
    # override via the FaultTolerantLoop ctor, not by mutating this field).
    restart_budget: int = 20            # MLSL_RESTART_BUDGET
    # --- elastic mesh (mlsl_tpu.elastic; docs/TUNING.md §18) ---
    # Arm the elastic coordinator: a DEVICE_LOSS fault (preemption, the
    # chaos device.lost site) is answered by re-deriving the mesh among
    # survivors and re-sharding ZeRO-1 optimizer state live — no checkpoint
    # restore — instead of the restart rung. Off, every loss restarts
    # (pre-elastic behavior, bit-for-bit unchanged).
    elastic: bool = False               # MLSL_ELASTIC
    # Capacity budget: total devices the run may shed across its lifetime
    # before a further loss escalates to the restart rung (the elastic
    # analog of MLSL_RESTART_BUDGET — bounded capacity churn, not bounded
    # restarts). 0 = auto: half the world, resolved at coordinator
    # construction where the world size is known.
    capacity_budget: int = 0            # MLSL_CAPACITY_BUDGET
    # Simulated/announced capacity return: steps after a shrink at which the
    # lost devices rejoin (through the admission audit). 0 = only on an
    # explicit ElasticCoordinator.announce_return() (production: the
    # replacement host announcing itself).
    elastic_grow_after: int = 0         # MLSL_ELASTIC_GROW_AFTER
    # Admission-audit retries: a rejoining replica whose fingerprint audit
    # fails is re-synced from a survivor copy and re-audited up to this many
    # times before the grow is abandoned.
    elastic_admit_retries: int = 1      # MLSL_ELASTIC_ADMIT_RETRIES
    # --- integrity sentinel (mlsl_tpu.sentinel; docs/TUNING.md §13) ---
    # Step quality gate response: '' = gate off; 'warn' logs and continues,
    # 'skip_step' discards the poisoned update (EF residuals and data order
    # stay consistent — the step behaves as if it never ran), 'rollback'
    # raises MLSLIntegrityError so FaultTolerantLoop restores the newest
    # VERIFIED checkpoint. An armed gate disables the no-comm fused step
    # shortcut (the gate needs the gradient boundary).
    sentinel_gate: str = ""             # MLSL_SENTINEL_GATE
    # Cross-replica consistency audit interval in steps (0 = off): a
    # blockwise int32 fingerprint of params + optimizer state is compared
    # across replicas via on-device pmin/pmax equality (no host gather).
    # Tunable via a tuner profile (tuner.KNOB_RANGES); exported env wins.
    sentinel_every: int = 0             # MLSL_SENTINEL_EVERY
    # Grad-norm spike screen: fire when the global gradient norm exceeds
    # this factor times its EMA (armed after sentinel_warmup healthy steps).
    sentinel_spike: float = 10.0        # MLSL_SENTINEL_SPIKE
    # Loss z-score screen: fire when |loss - EMA mean| exceeds this many
    # EMA standard deviations (armed after warmup).
    sentinel_zmax: float = 8.0          # MLSL_SENTINEL_ZMAX
    # Healthy steps observed before the spike/z-score screens arm (the
    # nonfinite screen is always armed — it needs no history).
    sentinel_warmup: int = 5            # MLSL_SENTINEL_WARMUP
    # Fingerprint block size in elements: one int32 checksum per block.
    # Smaller blocks localize a corruption better but grow the on-device
    # fingerprint vector (total_elems / block int32s).
    sentinel_block: int = 4096          # MLSL_SENTINEL_BLOCK
    # --- static analysis (mlsl_tpu.analysis; docs/TUNING.md §16) ---
    # Commit-time collective-plan verifier: MLSL_VERIFY=1 walks the
    # committed graph at Session.commit and statically checks issue-order
    # consistency, in-flight budgets, quantization geometry, EF
    # snapshot/rewind pairing, and Pallas-ring semaphore accounting
    # (analysis/plan.py; findings use the stable MLSL-Axxx codes).
    verify: bool = False                # MLSL_VERIFY
    # What an error-severity finding does at commit: 'error' (default)
    # raises MLSLError naming every code; 'warn' logs the findings and
    # commits anyway (both record the verdict in supervisor.status()['analysis']
    # and the ANALYSIS stats line).
    verify_severity: str = "error"      # MLSL_VERIFY_SEVERITY
    # Runtime lock witness (analysis/witness.py; docs/TUNING.md §23): kept
    # here for discoverability/printing only, like chaos_spec — the witness
    # reads the env at lock *creation* time (subsystems build their locks at
    # import/__init__, before any Config exists), so arming mid-run has no
    # effect. MLSL_LOCK_WITNESS=1 routes the named locks of the threaded
    # subsystems through an instrumented wrapper that records acquisition-
    # order edges, cycles, and over-budget holds.
    lock_witness: bool = False          # MLSL_LOCK_WITNESS
    # Hold-time budget: a release after more than this many ms is reported
    # as an over-budget hold (the runtime shadow of static rule A211).
    lock_witness_budget_ms: float = 250.0   # MLSL_LOCK_WITNESS_BUDGET_MS
    # Fault-injection spec; parsed by mlsl_tpu.chaos
    # (site:kind[=v][@after][xN][%p], comma-separated). Kept here for
    # discoverability/printing only.
    chaos_spec: str = ""            # MLSL_CHAOS

    # --- telemetry plane (mlsl_tpu.obs.metrics/serve/straggler;
    # docs/TUNING.md §19) ---
    # Arm the typed time-series registry: counter/gauge/histogram series
    # over every stats counter family plus per-step scalars (loss,
    # grad-norm, step_ms, input_stall_ms, dispatch->wait latency, per-algo
    # achieved algbw). Disabled = one module-attr check per site, zero
    # allocations (the tracer contract). Armed implicitly by
    # MLSL_METRICS_PORT.
    metrics: bool = False           # MLSL_METRICS
    # Sampler cadence in steps: loss readback, counter-family snapshot,
    # ring sample, and the JSONL append happen every this-many steps.
    # Tunable via a tuner profile (tuner.KNOB_RANGES); exported env wins.
    metrics_every: int = 20         # MLSL_METRICS_EVERY
    # Scrape surface: serve /metrics (Prometheus text), /healthz
    # (supervisor.status() as JSON) and /statusz (human summary) from a
    # stdlib HTTP daemon thread on this port. 0 = off.
    metrics_port: int = 0           # MLSL_METRICS_PORT
    # Timestamped samples retained per series (ring, deque(maxlen)).
    metrics_retention: int = 512    # MLSL_METRICS_RETENTION
    # Straggler sentinel (obs/straggler.py): fire when one replica's
    # windowed median step time exceeds this multiple of its peers'
    # median, sustained over straggler_sustain consecutive audits.
    # 0 = off; armed values must be > 1.
    straggler_skew: float = 0.0     # MLSL_STRAGGLER_SKEW
    # Observed steps per cross-replica audit window. Tunable via a tuner
    # profile (tuner.KNOB_RANGES); exported env wins.
    straggler_every: int = 20       # MLSL_STRAGGLER_EVERY
    # Consecutive suspect audits before a replica is CONFIRMED (one GC
    # pause / load spike must not flag, let alone shed).
    straggler_sustain: int = 2      # MLSL_STRAGGLER_SUSTAIN
    # Hand a confirmed straggler to the elastic coordinator as a shed
    # candidate (synthetic DEVICE_LOSS through ElasticCoordinator.shed;
    # needs MLSL_ELASTIC armed to act). Off = observe/flag only.
    straggler_shed: bool = False    # MLSL_STRAGGLER_SHED
    # Watchdog-trip device profile: on MLSLTimeoutError also capture a
    # short jax.profiler trace next to the flight record, so a wedged wait
    # arrives with host timeline AND device profile. Read per trip by
    # core/stats (recorded here for discoverability, like chaos_spec).
    profile_on_trip: bool = False   # MLSL_PROFILE_ON_TRIP

    # --- pod control plane (mlsl_tpu.control; docs/TUNING.md §20) ---
    # Heartbeat cadence on the control channel (stdlib TCP, separate from
    # the JAX collective fabric). Detection latency is
    # interval * misses; LAN/localhost pods can run well under a second.
    heartbeat_interval_s: float = 2.0   # MLSL_HEARTBEAT_INTERVAL_S
    # Consecutive missed intervals before a peer is declared locally dead
    # and proposed for a loss-epoch commit. Tunable via a tuner profile
    # (tuner.KNOB_RANGES: false-positive resharding vs detection latency);
    # exported env wins.
    heartbeat_misses: int = 3           # MLSL_HEARTBEAT_MISSES
    # Boot grace: silence from a never-heard peer is tolerated this long
    # (it may still be importing jax / compiling) before miss accounting
    # treats it like any other death.
    heartbeat_grace_s: float = 30.0     # MLSL_HEARTBEAT_GRACE_S
    # Cluster-scheduler hook (ROADMAP #2a): a scheduler that cannot
    # deliver SIGTERM writes this file; its appearance is a preemption
    # notice for this host, coordinated pod-wide like the signal.
    preemption_file: str = ""           # MLSL_PREEMPTION_FILE
    # Control-world bootstrap. Explicit form: "host:port,host:port,..."
    # (rank-ordered). Localhost shorthand for the CPU pod sim:
    # control_port (base) + control_world (N members, consecutive ports).
    # Both empty/0 = this process is not a pod member (the default — no
    # socket is ever opened).
    control_addrs: str = ""             # MLSL_CONTROL_ADDRS
    control_port: int = 0               # MLSL_CONTROL_PORT
    control_world: int = 0              # MLSL_CONTROL_WORLD
    control_rank: int = -1              # MLSL_CONTROL_RANK
    # jax.distributed.initialize retry budget (the gloo TCP preamble race,
    # KNOWN_FAILURES.md): attempts beyond the first, exponential backoff
    # from dist_init_backoff_s. Control-channel commit sends reuse the
    # same retry idiom.
    dist_init_retries: int = 3          # MLSL_DIST_INIT_RETRIES
    dist_init_backoff_s: float = 0.5    # MLSL_DIST_INIT_BACKOFF_S

    # --- observability tier (mlsl_tpu.obs span tracer) ---
    # Kept for discoverability/printing only, like chaos_spec: the tracer is
    # process-wide (armed at import unless MLSL_TRACE=0: the ring is the
    # flight recorder; obs.enable()/disable() at run time) and the
    # output dir / ring capacity are read from the SAME env vars per call —
    # override via the obs API, not by mutating these fields.
    trace: bool = True              # MLSL_TRACE: 0 disarms the span tracer
    trace_dir: str = ""             # MLSL_TRACE_DIR: trace-*.json output dir
    # MLSL_TRACE_CAPACITY: ring size (events); single source of truth is the
    # tracer's own default
    trace_capacity: int = _TRACE_DEFAULT_CAPACITY

    # --- accepted-for-parity no-ops (MPI/shm specific) ---
    server_affinity: str = ""       # MLSL_SERVER_AFFINITY
    heap_size_gb: int = 0           # MLSL_HEAP_SIZE_GB
    alltoall_split: int = 1         # MLSL_ALLTOALL_SPLIT
    thp_threshold_mb: int = 0       # MLSL_THP_THRESHOLD_MB

    # Commit-time AOT precompilation (comm: Session.precompile_collectives):
    # warm-execute every collective program the committed graph can dispatch —
    # plain, bucketed, and quant-ring — on zero buffers at Commit, so step 0
    # of the training loop contains no collective compilation. Composes with
    # JAX's persistent compilation cache (sysinfo.resolve_compile_cache): the
    # warm run itself reloads from disk.
    precompile: bool = False        # MLSL_PRECOMPILE

    def validate(self) -> None:
        """Reject contradictory or unserviceable settings with a clear
        MLSLError at init time instead of failing deep in dispatch. Parses
        ``collective_algo`` into the ``_forced_algos`` dict comm/algos.select
        consults (raising on names not in the registry); basic range sanity
        on the numeric knobs the engine and tuner rely on. Profile-file
        errors (missing/corrupt MLSL_TUNE_PROFILE) are raised by
        mlsl_tpu.tuner.init_profile, which Environment.init calls right after
        this."""
        from mlsl_tpu.comm import algos
        from mlsl_tpu.log import mlsl_assert

        self._forced_algos = algos.parse_forced(self.collective_algo)
        mlsl_assert(
            self.large_msg_size_mb >= 0,
            "MLSL_LARGE_MSG_SIZE_MB must be >= 0 (got %d)",
            self.large_msg_size_mb,
        )
        mlsl_assert(
            self.large_msg_chunks >= 1,
            "MLSL_LARGE_MSG_CHUNKS must be >= 1 (got %d)",
            self.large_msg_chunks,
        )
        mlsl_assert(
            self.quant_block_elems > 0,
            "MLSL_QUANT_BLOCK_ELEMS must be > 0 (got %d)",
            self.quant_block_elems,
        )
        mlsl_assert(
            0.0 < self.topk_ratio <= 1.0,
            "MLSL_TOPK_RATIO must be in (0, 1] (got %r)", self.topk_ratio,
        )
        mlsl_assert(
            self.grad_bucket_mb >= 0,
            "MLSL_GRAD_BUCKET_MB must be >= 0 (got %d)", self.grad_bucket_mb,
        )
        mlsl_assert(
            self.overlap_stages >= 1,
            "MLSL_OVERLAP_STAGES must be >= 1 (got %d)", self.overlap_stages,
        )
        mlsl_assert(
            self.pallas_ring_slots >= 2,
            "MLSL_PALLAS_RING_SLOTS must be >= 2 (the ring needs a double "
            "buffer; got %d)", self.pallas_ring_slots,
        )
        mlsl_assert(
            self.pallas_rhd_max_bytes >= 0,
            "MLSL_PALLAS_RHD_MAX_BYTES must be >= 0 (0 = derive from "
            "MLSL_MSG_PRIORITY_THRESHOLD; got %d)", self.pallas_rhd_max_bytes,
        )
        # MLSL_MESH_TIERS grammar, checked locally (comm.mesh's
        # parse_mesh_tiers applies the same rules but imports jax; validate()
        # must stay importable without it). World-coverage is checked where
        # the world is known (mesh.world_tier_ids).
        spec = (self.mesh_tiers or "").strip().lower()
        if spec:
            parts = spec.split("x")
            mlsl_assert(
                len(parts) == 2
                and all(p.strip().isdigit() and int(p) >= 1 for p in parts),
                "MLSL_MESH_TIERS must be 'TxL' with positive ints (got %r)",
                self.mesh_tiers,
            )
        mlsl_assert(
            self.hier_dcn_codec in _CODEC_NAMES,
            "MLSL_HIER_DCN_CODEC must be one of %s (got %r)",
            "/".join(_CODEC_NAMES), self.hier_dcn_codec,
        )
        mlsl_assert(
            self.codec in ("",) + _CODEC_NAMES,
            "MLSL_CODEC must be '' or one of %s (got %r)",
            "/".join(_CODEC_NAMES), self.codec,
        )
        mlsl_assert(
            isinstance(self.codec_assignment, dict),
            "codec_assignment must be a dict of request name -> calibration "
            "cell (got %r)", type(self.codec_assignment).__name__,
        )
        mlsl_assert(
            self.codec_nsr_budget > 0.0,
            "MLSL_CODEC_NSR_BUDGET must be > 0 (got %r)", self.codec_nsr_budget,
        )
        mlsl_assert(
            self.codec_guard_breaches >= 1,
            "MLSL_CODEC_GUARD_BREACHES must be >= 1 (got %d)",
            self.codec_guard_breaches,
        )
        mlsl_assert(
            1 <= self.vq_dim <= 64,
            "MLSL_VQ_DIM must be in [1, 64] (got %d)", self.vq_dim,
        )
        mlsl_assert(
            2 <= self.vq_codebook <= 256,
            "MLSL_VQ_CODEBOOK must be in [2, 256] (one index byte per "
            "vector; got %d)", self.vq_codebook,
        )
        mlsl_assert(
            0.0 < self.prune_ratio <= 1.0,
            "MLSL_PRUNE_RATIO must be in (0, 1] (got %r)", self.prune_ratio,
        )
        mlsl_assert(
            self.pallas_interpret in ("", "0", "1"),
            "MLSL_PALLAS_INTERPRET must be '', '0' or '1' (got %r)",
            self.pallas_interpret,
        )
        mlsl_assert(
            self.watchdog_timeout_s >= 0,
            "MLSL_WATCHDOG_TIMEOUT must be >= 0 (got %r)",
            self.watchdog_timeout_s,
        )
        mlsl_assert(
            self.comm_retries >= 0,
            "MLSL_COMM_RETRIES must be >= 0 (got %d)", self.comm_retries,
        )
        mlsl_assert(
            self.comm_retry_backoff_s >= 0,
            "MLSL_COMM_RETRY_BACKOFF_S must be >= 0 (got %r)",
            self.comm_retry_backoff_s,
        )
        mlsl_assert(
            self.breaker_threshold >= 1,
            "MLSL_BREAKER_THRESHOLD must be >= 1 (got %d)",
            self.breaker_threshold,
        )
        mlsl_assert(
            self.breaker_window_s >= 0 and self.breaker_cooldown_s >= 0,
            "MLSL_BREAKER_WINDOW_S / MLSL_BREAKER_COOLDOWN_S must be >= 0 "
            "(got %r / %r)", self.breaker_window_s, self.breaker_cooldown_s,
        )
        mlsl_assert(
            self.restart_budget >= 0,
            "MLSL_RESTART_BUDGET must be >= 0 (got %d)", self.restart_budget,
        )
        mlsl_assert(
            self.capacity_budget >= 0,
            "MLSL_CAPACITY_BUDGET must be >= 0 (0 = half the world; got %d)",
            self.capacity_budget,
        )
        mlsl_assert(
            self.elastic_grow_after >= 0,
            "MLSL_ELASTIC_GROW_AFTER must be >= 0 (0 = manual announce; "
            "got %d)", self.elastic_grow_after,
        )
        mlsl_assert(
            self.elastic_admit_retries >= 0,
            "MLSL_ELASTIC_ADMIT_RETRIES must be >= 0 (got %d)",
            self.elastic_admit_retries,
        )
        mlsl_assert(
            self.sentinel_gate in ("", "warn", "skip_step", "rollback"),
            "MLSL_SENTINEL_GATE must be one of '', 'warn', 'skip_step', "
            "'rollback' (got %r)", self.sentinel_gate,
        )
        mlsl_assert(
            self.sentinel_every >= 0,
            "MLSL_SENTINEL_EVERY must be >= 0 (got %d)", self.sentinel_every,
        )
        mlsl_assert(
            self.sentinel_spike > 1.0,
            "MLSL_SENTINEL_SPIKE must be > 1 (got %r)", self.sentinel_spike,
        )
        mlsl_assert(
            self.sentinel_zmax > 0,
            "MLSL_SENTINEL_ZMAX must be > 0 (got %r)", self.sentinel_zmax,
        )
        mlsl_assert(
            self.sentinel_warmup >= 0,
            "MLSL_SENTINEL_WARMUP must be >= 0 (got %d)",
            self.sentinel_warmup,
        )
        mlsl_assert(
            self.sentinel_block > 0,
            "MLSL_SENTINEL_BLOCK must be > 0 (got %d)", self.sentinel_block,
        )
        try:
            # common, not wire: the grammar parser is dependency-free, so
            # validate() does not drag in jax/numpy/the Pallas kernels
            from mlsl_tpu.data.common import parse_wire_spec

            parse_wire_spec(self.feed_wire_dtype)
        except ValueError as e:
            from mlsl_tpu.log import MLSLError

            raise MLSLError(f"MLSL_FEED_WIRE_DTYPE: {e}") from e
        mlsl_assert(
            self.feed_depth >= 1,
            "MLSL_FEED_DEPTH must be >= 1 (got %d)", self.feed_depth,
        )
        mlsl_assert(
            self.feed_cache_mb >= 0,
            "MLSL_FEED_CACHE_MB must be >= 0 (got %d)", self.feed_cache_mb,
        )
        mlsl_assert(
            self.feed_retries >= 0,
            "MLSL_FEED_RETRIES must be >= 0 (got %d)", self.feed_retries,
        )
        mlsl_assert(
            self.serve_max_batch >= 1,
            "MLSL_SERVE_MAX_BATCH must be >= 1 (got %d)",
            self.serve_max_batch,
        )
        mlsl_assert(
            self.serve_kv_page_elems >= 1,
            "MLSL_SERVE_KV_PAGE_ELEMS must be >= 1 (got %d)",
            self.serve_kv_page_elems,
        )
        mlsl_assert(
            self.serve_kv_cache_mb >= 1,
            "MLSL_SERVE_KV_CACHE_MB must be >= 1 — a zero-page cache "
            "cannot admit any sequence (got %d)", self.serve_kv_cache_mb,
        )
        mlsl_assert(
            self.serve_queue_depth >= 1,
            "MLSL_SERVE_QUEUE_DEPTH must be >= 1 (got %d)",
            self.serve_queue_depth,
        )
        mlsl_assert(
            self.verify_severity in ("error", "warn"),
            "MLSL_VERIFY_SEVERITY must be 'error' or 'warn' (got %r)",
            self.verify_severity,
        )
        mlsl_assert(
            self.lock_witness_budget_ms > 0,
            "MLSL_LOCK_WITNESS_BUDGET_MS must be > 0 (got %s)",
            self.lock_witness_budget_ms,
        )
        mlsl_assert(
            self.metrics_every >= 1,
            "MLSL_METRICS_EVERY must be >= 1 (got %d)", self.metrics_every,
        )
        mlsl_assert(
            0 <= self.metrics_port <= 65535,
            "MLSL_METRICS_PORT must be in [0, 65535] (0 = off; got %d)",
            self.metrics_port,
        )
        mlsl_assert(
            self.metrics_retention >= 2,
            "MLSL_METRICS_RETENTION must be >= 2 (got %d)",
            self.metrics_retention,
        )
        mlsl_assert(
            self.straggler_skew == 0 or self.straggler_skew > 1.0,
            "MLSL_STRAGGLER_SKEW must be 0 (off) or > 1 — a skew ratio at "
            "or below 1 would flag healthy replicas (got %r)",
            self.straggler_skew,
        )
        mlsl_assert(
            self.straggler_every >= 3,
            "MLSL_STRAGGLER_EVERY must be >= 3 (a replica needs 3 window "
            "samples to be judged — a smaller window closes before anyone "
            "is judgeable and silently disables detection; got %d)",
            self.straggler_every,
        )
        mlsl_assert(
            self.straggler_sustain >= 1,
            "MLSL_STRAGGLER_SUSTAIN must be >= 1 (got %d)",
            self.straggler_sustain,
        )
        mlsl_assert(
            self.heartbeat_interval_s > 0,
            "MLSL_HEARTBEAT_INTERVAL_S must be > 0 (got %r)",
            self.heartbeat_interval_s,
        )
        mlsl_assert(
            self.heartbeat_misses >= 1,
            "MLSL_HEARTBEAT_MISSES must be >= 1 (a zero miss budget would "
            "declare every peer dead on the first tick; got %d)",
            self.heartbeat_misses,
        )
        mlsl_assert(
            self.heartbeat_grace_s >= 0,
            "MLSL_HEARTBEAT_GRACE_S must be >= 0 (got %r)",
            self.heartbeat_grace_s,
        )
        mlsl_assert(
            0 <= self.control_port <= 65535,
            "MLSL_CONTROL_PORT must be in [0, 65535] (0 = off; got %d)",
            self.control_port,
        )
        mlsl_assert(
            self.control_world >= 0,
            "MLSL_CONTROL_WORLD must be >= 0 (got %d)", self.control_world,
        )
        mlsl_assert(
            not (self.control_addrs and self.control_world),
            "MLSL_CONTROL_ADDRS and MLSL_CONTROL_PORT/WORLD are mutually "
            "exclusive bootstrap forms — set one",
        )
        if self.control_addrs or self.control_world:
            world = (
                len(self.control_addrs.split(","))
                if self.control_addrs else self.control_world
            )
            mlsl_assert(
                0 <= self.control_rank < world,
                "MLSL_CONTROL_RANK must name this process's slot in the "
                "%d-member control world (got %d)", world, self.control_rank,
            )
        mlsl_assert(
            self.dist_init_retries >= 0,
            "MLSL_DIST_INIT_RETRIES must be >= 0 (got %d)",
            self.dist_init_retries,
        )
        mlsl_assert(
            self.dist_init_backoff_s >= 0,
            "MLSL_DIST_INIT_BACKOFF_S must be >= 0 (got %r)",
            self.dist_init_backoff_s,
        )

    @staticmethod
    def from_env() -> "Config":
        c = Config()
        # Record which knobs the user set EXPLICITLY via MLSL_* env vars:
        # sysinfo.auto_config tunes only the others (explicit always wins,
        # mirroring the reference where MLSL_AUTO_CONFIG never overrides a
        # user-exported variable, src/mlsl.cpp:649-682).
        c._explicit = {
            field for env, field in _ENV_FIELDS.items() if os.environ.get(env)
        }
        c.log_level = _env_int("MLSL_LOG_LEVEL", c.log_level)
        c.dup_group = _env_bool("MLSL_DUP_GROUP", c.dup_group)
        c.enable_stats = _env_bool("MLSL_STATS", c.enable_stats)
        c.auto_config_type = _env_int("MLSL_AUTO_CONFIG_TYPE", c.auto_config_type)
        c.num_servers = _env_int("MLSL_NUM_SERVERS", c.num_servers)
        c.large_msg_size_mb = _env_int("MLSL_LARGE_MSG_SIZE_MB", c.large_msg_size_mb)
        c.large_msg_chunks = _env_int("MLSL_LARGE_MSG_CHUNKS", c.large_msg_chunks)
        c.max_short_msg_size = _env_int("MLSL_MAX_SHORT_MSG_SIZE", c.max_short_msg_size)
        c.gather_device_limit_mb = _env_int(
            "MLSL_GATHER_DEVICE_LIMIT_MB", c.gather_device_limit_mb
        )
        c.grad_bucket_mb = _env_int("MLSL_GRAD_BUCKET_MB", c.grad_bucket_mb)
        c.msg_priority = _env_bool("MLSL_MSG_PRIORITY", c.msg_priority)
        c.msg_priority_threshold = _env_int(
            "MLSL_MSG_PRIORITY_THRESHOLD", c.msg_priority_threshold
        )
        c.msg_priority_mode = _env_bool("MLSL_MSG_PRIORITY_MODE", c.msg_priority_mode)
        c.msg_priority_flush_ms = _env_float(
            "MLSL_MSG_PRIORITY_FLUSH_MS", c.msg_priority_flush_ms
        )
        c.collective_algo = os.environ.get("MLSL_ALGO", c.collective_algo)
        c.tune = _env_bool("MLSL_TUNE", c.tune)
        c.tune_profile = os.environ.get("MLSL_TUNE_PROFILE", c.tune_profile)
        c.feed_wire_dtype = os.environ.get(
            "MLSL_FEED_WIRE_DTYPE", c.feed_wire_dtype
        )
        c.feed_cache_mb = _env_int("MLSL_FEED_CACHE_MB", c.feed_cache_mb)
        c.feed_depth = _env_int("MLSL_FEED_DEPTH", c.feed_depth)
        c.feed_retries = _env_int("MLSL_FEED_RETRIES", c.feed_retries)
        c.serve_max_batch = _env_int("MLSL_SERVE_MAX_BATCH", c.serve_max_batch)
        c.serve_kv_page_elems = _env_int("MLSL_SERVE_KV_PAGE_ELEMS",
                                         c.serve_kv_page_elems)
        c.serve_kv_cache_mb = _env_int("MLSL_SERVE_KV_CACHE_MB",
                                       c.serve_kv_cache_mb)
        c.serve_queue_depth = _env_int("MLSL_SERVE_QUEUE_DEPTH",
                                       c.serve_queue_depth)
        c.serve_kv_quant = _env_bool("MLSL_SERVE_KV_QUANT", c.serve_kv_quant)
        c.overlap_compiled = _env_bool("MLSL_OVERLAP_COMPILED", c.overlap_compiled)
        c.overlap_stages = _env_int("MLSL_OVERLAP_STAGES", c.overlap_stages)
        c.quant_block_elems = _env_int("MLSL_QUANT_BLOCK_ELEMS", c.quant_block_elems)
        c.mesh_tiers = os.environ.get("MLSL_MESH_TIERS", c.mesh_tiers).strip()
        c.hier_dcn_codec = (
            os.environ.get("MLSL_HIER_DCN_CODEC", "").strip().lower()
            or c.hier_dcn_codec
        )
        c.pallas_ring_slots = _env_int("MLSL_PALLAS_RING_SLOTS",
                                       c.pallas_ring_slots)
        c.pallas_ring_bidir = _env_bool("MLSL_PALLAS_RING_BIDIR",
                                        c.pallas_ring_bidir)
        c.pallas_rhd = _env_bool("MLSL_PALLAS_RHD", c.pallas_rhd)
        c.pallas_rhd_max_bytes = _env_int("MLSL_PALLAS_RHD_MAX_BYTES",
                                          c.pallas_rhd_max_bytes)
        c.pallas_a2a_quant = _env_bool("MLSL_PALLAS_A2A_QUANT",
                                       c.pallas_a2a_quant)
        c.pallas_interpret = os.environ.get("MLSL_PALLAS_INTERPRET",
                                            c.pallas_interpret).strip()
        c.topk_ratio = _env_float("MLSL_TOPK_RATIO", c.topk_ratio)
        c.codec = os.environ.get("MLSL_CODEC", c.codec).strip().lower()
        c.tune_codec = _env_bool("MLSL_TUNE_CODEC", c.tune_codec)
        c.codec_nsr_budget = _env_float(
            "MLSL_CODEC_NSR_BUDGET", c.codec_nsr_budget
        )
        c.codec_guard_breaches = _env_int(
            "MLSL_CODEC_GUARD_BREACHES", c.codec_guard_breaches
        )
        c.vq_dim = _env_int("MLSL_VQ_DIM", c.vq_dim)
        c.vq_codebook = _env_int("MLSL_VQ_CODEBOOK", c.vq_codebook)
        c.prune_ratio = _env_float("MLSL_PRUNE_RATIO", c.prune_ratio)
        c.watchdog_timeout_s = _env_float("MLSL_WATCHDOG_TIMEOUT", c.watchdog_timeout_s)
        c.comm_retries = _env_int("MLSL_COMM_RETRIES", c.comm_retries)
        c.comm_retry_backoff_s = _env_float(
            "MLSL_COMM_RETRY_BACKOFF_S", c.comm_retry_backoff_s
        )
        c.breaker_threshold = _env_int("MLSL_BREAKER_THRESHOLD", c.breaker_threshold)
        c.breaker_window_s = _env_float("MLSL_BREAKER_WINDOW_S", c.breaker_window_s)
        c.breaker_cooldown_s = _env_float(
            "MLSL_BREAKER_COOLDOWN_S", c.breaker_cooldown_s
        )
        c.restart_budget = _env_int("MLSL_RESTART_BUDGET", c.restart_budget)
        c.elastic = _env_bool("MLSL_ELASTIC", c.elastic)
        c.capacity_budget = _env_int("MLSL_CAPACITY_BUDGET", c.capacity_budget)
        c.elastic_grow_after = _env_int(
            "MLSL_ELASTIC_GROW_AFTER", c.elastic_grow_after
        )
        c.elastic_admit_retries = _env_int(
            "MLSL_ELASTIC_ADMIT_RETRIES", c.elastic_admit_retries
        )
        c.sentinel_gate = os.environ.get("MLSL_SENTINEL_GATE", c.sentinel_gate)
        c.sentinel_every = _env_int("MLSL_SENTINEL_EVERY", c.sentinel_every)
        c.sentinel_spike = _env_float("MLSL_SENTINEL_SPIKE", c.sentinel_spike)
        c.sentinel_zmax = _env_float("MLSL_SENTINEL_ZMAX", c.sentinel_zmax)
        c.sentinel_warmup = _env_int("MLSL_SENTINEL_WARMUP", c.sentinel_warmup)
        c.sentinel_block = _env_int("MLSL_SENTINEL_BLOCK", c.sentinel_block)
        c.ckpt_save_retries = _env_int("MLSL_CKPT_SAVE_RETRIES", c.ckpt_save_retries)
        c.ckpt_retry_backoff_s = _env_float(
            "MLSL_CKPT_RETRY_BACKOFF_S", c.ckpt_retry_backoff_s
        )
        c.metrics = _env_bool("MLSL_METRICS", c.metrics)
        c.metrics_every = _env_int("MLSL_METRICS_EVERY", c.metrics_every)
        c.metrics_port = _env_int("MLSL_METRICS_PORT", c.metrics_port)
        c.metrics_retention = _env_int(
            "MLSL_METRICS_RETENTION", c.metrics_retention
        )
        c.straggler_skew = _env_float("MLSL_STRAGGLER_SKEW", c.straggler_skew)
        c.straggler_every = _env_int(
            "MLSL_STRAGGLER_EVERY", c.straggler_every
        )
        c.straggler_sustain = _env_int(
            "MLSL_STRAGGLER_SUSTAIN", c.straggler_sustain
        )
        c.straggler_shed = _env_bool("MLSL_STRAGGLER_SHED", c.straggler_shed)
        c.profile_on_trip = _env_bool(
            "MLSL_PROFILE_ON_TRIP", c.profile_on_trip
        )
        c.heartbeat_interval_s = _env_float(
            "MLSL_HEARTBEAT_INTERVAL_S", c.heartbeat_interval_s
        )
        c.heartbeat_misses = _env_int(
            "MLSL_HEARTBEAT_MISSES", c.heartbeat_misses
        )
        c.heartbeat_grace_s = _env_float(
            "MLSL_HEARTBEAT_GRACE_S", c.heartbeat_grace_s
        )
        c.preemption_file = os.environ.get(
            "MLSL_PREEMPTION_FILE", c.preemption_file
        )
        c.control_addrs = os.environ.get(
            "MLSL_CONTROL_ADDRS", c.control_addrs
        )
        c.control_port = _env_int("MLSL_CONTROL_PORT", c.control_port)
        c.control_world = _env_int("MLSL_CONTROL_WORLD", c.control_world)
        c.control_rank = _env_int("MLSL_CONTROL_RANK", c.control_rank)
        c.dist_init_retries = _env_int(
            "MLSL_DIST_INIT_RETRIES", c.dist_init_retries
        )
        c.dist_init_backoff_s = _env_float(
            "MLSL_DIST_INIT_BACKOFF_S", c.dist_init_backoff_s
        )
        c.verify = _env_bool("MLSL_VERIFY", c.verify)
        c.verify_severity = os.environ.get(
            "MLSL_VERIFY_SEVERITY", c.verify_severity
        ).strip().lower() or c.verify_severity
        c.lock_witness = _env_bool("MLSL_LOCK_WITNESS", c.lock_witness)
        c.lock_witness_budget_ms = _env_float(
            "MLSL_LOCK_WITNESS_BUDGET_MS", c.lock_witness_budget_ms
        )
        c.chaos_spec = os.environ.get("MLSL_CHAOS", c.chaos_spec)
        c.trace = _env_bool("MLSL_TRACE", c.trace)
        c.trace_dir = os.environ.get("MLSL_TRACE_DIR", c.trace_dir)
        c.trace_capacity = _env_int("MLSL_TRACE_CAPACITY", c.trace_capacity)
        c.precompile = _env_bool("MLSL_PRECOMPILE", c.precompile)
        c.server_affinity = os.environ.get("MLSL_SERVER_AFFINITY", c.server_affinity)
        c.heap_size_gb = _env_int("MLSL_HEAP_SIZE_GB", c.heap_size_gb)
        c.alltoall_split = _env_int("MLSL_ALLTOALL_SPLIT", c.alltoall_split)
        c.thp_threshold_mb = _env_int("MLSL_THP_THRESHOLD_MB", c.thp_threshold_mb)
        return c
