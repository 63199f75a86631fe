"""Tuner profile: the persisted selection table + tuned knob set.

A profile is one JSON document keyed by a ``sysinfo`` topology fingerprint
(platform, chip generation, world size, host spread). Cells map
(kind, group shape, compression, payload band) -> algorithm name; knobs are
whole-config values (chunk/bucket/priority/quant-block) the sweep measured.
Both carry the raw measurements they were derived from, so an operator can
audit WHY a cell picked its algorithm (docs/TUNING.md §10).

Load contract (the config-validation satellite): a missing or corrupt file
is an immediate ``MLSLError`` — pointing MLSL_TUNE_PROFILE at garbage must
fail at init, not deep in dispatch. A well-formed profile whose fingerprint
disagrees with the probed hardware is STALE: rejected with a warning and the
untuned defaults keep running (measurements do not transfer across
machines).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import List, Optional, Tuple

from mlsl_tpu.log import MLSLError

PROFILE_VERSION = 1
DEFAULT_PROFILE_FILE = "mlsl_tune_profile.json"

#: knob name -> minimum legal value: the Config fields a profile's knob
#: table may set (anything else under "knobs" is measurement metadata,
#: ignored on apply). Checked at LOAD time — a profile file with a
#: nonsensical knob must fail with an MLSLError naming the file, not deep
#: inside the first collective that consumes the knob (the same
#: fail-at-init contract as Config.validate()).
KNOB_RANGES = {
    "msg_priority_threshold": 1,
    "grad_bucket_mb": 0,
    "large_msg_size_mb": 0,
    "large_msg_chunks": 1,
    "quant_block_elems": 1,
    # pallas-ring comm slots per direction (ops/ring_kernels.py): profiles
    # may carry a measured double-buffer depth for this machine's ICI; an
    # exported MLSL_PALLAS_RING_SLOTS always wins
    "pallas_ring_slots": 2,
    # latency-class allreduce payload band (ops/rhd_kernels.py): profiles
    # may carry the measured rhd/ring crossover in bytes for this fabric
    # (0 = derive from msg_priority_threshold); an exported
    # MLSL_PALLAS_RHD_MAX_BYTES always wins
    "pallas_rhd_max_bytes": 0,
    # fused-alltoall wire codec (ops/a2a_kernels.py): 1 = int8 blockwise,
    # 0 = dense f32 variant of the same kernel. Carried as 0/1 (the range
    # table rejects bools); an exported MLSL_PALLAS_A2A_QUANT always wins
    "pallas_a2a_quant": 0,
    # compiled-overlap staging depth (comm/overlap.py): profiles may carry
    # the measured number of unit-starts a layer's reduce phases spread
    # over; an exported MLSL_OVERLAP_STAGES always wins
    "overlap_stages": 1,
    # feed-pipeline prefetch depth (mlsl_tpu.data): profiles may carry the
    # depth measured best for this machine's h2d link (a training cell's
    # `input_stall_ms_per_step`, perf/); an exported MLSL_FEED_DEPTH
    # always wins
    "feed_depth": 1,
    # integrity-sentinel audit interval (mlsl_tpu.sentinel): profiles may
    # carry the interval measured to keep gate+audit overhead under its
    # budget on this machine; an exported MLSL_SENTINEL_EVERY always wins
    # (0 = audit off)
    "sentinel_every": 0,
    # telemetry sampler cadence (obs/metrics.py): profiles may carry the
    # cadence measured to keep the armed-path cost under its budget on
    # this machine; an exported MLSL_METRICS_EVERY always wins
    "metrics_every": 1,
    # straggler audit window (obs/straggler.py): an exported
    # MLSL_STRAGGLER_EVERY always wins; floor = the judgeable minimum
    # (MIN_WINDOW_SAMPLES — below it no replica is ever judged)
    "straggler_every": 3,
    # heartbeat miss budget (control/plane.py): profiles may carry the
    # consecutive-miss count measured to cover this pod's worst GC/compile
    # pause without false-declaring a host dead (each extra miss delays
    # real-failure detection by one MLSL_HEARTBEAT_INTERVAL_S); an exported
    # MLSL_HEARTBEAT_MISSES always wins
    "heartbeat_misses": 1,
    # codec-lab knobs (mlsl_tpu.codecs; docs/TUNING.md §22): calibration
    # may carry whole-run codec parameters alongside the per-set assignment
    # table; exported MLSL_VQ_* / MLSL_PRUNE_RATIO always win
    "vq_dim": 1,
    "vq_codebook": 2,
    "prune_ratio": 1e-4,
    # serving decode-slot ceiling (serve/engine.py): profiles may carry the
    # batch measured to maximize tokens/s while holding the inter-token
    # latency on this chip (perf/sweep.py walks a serving cell's rate);
    # an exported MLSL_SERVE_MAX_BATCH always wins
    "serve_max_batch": 1,
    # KV page granularity in tokens (serve/kv_cache.py): profiles may carry
    # the page size measured to balance HBM tail waste against page-table
    # gather cost; an exported MLSL_SERVE_KV_PAGE_ELEMS always wins
    "serve_kv_page_elems": 1,
    # paged-KV HBM budget in MiB (serve/kv_cache.py): profiles may carry
    # the budget measured to fit this chip's free HBM after weights; an
    # exported MLSL_SERVE_KV_CACHE_MB always wins
    "serve_kv_cache_mb": 1,
    # admission queue depth (serve/engine.py): profiles may carry the depth
    # measured to absorb offered-load bursts without breaching TTFT; an
    # exported MLSL_SERVE_QUEUE_DEPTH always wins
    "serve_queue_depth": 1,
}

#: string-valued knobs -> allowed values: same load-time validation contract
#: as KNOB_RANGES, for knobs that pick a variant rather than a magnitude
KNOB_CHOICES = {
    # DCN-tier codec for the 'hier' lowering (comm/algos/hier.py): profiles
    # tuned on a two-tier mesh may carry the codec that measured best on
    # its DCN; an exported MLSL_HIER_DCN_CODEC always wins. Registry codecs
    # (mlsl_tpu.codecs) are legal DCN members since the codec-lab PR.
    "hier_dcn_codec": ("int8", "f32", "topk", "vq", "prune"),
}


def default_profile_path() -> str:
    """Where an unnamed profile lands: ``MLSL_STATS_DIR`` (default CWD), the
    same routing contract as mlsl_stats.log (core/stats.stats_path)."""
    d = os.environ.get("MLSL_STATS_DIR")
    return os.path.join(d, DEFAULT_PROFILE_FILE) if d else DEFAULT_PROFILE_FILE


@dataclasses.dataclass
class TunedProfile:
    """In-memory form of one profile document."""

    fingerprint: dict
    cells: List[dict] = dataclasses.field(default_factory=list)
    knobs: dict = dataclasses.field(default_factory=dict)
    created: str = ""
    # codec-lab calibration table (tuner/calibrate.py; docs/TUNING.md §22):
    # request name -> {"codec": registry name, "block": int8 block or 0,
    # "params": codec knobs, "nsr": measured noise-to-signal, "wire_bytes":
    # per-round compressed image}. Absent in pre-codec-lab profiles — the
    # loader tolerates a missing section (older files keep loading).
    codecs: dict = dataclasses.field(default_factory=dict)

    # -- selection ---------------------------------------------------------

    def select(
        self,
        kind: str,
        shape: Tuple[int, ...],
        compression,
        payload_bytes: int,
    ) -> Optional[str]:
        """Tuned algorithm for (kind, group shape, compression, payload), or
        None when no cell covers it (the caller falls back to the heuristic
        default). Cells are size-banded: the matching cell is the smallest
        ``max_bytes`` band that still covers the payload; a cell with
        ``max_bytes: null`` is the open top band."""
        comp = _comp_name(compression)
        shape = tuple(int(s) for s in shape)
        best = None
        best_cap = None
        for cell in self.cells:
            if cell.get("kind") != kind or _comp_name(cell.get("compression", "none")) != comp:
                continue
            if tuple(int(s) for s in cell.get("shape", ())) != shape:
                continue
            cap = cell.get("max_bytes")
            if cap is not None and payload_bytes > cap:
                continue
            if best is None or (cap is not None and (best_cap is None or cap < best_cap)):
                best, best_cap = cell, cap
        return best.get("algo") if best else None

    def matches(self, fingerprint: dict) -> bool:
        return dict(self.fingerprint) == dict(fingerprint)

    # -- persistence -------------------------------------------------------

    def to_doc(self) -> dict:
        doc = {
            "version": PROFILE_VERSION,
            "fingerprint": self.fingerprint,
            "created": self.created,
            "cells": self.cells,
            "knobs": self.knobs,
        }
        if self.codecs:
            doc["codecs"] = self.codecs
        return doc

    def save(self, path: str) -> str:
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self.to_doc(), f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)  # atomic: a reader never sees a half-written file
        return path


def _comp_name(compression) -> str:
    if isinstance(compression, str):
        return compression
    from mlsl_tpu.types import CompressionType

    try:
        return CompressionType(compression).name.lower()
    except ValueError:
        return str(compression)


def load_profile(path: str) -> TunedProfile:
    """Parse a profile file; MLSLError on missing/corrupt/unknown-version —
    the fail-at-init contract for MLSL_TUNE_PROFILE."""
    if not os.path.exists(path):
        raise MLSLError(
            f"MLSL_TUNE_PROFILE points at a missing file: {path} "
            f"(run MLSL_TUNE=1 or scripts/run_tune.sh to produce one)"
        )
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        raise MLSLError(
            f"MLSL_TUNE_PROFILE file {path} is unreadable or corrupt: {e!r}"
        ) from e
    if not isinstance(doc, dict) or "fingerprint" not in doc or "cells" not in doc:
        raise MLSLError(
            f"MLSL_TUNE_PROFILE file {path} is not a tuner profile "
            f"(missing fingerprint/cells)"
        )
    if doc.get("version") != PROFILE_VERSION:
        raise MLSLError(
            f"MLSL_TUNE_PROFILE file {path} has unsupported version "
            f"{doc.get('version')!r} (this build reads version {PROFILE_VERSION})"
        )
    cells = doc["cells"]
    if not isinstance(cells, list) or not all(isinstance(c, dict) for c in cells):
        raise MLSLError(f"MLSL_TUNE_PROFILE file {path} has a malformed cell table")
    from mlsl_tpu.comm import algos

    for cell in cells:
        if cell.get("algo") not in algos.ALGORITHMS:
            raise MLSLError(
                f"MLSL_TUNE_PROFILE file {path} names unknown algorithm "
                f"{cell.get('algo')!r} (registry: {', '.join(algos.ALGORITHMS)})"
            )
    knobs = doc.get("knobs", {}) or {}
    for name, lo in KNOB_RANGES.items():
        v = knobs.get(name)
        if v is None:
            continue
        if isinstance(v, bool) or not isinstance(v, (int, float)) or v < lo:
            raise MLSLError(
                f"MLSL_TUNE_PROFILE file {path} has invalid knob "
                f"{name}={v!r} (expected a number >= {lo})"
            )
    for name, allowed in KNOB_CHOICES.items():
        v = knobs.get(name)
        if v is not None and v not in allowed:
            raise MLSLError(
                f"MLSL_TUNE_PROFILE file {path} has invalid knob "
                f"{name}={v!r} (expected one of {', '.join(allowed)})"
            )
    codec_cells = doc.get("codecs", {}) or {}
    if not isinstance(codec_cells, dict) or not all(
        isinstance(k, str) and isinstance(v, dict) and isinstance(v.get("codec"), str)
        for k, v in codec_cells.items()
    ):
        raise MLSLError(
            f"MLSL_TUNE_PROFILE file {path} has a malformed codecs table "
            f"(expected request name -> {{'codec': name, ...}})"
        )
    from mlsl_tpu import codecs as codecs_mod

    for rname, cell in codec_cells.items():
        if cell["codec"] not in codecs_mod.names():
            raise MLSLError(
                f"MLSL_TUNE_PROFILE file {path} assigns unknown codec "
                f"{cell['codec']!r} to {rname!r} "
                f"(registry: {', '.join(codecs_mod.names())})"
            )
    return TunedProfile(
        fingerprint=doc["fingerprint"],
        cells=cells,
        knobs=knobs,
        created=str(doc.get("created", "")),
        codecs=codec_cells,
    )
