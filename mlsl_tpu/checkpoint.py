"""Checkpoint/resume: orbax-backed persistence of training state.

The reference has no checkpointing (SURVEY.md §5.4 — MLSL only moves bytes; its
closest artifact is the endpoint-server async file-IO offload). A *framework* needs
one, so this module provides it TPU-natively: async orbax saves (the save executes in
the background while training continues — the same overlap idea as eplib's offloaded
file reads), sharding-preserving restore, and trainer integration.

Hardened for production faults (the chaos layer exercises every path below,
tests/test_chaos.py):

- **Async errors surface.** A failed background save must never be mistaken for
  a committed resume point: ``save()``/``wait()`` run orbax's
  ``check_for_errors`` and re-raise.
- **Checksum manifests.** Every committed step gets a ``manifest-<step>.json``
  of per-file sha256 sums written alongside it; ``verify()`` detects bit-rot.
- **Verified fallback.** ``restore_trainer`` walks steps newest-first and skips
  any step that fails verification (or whose restore raises), resuming from the
  newest *verified* step instead of dying on a corrupt latest.
- **Save retry.** Transient IO errors (OSError) during save dispatch retry with
  exponential backoff (MLSL_CKPT_SAVE_RETRIES / MLSL_CKPT_RETRY_BACKOFF_S).
- **Verified-good steps.** A save made with a passing sentinel audit
  fingerprint (mlsl_tpu.sentinel) records it in the step manifest;
  ``restore_trainer`` prefers the newest VERIFIED step over newer
  unverified ones, so a silently corrupted checkpoint is never the
  preferred resume point once any verified one exists.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Any, List, Optional

import jax
import orbax.checkpoint as ocp

from mlsl_tpu import chaos
from mlsl_tpu.config import _env_float, _env_int
from mlsl_tpu.log import MLSLError, log_info, log_warning
from mlsl_tpu.obs import tracer as obs


class CheckpointManager:
    """Save/restore pytrees of (possibly sharded) jax.Arrays by step number."""

    def __init__(
        self,
        directory: str,
        max_to_keep: int = 3,
        save_retries: Optional[int] = None,
        retry_backoff_s: Optional[float] = None,
    ):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.save_retries = (
            _env_int("MLSL_CKPT_SAVE_RETRIES", 3)
            if save_retries is None
            else save_retries
        )
        self.retry_backoff_s = (
            _env_float("MLSL_CKPT_RETRY_BACKOFF_S", 0.05)
            if retry_backoff_s is None
            else retry_backoff_s
        )
        self._unverified: set = set()  # steps saved but not yet checksummed
        self._bitrot: set = set()      # chaos: steps to corrupt post-manifest
        # step -> passing sentinel audit digest, recorded into the step's
        # manifest at flush (the "verified-good" half of the integrity
        # sentinel: restore_trainer prefers steps that carry one)
        self._fingerprints: dict = {}
        # step -> active world size at save time (elastic mesh): a
        # checkpoint saved on a shrunk world carries ZeRO-1 shard shapes a
        # different world cannot restore — the manifest records the size so
        # restore_trainer can NAME the mismatch instead of surfacing an
        # opaque shape error
        self._worlds: dict = {}
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep, enable_async_checkpointing=True
            ),
        )

    # -- async-error surfacing --------------------------------------------

    def check_errors(self) -> None:
        """Surface a failed background save (orbax ``check_for_errors``) — a
        silent async failure would otherwise let the caller believe the step
        is a committed resume point."""
        chk = getattr(self._mgr, "check_for_errors", None)
        if chk is not None:
            chk()

    # -- save/restore ------------------------------------------------------

    def save(self, step: int, state: Any, wait: bool = False,
             fingerprint: Optional[str] = None,
             world: Optional[int] = None) -> None:
        """Dispatch an async save of ``state`` (any pytree of arrays).

        ``fingerprint`` is a PASSING sentinel audit digest of this state
        (mlsl_tpu.sentinel); it is recorded in the step's manifest, marking
        the step *verified* — ``restore_trainer`` prefers verified steps and
        FaultTolerantLoop's post-restore re-audit compares against it.
        ``world`` is the active world size at save time (elastic mesh),
        recorded in the manifest for restore-time mismatch diagnosis.

        Transient IO errors (OSError) at dispatch retry with exponential
        backoff; anything else propagates (recoverable by FaultTolerantLoop).
        """
        self.check_errors()
        if fingerprint is not None:
            self._fingerprints[step] = fingerprint
        if world is not None:
            self._worlds[step] = int(world)
        tr = obs._tracer
        t0 = tr.now() if tr is not None else 0
        delay = self.retry_backoff_s
        for attempt in range(self.save_retries + 1):
            try:
                # chaos first: an injected OSError exercises the same retry
                # path a flaky filesystem would
                plan = chaos.inject("checkpoint.save", step=step, attempt=attempt)
                if plan is not None and plan.kind == "bitrot":
                    self._bitrot.add(step)
                self._mgr.save(step, args=ocp.args.StandardSave(state))
                break
            except OSError as e:
                if attempt >= self.save_retries:
                    raise
                if tr is not None:
                    tr.instant("ckpt.save.retry", "ckpt", step=step,
                               attempt=attempt + 1, error=repr(e))
                log_warning(
                    "checkpoint save of step %d failed (%s: %s); "
                    "retry %d/%d in %.2fs",
                    step, type(e).__name__, e,
                    attempt + 1, self.save_retries, delay,
                )
                time.sleep(delay)
                delay *= 2
        self._unverified.add(step)
        if tr is not None:
            # dispatch span only: the orbax write itself runs async in the
            # background — its drain lands in the wait() span below
            tr.complete("ckpt.save", "ckpt", t0, step=step, attempts=attempt + 1)
        if wait:
            self.wait()
        # async path: manifests are checksummed at the next drain point
        # (wait()/close()/restore) — never inline on the training hot path,
        # which would stall exactly the overlap the async save buys

    def restore(self, step: Optional[int] = None, template: Any = None) -> Any:
        """Restore the given (or latest) step. ``template`` — a pytree of arrays or
        ShapeDtypeStructs with shardings — reproduces the original placement."""
        if step is None:
            step = self.latest_step()
        if step is None:
            return None
        chaos.inject("checkpoint.restore", step=step)
        tr = obs._tracer
        t0 = tr.now() if tr is not None else 0
        if template is not None:
            target = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=getattr(x, "sharding", None))
                if hasattr(x, "shape")
                else x,
                template,
            )
            out = self._mgr.restore(step, args=ocp.args.StandardRestore(target))
        else:
            out = self._mgr.restore(step)
        if tr is not None:
            tr.complete("ckpt.restore", "ckpt", t0, step=step)
        return out

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def all_steps(self) -> List[int]:
        return sorted(self._mgr.all_steps())

    def wait(self) -> None:
        tr = obs._tracer
        t0 = tr.now() if tr is not None else 0
        self._mgr.wait_until_finished()
        self.check_errors()
        self._flush_manifests()
        if tr is not None:
            tr.complete("ckpt.drain", "ckpt", t0)

    def close(self) -> None:
        self._mgr.wait_until_finished()
        self.check_errors()  # a failed final save must not vanish at close
        self._flush_manifests()
        self._mgr.close()

    # -- checksum manifests ------------------------------------------------

    def _manifest_path(self, step: int) -> str:
        return os.path.join(self.directory, f"manifest-{step}.json")

    def _step_dir(self, step: int) -> Optional[str]:
        """The committed step directory, or None while the save is in flight
        (orbax renames the tmp dir into place only on commit)."""
        cand = os.path.join(self.directory, str(step))
        if os.path.isdir(cand):
            return cand
        for name in os.listdir(self.directory):  # non-default step formats
            p = os.path.join(self.directory, name)
            if (
                os.path.isdir(p)
                and "tmp" not in name
                and name.rsplit("_", 1)[-1] == str(step)
            ):
                return p
        return None

    @staticmethod
    def _file_sha256(path: str) -> str:
        h = hashlib.sha256()
        with open(path, "rb") as f:
            for block in iter(lambda: f.read(1 << 20), b""):
                h.update(block)
        return h.hexdigest()

    def _checksum_tree(self, root: str) -> dict:
        files = {}
        for base, _, names in os.walk(root):
            for n in sorted(names):
                p = os.path.join(base, n)
                files[os.path.relpath(p, root)] = self._file_sha256(p)
        return files

    def _flush_manifests(self) -> None:
        """Write ``manifest-<step>.json`` for every save that has committed
        since the last flush, then apply any chaos bit-rot (after the manifest,
        as real rot happens: the manifest records the good bytes, so verify()
        catches the corruption)."""
        live = set(self._mgr.all_steps())
        newest = max(live) if live else None
        for step in sorted(self._unverified):
            d = self._step_dir(step)
            if (
                step not in live
                and d is None
                and newest is not None
                and step < newest
            ):
                # only an OLDER step missing from both the registry and the
                # filesystem was reaped by max_to_keep; the newest save may
                # simply not be listed/committed yet
                self._unverified.discard(step)
                continue
            if d is None:
                continue  # still in flight
            manifest = {"step": step, "written_at": time.time(),
                        "files": self._checksum_tree(d)}
            w = self._worlds.pop(step, None)
            if w is not None:
                manifest["world"] = w
            fp = self._fingerprints.pop(step, None)
            if fp is not None:
                # verified-good marker: the state in this step passed the
                # sentinel's consistency audit at save time, and this digest
                # identifies those exact bytes (post-restore re-audit target)
                manifest["sentinel"] = {"fingerprint": fp}
            tmp = self._manifest_path(step) + ".tmp"
            with open(tmp, "w") as f:
                json.dump(manifest, f)
            os.replace(tmp, self._manifest_path(step))
            self._unverified.discard(step)
            if step in self._bitrot:
                self._bitrot.discard(step)
                self._apply_bitrot(step, d)
        # drop manifests whose step was garbage-collected
        for name in os.listdir(self.directory):
            if name.startswith("manifest-") and name.endswith(".json"):
                try:
                    s = int(name[len("manifest-"):-len(".json")])
                except ValueError:
                    continue
                if s not in live and s not in self._unverified:
                    try:
                        os.remove(os.path.join(self.directory, name))
                    except OSError:
                        pass

    def _apply_bitrot(self, step: int, step_dir: str) -> None:
        """Chaos 'bitrot' kind: flip bytes in the largest payload file of a
        committed checkpoint, simulating on-disk corruption after a clean
        write. verify() must subsequently fail for this step."""
        target, size = None, -1
        for base, _, names in os.walk(step_dir):
            for n in names:
                p = os.path.join(base, n)
                sz = os.path.getsize(p)
                if sz > size:
                    target, size = p, sz
        if target is None:
            return
        with open(target, "r+b") as f:
            f.seek(size // 2)
            chunk = f.read(64) or b"\0"
            f.seek(size // 2)
            f.write(bytes(b ^ 0xFF for b in chunk))
        log_warning("chaos: bit-rot injected into step %d (%s)", step, target)

    def recorded_fingerprint(self, step: int) -> Optional[str]:
        """The sentinel audit digest this step's manifest records, or None
        (no manifest yet, or the step was saved without one — an unverified
        checkpoint)."""
        fp = self._fingerprints.get(step)
        if fp is not None:
            return fp  # save dispatched, manifest not yet flushed
        try:
            with open(self._manifest_path(step)) as f:
                manifest = json.load(f)
        except (OSError, ValueError):
            return None
        return (manifest.get("sentinel") or {}).get("fingerprint")

    def recorded_world(self, step: int) -> Optional[int]:
        """The active world size this step's manifest records, or None (no
        manifest, or a pre-elastic save)."""
        w = self._worlds.get(step)
        if w is not None:
            return w
        try:
            with open(self._manifest_path(step)) as f:
                manifest = json.load(f)
        except (OSError, ValueError):
            return None
        w = manifest.get("world")
        return int(w) if w is not None else None

    def verify(self, step: int) -> Optional[bool]:
        """True: manifest present and every file matches. False: corrupt
        (mismatch, missing file, or unreadable manifest). None: no manifest
        (pre-manifest checkpoint or a save that never committed cleanly)."""
        mp = self._manifest_path(step)
        if not os.path.exists(mp):
            return None
        try:
            with open(mp) as f:
                manifest = json.load(f)
        except (OSError, ValueError):
            return False
        d = self._step_dir(step)
        if d is None:
            return False
        for rel, want in manifest.get("files", {}).items():
            p = os.path.join(d, rel)
            try:
                if self._file_sha256(p) != want:
                    return False
            except OSError:
                return False
        return True


def _trainer_state(trainer, step: int) -> dict:
    state = {"params": trainer.params, "step": step}
    # optax state (replicated and/or ZeRO-1 owned-shard buffers) must resume
    # with the params — restarting Adam from zero moments silently diverges
    # the trajectory.
    if getattr(trainer, "_opt_state", None) is not None:
        state["opt_state"] = trainer._opt_state
    if getattr(trainer, "_du_opt_state", None) is not None:
        state["du_opt_state"] = trainer._du_opt_state
    return state


def _apply_state(trainer, state) -> int:
    trainer.params = state["params"]
    if "opt_state" in state:
        trainer._opt_state = state["opt_state"]
    if "du_opt_state" in state:
        trainer._du_opt_state = state["du_opt_state"]
    return int(state["step"])


def _trainer_world(trainer) -> Optional[int]:
    mesh = getattr(trainer, "mesh", None)
    return int(mesh.devices.size) if mesh is not None else None


def save_trainer(mgr: CheckpointManager, trainer, step: int, wait: bool = False,
                 fingerprint: Optional[str] = None) -> None:
    """Persist a DataParallelTrainer/HybridTrainer's parameters (and optimizer
    state, when the trainer carries one). ``fingerprint`` marks the step
    sentinel-verified (see CheckpointManager.save); the active world size
    rides in the manifest so a cross-world restore names its mismatch."""
    mgr.save(step, _trainer_state(trainer, step), wait=wait,
             fingerprint=fingerprint, world=_trainer_world(trainer))


def restore_trainer(mgr: CheckpointManager, trainer, step: Optional[int] = None) -> Optional[int]:
    """Restore parameters (and optimizer state) in place; returns the restored
    step or None when the directory holds no checkpoints.

    With ``step=None`` the candidate order is newest VERIFIED first:
    steps whose manifest records a passing sentinel audit fingerprint
    (newest to oldest), then unverified steps (newest to oldest) — a
    checkpoint that might hold silently corrupted state is only used when
    no verified one restores. Within that order, a step that fails checksum
    verification, or whose restore raises, is skipped with a warning and
    the next candidate is tried — a corrupt latest checkpoint costs a
    longer replay, not the run. If checkpoints exist but none restores,
    raise (silently restarting from scratch would discard the entire run's
    progress)."""
    template = _trainer_state(trainer, 0)
    if step is not None:
        state = mgr.restore(step, template=template)
        return None if state is None else _apply_state(trainer, state)
    steps = mgr.all_steps()
    if not steps:
        return None
    mgr._flush_manifests()  # checksum anything committed-but-unverified
    newest_first = sorted(steps, reverse=True)
    verified = [s for s in newest_first if mgr.recorded_fingerprint(s)]
    unverified = [s for s in newest_first if s not in verified]
    if verified and unverified and unverified[0] > verified[0]:
        log_warning(
            "preferring newest VERIFIED checkpoint step %d over newer "
            "unverified step %d (no passing audit fingerprint recorded)",
            verified[0], unverified[0],
        )
    world_now = _trainer_world(trainer)
    for s in verified + unverified:
        verdict = mgr.verify(s)
        if verdict is False:
            log_warning(
                "checkpoint step %d fails checksum verification; falling back", s
            )
            continue
        w = mgr.recorded_world(s)
        if w is not None and world_now is not None and w != world_now:
            # elastic mesh: the step was saved at a different world size.
            # Replicated-only state restores anyway (and a same-shape ZeRO-1
            # layout would too), so still TRY — but name the mismatch first,
            # because the opaque alternative is an orbax shape error
            log_warning(
                "checkpoint step %d was saved at world size %d but the "
                "active world is %d (elastic reshard between save and "
                "restore); ZeRO-1 shard shapes may not restore", s, w,
                world_now,
            )
        try:
            state = mgr.restore(s, template=template)
        except Exception as e:
            log_warning(
                "restore of checkpoint step %d failed (%s: %s); falling back",
                s, type(e).__name__, e,
            )
            continue
        if state is None:
            continue
        if s != newest_first[0]:
            log_info("restored fallback step %d (latest step %d unusable)",
                     s, newest_first[0])
        return _apply_state(trainer, state)
    raise MLSLError(
        f"no restorable checkpoint in {mgr.directory}: all {len(steps)} steps "
        "are corrupt or unreadable"
    )
